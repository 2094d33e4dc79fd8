"""TPU WGL kernel golden tests: the device checker must agree with the host
oracle on every history (the SURVEY's 'golden tests for the TPU kernels:
same history arrays in, same verdicts out')."""

import heapq
import random
from pathlib import Path

import numpy as np
import pytest

from jepsen_tpu import models
from jepsen_tpu import models as m
from jepsen_tpu.checker import synth
from jepsen_tpu.checker.linear import analysis_host
from jepsen_tpu.checker import wgl
from jepsen_tpu.checker.wgl import (SlotOverflow, analysis_tpu,
                                    analysis_tpu_batch, build_steps,
                                    check_batch_sharded,
                                    encode_ops_for_model)
from jepsen_tpu.history import (KIND_INFO, KIND_OK, NIL, PENDING_RET,
                                History, OpArray)


def op(type, f, value, process=0, time=0):
    return {"type": type, "f": f, "value": value, "process": process,
            "time": time}


SMALL = dict(frontier=128, slots=32)


# -- literal corpus (mirrors test_linear_host) -------------------------------

CORPUS = [
    ("valid write-read", True, [
        op("invoke", "write", 1, 0), op("ok", "write", 1, 0),
        op("invoke", "read", None, 0), op("ok", "read", 1, 0)]),
    ("stale read", False, [
        op("invoke", "write", 1, 0), op("ok", "write", 1, 0),
        op("invoke", "read", None, 0), op("ok", "read", 2, 0)]),
    ("concurrent read old", True, [
        op("invoke", "write", 0, 0), op("ok", "write", 0, 0),
        op("invoke", "write", 1, 0),
        op("invoke", "read", None, 1), op("ok", "read", 0, 1),
        op("ok", "write", 1, 0)]),
    ("concurrent read new", True, [
        op("invoke", "write", 0, 0), op("ok", "write", 0, 0),
        op("invoke", "write", 1, 0),
        op("invoke", "read", None, 1), op("ok", "read", 1, 1),
        op("ok", "write", 1, 0)]),
    ("read after second write", False, [
        op("invoke", "write", 1, 0), op("ok", "write", 1, 0),
        op("invoke", "write", 2, 0), op("ok", "write", 2, 0),
        op("invoke", "read", 1, 1), op("ok", "read", 1, 1)]),
    ("crashed write applied", True, [
        op("invoke", "write", 1, 0), op("ok", "write", 1, 0),
        op("invoke", "write", 2, 1), op("info", "write", 2, 1),
        op("invoke", "read", None, 2), op("ok", "read", 2, 2)]),
    ("crashed write skipped", True, [
        op("invoke", "write", 1, 0), op("ok", "write", 1, 0),
        op("invoke", "write", 2, 1), op("info", "write", 2, 1),
        op("invoke", "read", None, 2), op("ok", "read", 1, 2)]),
    ("failed write must not apply", False, [
        op("invoke", "write", 1, 0), op("ok", "write", 1, 0),
        op("invoke", "write", 2, 1), op("fail", "write", 2, 1),
        op("invoke", "read", None, 2), op("ok", "read", 2, 2)]),
    ("cas chain", True, [
        op("invoke", "write", 1, 0), op("ok", "write", 1, 0),
        op("invoke", "cas", (1, 3), 1), op("ok", "cas", (1, 3), 1),
        op("invoke", "read", None, 0), op("ok", "read", 3, 0)]),
    ("impossible cas", False, [
        op("invoke", "write", 1, 0), op("ok", "write", 1, 0),
        op("invoke", "cas", (2, 3), 1), op("ok", "cas", (2, 3), 1)]),
    ("two concurrent writes read first", True, [
        op("invoke", "write", 1, 0),
        op("invoke", "write", 2, 1),
        op("ok", "write", 1, 0),
        op("ok", "write", 2, 1),
        op("invoke", "read", None, 2), op("ok", "read", 1, 2)]),
    ("late read of crashed write", True, [
        op("invoke", "write", 1, 0), op("ok", "write", 1, 0),
        op("invoke", "write", 9, 3), op("info", "write", 9, 3),
        op("invoke", "write", 2, 0), op("ok", "write", 2, 0),
        op("invoke", "read", None, 1), op("ok", "read", 9, 1)]),
    ("empty", True, []),
]


@pytest.mark.parametrize("name,expect,ops",
                         CORPUS, ids=[c[0] for c in CORPUS])
def test_corpus_register(name, expect, ops):
    hist = History(ops)
    a = analysis_tpu(m.cas_register(), hist, **SMALL)
    assert a["valid?"] is expect, a
    # and it agrees with the host oracle
    assert analysis_host(m.cas_register(), hist)["valid?"] is expect


def test_failing_op_diagnosis():
    hist = History([
        op("invoke", "write", 1, 0), op("ok", "write", 1, 0),
        op("invoke", "read", None, 1), op("ok", "read", 2, 1)])
    a = analysis_tpu(m.cas_register(), hist, **SMALL)
    assert a["valid?"] is False
    assert a["op"]["f"] == "read" and a["op"]["value"] == 2


def test_mutex_on_device():
    good = History([
        op("invoke", "acquire", None, 0), op("ok", "acquire", None, 0),
        op("invoke", "release", None, 0), op("ok", "release", None, 0),
        op("invoke", "acquire", None, 1), op("ok", "acquire", None, 1)])
    assert analysis_tpu(m.mutex(), good, **SMALL)["valid?"] is True
    bad = History([
        op("invoke", "acquire", None, 0), op("ok", "acquire", None, 0),
        op("invoke", "acquire", None, 1), op("ok", "acquire", None, 1)])
    assert analysis_tpu(m.mutex(), bad, **SMALL)["valid?"] is False


def test_pending_acquire_not_dropped():
    # a crashed acquire may have taken the lock: a later failed... rather,
    # a later acquire succeeding is only explainable if the crashed one
    # never applied; both verdicts valid. But a crashed acquire followed by
    # an impossible release sequence must still be checked.
    ops = encode_ops_for_model(m.mutex(), History([
        op("invoke", "acquire", None, 0), op("info", "acquire", None, 0)]))
    assert len(ops) == 1  # pending acquire kept (unlike pending reads)


# -- randomized golden agreement --------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_random_valid_histories(seed):
    hist = synth.register_history(60, concurrency=4, values=4,
                                  crash_rate=0.05, seed=seed)
    a = analysis_tpu(m.cas_register(), hist, **SMALL)
    assert a["valid?"] is True, a


@pytest.mark.parametrize("seed", range(8))
def test_random_corrupted_histories(seed):
    hist = synth.corrupt(
        synth.register_history(60, concurrency=4, values=4,
                               crash_rate=0.05, seed=seed), seed)
    a = analysis_tpu(m.cas_register(), hist, **SMALL)
    host = analysis_host(m.cas_register(), hist)
    assert a["valid?"] is host["valid?"] is False


@pytest.mark.parametrize("seed", range(4))
def test_random_agreement_mutex(seed):
    hist = synth.mutex_history(40, concurrency=3, seed=seed)
    a = analysis_tpu(m.mutex(), hist, **SMALL)
    host = analysis_host(m.mutex(), hist)
    assert a["valid?"] is host["valid?"], (a, host)


# -- batching & sharding ------------------------------------------------------

def test_batch():
    hists = [synth.register_history(40, concurrency=3, seed=s)
             for s in range(4)]
    hists.append(synth.corrupt(hists[0]))
    rs = analysis_tpu_batch(m.cas_register(), hists, frontier=128, slots=16)
    assert [r["valid?"] for r in rs] == [True, True, True, True, False]
    assert rs[4].get("op") is not None


def test_sharded_over_mesh():
    import jax
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    hists = [synth.register_history(30, concurrency=3, seed=s)
             for s in range(16)]
    all_ok, per_key = check_batch_sharded(m.cas_register(), hists,
                                          frontier=128, slots=16)
    assert all_ok and per_key.all()
    hists[5] = synth.corrupt(hists[5])
    all_ok, per_key = check_batch_sharded(m.cas_register(), hists,
                                          frontier=128, slots=16)
    assert not all_ok
    assert not per_key[5] and per_key[[i for i in range(16) if i != 5]].all()


def test_batch_mixed_slot_buckets_matches_scalar():
    # keys spanning several slot buckets exercise the bucketed dispatch
    # groups; verdicts must match the per-history scalar checker
    hists = [synth.register_history(60, concurrency=3 + (i % 4) * 2,
                                    values=5, crash_rate=0.02, seed=70 + i)
             for i in range(8)]
    hists.append(synth.corrupt(hists[3]))
    rs = analysis_tpu_batch(m.cas_register(), hists)
    scalar = [analysis_tpu(m.cas_register(), h) for h in hists]
    assert [r["valid?"] for r in rs] == [s["valid?"] for s in scalar]
    assert all("duration-ms" in r for r in rs)


def test_batch_zero_budget_reports_unknown_without_dispatch():
    hists = [synth.register_history(60, concurrency=3 + (i % 3) * 3,
                                    seed=i) for i in range(6)]
    rs = analysis_tpu_batch(m.cas_register(), hists, budget_s=0.0)
    assert all(r["valid?"] == "unknown" for r in rs)
    assert all("duration-ms" in r for r in rs)


def test_sharded_mixed_slot_buckets():
    hists = [synth.register_history(50, concurrency=3 + (i % 5),
                                    values=5, crash_rate=0.01, seed=200 + i)
             for i in range(12)]
    all_ok, per_key = check_batch_sharded(m.cas_register(), hists, slots=16)
    assert all_ok and per_key.all()
    hists.append(synth.corrupt(hists[0], seed=3))
    all_ok, per_key = check_batch_sharded(m.cas_register(), hists, slots=16)
    assert not all_ok and not per_key[-1] and per_key[:-1].all()


def test_sharded_dense_groups_are_the_batch_plan():
    """check_batch_sharded dispatches the dense groups _plan_groups
    plans, the length split included: sixteen short keys and one long
    one of the same slot bucket run as two groups."""
    hists = [synth.register_history(1200 if i == 0 else 40, concurrency=3,
                                    crash_rate=0.0, seed=300 + i)
             for i in range(17)]
    ops = [encode_ops_for_model(m.cas_register(), h) for h in hists]
    plan = wgl._plan_groups(m.cas_register(), ops,
                            [wgl.required_slots(o) for o in ops], "auto",
                            frontier=128, slots=16)
    dense = [(len(g.keys), g.slots) for g in plan if g.dense is not None]
    assert len(dense) == 2
    all_ok, per_key, info = check_batch_sharded(
        m.cas_register(), hists, frontier=128, slots=16, return_info=True)
    assert all_ok and per_key.all()
    assert dense == [(g["keys"], g["slots"]) for g in info["groups"]
                     if g["family"] == "dense"]


def test_sharded_forced_sort_sizes_own_slots():
    # a key needing more slots than the caller passed must not blow up
    hists = [synth.register_history(40, concurrency=7, seed=s)
             for s in range(4)]
    all_ok, per_key = check_batch_sharded(m.cas_register(), hists,
                                          slots=4, engine="sort")
    assert all_ok and per_key.all()


# -- slot machinery -----------------------------------------------------------

def test_slot_overflow_detection():
    hist = History(
        [op("invoke", "write", i, i) for i in range(10)])  # 10 pending
    ops = encode_ops_for_model(m.cas_register(), hist)
    with pytest.raises(SlotOverflow):
        build_steps(ops, 4)


# The per-event loops build_steps and required_slots replaced, kept as
# the oracle the array passes must match bit for bit.

def _oracle_required_slots(ops):
    events = []
    for r in range(len(ops)):
        events.append((int(ops.inv[r]), 0, 1))
        if ops.kind[r] == KIND_OK:
            events.append((int(ops.ret[r]), 1, -1))
        elif ops.kind[r] == wgl.KIND_RELEASED:
            events.append((int(ops.ret[r]), -1, -1))
    events.sort()
    cur = peak = 0
    for _, _, d in events:
        cur += d
        peak = max(peak, cur)
    return max(peak, 1)


def _oracle_build_steps(ops, p, merge=True):
    events = []  # (position, order, kind, row)
    for r in range(len(ops)):
        events.append((int(ops.inv[r]), 0, 0, r))
        if ops.kind[r] == KIND_OK:
            events.append((int(ops.ret[r]), 1, 1, r))
        elif ops.kind[r] == wgl.KIND_RELEASED:
            events.append((int(ops.ret[r]), -1, 2, r))
    events.sort()
    w = max(1, (p + 31) // 32)
    free = list(range(p))
    heapq.heapify(free)
    slot_of_row = {}
    masks, rest, ret_row, inv_row = [], [], [], []
    pend = [0] * w
    pend_rows = []

    def flush(inv_slot, f, a, b, row):
        nonlocal pend, pend_rows
        masks.append(pend)
        rest.append((inv_slot, f, a, b))
        ret_row.append(pend_rows[0] if len(pend_rows) == 1 else -1)
        inv_row.append(row)
        pend = [0] * w
        pend_rows = []

    for _, _, k, r in events:
        if k == 0:
            if not free:
                raise SlotOverflow(
                    f"more than {p} pending ops at op row {r} "
                    f"(crashed ops hold slots forever); raise p or check "
                    f"on the host")
            s = heapq.heappop(free)
            slot_of_row[r] = s
            flush(s, int(ops.f[r]), int(ops.a[r]), int(ops.b[r]), r)
        elif k == 2:
            if pend_rows:
                flush(-1, 0, NIL, NIL, -1)
            s = slot_of_row.pop(r)
            heapq.heappush(free, s)
            pend[s // 32] |= 1 << (s % 32)
            flush(-1, wgl.F_RELEASE, NIL, NIL, -1)
        else:
            s = slot_of_row.pop(r)
            heapq.heappush(free, s)
            pend[s // 32] |= 1 << (s % 32)
            pend_rows.append(r)
            if not merge:
                flush(-1, 0, NIL, NIL, -1)
    if any(pend):
        flush(-1, 0, NIL, NIL, -1)
    n = len(masks)
    mask_arr = np.asarray(masks, np.uint32).reshape(n, w)
    rest_arr = np.asarray(rest, np.int32).reshape(n, 4)
    return wgl.Steps(np.concatenate([mask_arr.view(np.int32), rest_arr],
                                    axis=1),
                     np.asarray(ret_row, np.int32),
                     np.asarray(inv_row, np.int32), w, n)


def _random_ops(seed, n=300):
    """Random rows: distinct invoke positions, returns tied with each
    other and with invokes, a few long-running ops holding many slots,
    and crashes of a few kinds pending forever."""
    rng = np.random.default_rng(seed)
    conc = (4, 24, 40)[seed % 3]
    inv = np.cumsum(rng.integers(1, 3, n)).astype(np.int32)
    ret = (inv + rng.integers(0, 2 * conc, n)).astype(np.int32)
    kind = np.where(rng.random(n) < 0.06, KIND_INFO, KIND_OK)
    f = rng.integers(1, 3, n)
    a = rng.integers(0, 2, n)
    b = np.where(f == 2, rng.integers(0, 2, n), NIL)
    return OpArray(f=f.astype(np.int32), a=a.astype(np.int32),
                   b=b.astype(np.int32), kind=kind.astype(np.int32),
                   inv=inv, ret=np.where(kind == KIND_OK, ret, PENDING_RET),
                   process=np.zeros(n, np.int32), index=inv.copy())


def _empty_ops():
    z = np.zeros(0, np.int32)
    return OpArray(f=z, a=z, b=z, kind=z, inv=z, ret=z, process=z, index=z)


def _assert_same_steps(got, want):
    assert (got.w, got.n) == (want.w, want.n)
    for field in ("x", "ret_row", "inv_row"):
        g, e = getattr(got, field), getattr(want, field)
        assert g.dtype == e.dtype and g.shape == e.shape
        assert (g == e).all(), field


@pytest.mark.parametrize("merge", [True, False], ids=["merged", "unmerged"])
@pytest.mark.parametrize("p", [31, 33, 64])
@pytest.mark.parametrize("m", [0, 1, 2], ids=["held", "m1", "m2"])
@pytest.mark.parametrize("seed", range(3))
def test_steps_and_slots_match_the_per_event_loops(seed, m, p, merge):
    """The array passes give the per-event loops' steps bit for bit:
    tied positions, crashes pending forever or to release_crashed's
    deadlines, merged and unmerged, masks of one word and of two; the
    same SlotOverflow where p is short, and at required_slots - 1."""
    ops = _random_ops(seed)
    if m:
        ops, released = wgl.release_crashed(ops, m)
        assert released
    need = _oracle_required_slots(ops)
    assert wgl.required_slots(ops) == need
    for slots in (p, need - 1):
        try:
            want = _oracle_build_steps(ops, slots, merge)
        except SlotOverflow as e:
            with pytest.raises(SlotOverflow) as got:
                build_steps(ops, slots, merge)
            assert str(got.value) == str(e)
            assert slots < need
            continue
        assert slots >= need
        _assert_same_steps(build_steps(ops, slots, merge), want)


@pytest.mark.parametrize("merge", [True, False], ids=["merged", "unmerged"])
def test_steps_of_an_empty_history(merge):
    ops = _empty_ops()
    assert wgl.required_slots(ops) == _oracle_required_slots(ops) == 1
    _assert_same_steps(build_steps(ops, 8, merge),
                       _oracle_build_steps(ops, 8, merge))


def _ycsb_keys(seed):
    """The encoded keys of a small seeded YCSB-A history: a Zipfian key
    choice, so a few long keys and many short ones, crashes by a coin
    and release_crashed's deadlines."""
    from benchmark import harness
    from jepsen_tpu.independent import KV, subhistories

    ycsb = harness.load_module(
        Path(__file__).resolve().parent.parent
        / "benchmark" / "generators" / "ycsb.py")
    shape = {"threads_per_key": 20, "values": 5,
             "mix": {"read": 1, "write": 1}, "records": 200,
             "distribution": "scrambled-zipfian",
             "zipfian_constant": 0.99}
    ops, _ = ycsb.history(shape, {"ops": 3000, "crash": {
        "rate": 0.05, "effect": "either"}}, random.Random(seed))
    hist = History([{**o, "value": KV(*o["value"])} for o in ops])
    return [wgl.release_crashed(
        encode_ops_for_model(m.cas_register(), h), 1)[0]
        for h in subhistories(hist).values()]


@pytest.mark.parametrize("merge", [True, False], ids=["merged", "unmerged"])
@pytest.mark.parametrize("seed", range(2))
def test_a_groups_stack_is_its_keys_steps_stacked(seed, merge):
    """build_steps_batch writes a group's keys of very unequal length,
    an empty one among them, into one bucketed stack equal to each key's
    steps padded to E, then empty lanes up to the bucketed B; the
    batched required_slots is the per-key list."""
    keys = _ycsb_keys(seed)
    keys.insert(len(keys) // 2, _empty_ops())
    lens = sorted(len(o) for o in keys)
    assert lens[0] == 0 and lens[-1] > 8 * lens[len(lens) // 2]
    need = [_oracle_required_slots(o) for o in keys]
    assert wgl.required_slots_batch(keys) == need
    p = max(need)
    E = wgl._bucket(max(wgl.event_count(o) for o in keys))
    lanes = wgl._bucket(len(keys), lo=1)
    per_key = [_oracle_build_steps(o, p, merge).pad_to(E) for o in keys]
    per_key += [_oracle_build_steps(_empty_ops(), p, merge).pad_to(E)] * (
        lanes - len(keys))
    got = wgl.build_steps_batch(keys, p, merge, t=E, lanes=lanes)
    assert got.w == per_key[0].w
    for field in ("x", "ret_row", "inv_row"):
        want = np.stack([getattr(st, field) for st in per_key])
        assert getattr(got, field).shape == want.shape
        assert (getattr(got, field) == want).all(), field
    assert got.n.dtype == np.int32
    assert (got.n == [st.n for st in per_key]).all()
    # by default a lane holds the longest key's steps, bucketed
    assert wgl.build_steps_batch(keys, p, merge).x.shape[1] == \
        wgl._bucket(max(st.n for st in per_key))
    with pytest.raises(SlotOverflow):
        wgl.build_steps_batch(keys, p - 1, merge)


def test_slot_overflow_escalates_transparently():
    # 8 fully-concurrent writes need 8 slots; we hand the checker 4 and it
    # must escalate. frontier 4096 covers all 2^8*8 reachable configs, so
    # no truncation nondeterminism.
    hist = History(
        [op("invoke", "write", i, i) for i in range(8)]
        + [op("ok", "write", i, i) for i in range(8)])
    a = analysis_tpu(m.cas_register(), hist, frontier=4096, slots=4)
    assert a["valid?"] is True


# -- chunked execution / budget (long-search checkpointing) ------------------

def test_required_slots():
    from jepsen_tpu.checker.wgl import encode_ops_for_model, required_slots
    h = synth.register_history(200, concurrency=4, values=5,
                               crash_rate=0.0, seed=7)
    ops = encode_ops_for_model(models.cas_register(), h)
    assert 1 <= required_slots(ops) <= 4
    # crashed ops hold slots forever
    h2 = synth.register_history(200, concurrency=4, values=5,
                                crash_rate=0.05, seed=7)
    ops2 = encode_ops_for_model(models.cas_register(), h2)
    assert required_slots(ops2) > required_slots(ops)


def test_chunked_matches_single_call():
    """Chunked execution must agree with the one-shot kernel."""
    h = synth.register_history(400, concurrency=4, values=5,
                               crash_rate=0.01, seed=11)
    a1 = wgl.analysis_tpu(models.cas_register(), h, chunk_entries=10**9)
    a2 = wgl.analysis_tpu(models.cas_register(), h, chunk_entries=64)
    assert a1["valid?"] == a2["valid?"]


def test_budget_returns_unknown():
    """Past the wall-clock budget an undecided search degrades to
    'unknown' rather than hanging."""
    h = synth.register_history(600, concurrency=5, values=5,
                               crash_rate=0.1, seed=3)  # exponential-ish
    a = wgl.analysis_tpu(models.cas_register(), h, frontier=8,
                         chunk_entries=16, budget_s=0.0)
    assert a["valid?"] == "unknown"
    assert "budget" in a["error"]


def test_budget_never_downgrades_completed_search():
    """A search that finishes all entries is definitive even when it
    blew the budget — no 'unknown' for completed valid verdicts."""
    h = synth.register_history(300, concurrency=4, values=5,
                               crash_rate=0.0, seed=5)
    a = wgl.analysis_tpu(models.cas_register(), h, budget_s=0.0,
                         chunk_entries=10**9)
    assert a["valid?"] is True


def test_invalid_verdict_carries_final_paths_and_configs():
    """Device 'invalid' verdicts reconstruct knossos-style explanations
    via a host re-search of the failing prefix (checker.clj:205-216)."""
    h = synth.corrupt(synth.register_history(300, concurrency=4, values=5,
                                             crash_rate=0.0, seed=9))
    a = wgl.analysis_tpu(models.cas_register(), h)
    assert a["valid?"] is False
    assert a["op"] is not None
    assert a["final-paths"], "failure must carry final paths"
    # each path ends with the failing attempt at the culprit op
    for path in a["final-paths"]:
        assert "Inconsistent" in path[-1]["model"] or \
            "inconsistent" in path[-1]["model"].lower()
    assert a["configs"]


def test_explain_off_skips_host_re_search():
    h = synth.corrupt(synth.register_history(300, concurrency=4, values=5,
                                             crash_rate=0.0, seed=9))
    a = wgl.analysis_tpu(models.cas_register(), h, explain=False)
    assert a["valid?"] is False
    assert a["final-paths"] == []


def test_linear_svg_written_to_store(tmp_path):
    from jepsen_tpu.checker.linear import linearizable

    h = synth.corrupt(synth.register_history(200, concurrency=4, values=5,
                                             crash_rate=0.0, seed=13))
    test = {"name": "svgtest", "start-time": "t0",
            "store-dir": str(tmp_path)}
    c = linearizable(models.cas_register(), "auto")
    res = c.check(test, h, {})
    assert res["valid?"] is False
    svg = tmp_path / "svgtest" / "t0" / "linear.svg"
    assert svg.exists()
    body = svg.read_text()
    assert "nonlinearizable" in body and "final paths" in body


def test_competition_mode():
    from jepsen_tpu.checker.linear import linearizable

    good = synth.register_history(300, concurrency=4, values=5,
                                  crash_rate=0.0, seed=21)
    bad = synth.corrupt(good)
    c = linearizable(models.cas_register(), "competition")
    r1 = c.check({}, good, {})
    assert r1["valid?"] is True and r1["competition-winner"] in ("host",
                                                                "tpu")
    r2 = c.check({}, bad, {})
    assert r2["valid?"] is False


def test_competition_host_only_model():
    # a model with no device form competes by just running the host
    from jepsen_tpu.checker.linear import linearizable
    from jepsen_tpu.models import Model

    class Weird(Model):
        device_model = None

        def step(self, op):
            return self

    h = synth.register_history(50, concurrency=3, values=3,
                               crash_rate=0.0, seed=2)
    r = linearizable(Weird(), "competition").check({}, h, {})
    assert r["valid?"] is True


def test_cancel_hook_stops_device_search():
    h = synth.register_history(600, concurrency=5, values=5,
                               crash_rate=0.1, seed=3)
    a = wgl.analysis_tpu(models.cas_register(), h, frontier=8,
                         chunk_entries=16, cancel=lambda: True)
    assert a["valid?"] == "unknown"
    assert "cancelled" in a["error"]


def test_batch_budget_returns_unknown_for_undecided_keys():
    """A zero budget with tiny chunks leaves later keys undecided:
    they must report 'unknown', not stall or claim a verdict."""
    hs = [synth.register_history(400, concurrency=4, values=4,
                                 crash_rate=0.01, seed=s)
          for s in range(4)]
    rs = analysis_tpu_batch(models.cas_register(), hs, frontier=64,
                            slots=16, chunk_entries=8, budget_s=0.0)
    assert len(rs) == 4
    assert all(r["valid?"] in (True, False, "unknown") for r in rs)
    assert any(r["valid?"] == "unknown" for r in rs)


def test_batch_budget_none_still_decides_everything():
    hs = [synth.register_history(200, concurrency=4, values=4,
                                 crash_rate=0.01, seed=s)
          for s in range(3)]
    hs.append(synth.corrupt(hs[0]))
    rs = analysis_tpu_batch(models.cas_register(), hs, frontier=128,
                            slots=16, chunk_entries=64)
    assert [r["valid?"] for r in rs] == [True, True, True, False]


def test_adversarial_history_device_vs_host():
    """The adversarial crashed-write shape must verify on BOTH device
    engines and agree with the host oracle at small scale."""
    h = synth.adversarial_register_history(300, concurrency=4,
                                           crashed_writes=4)
    a = analysis_tpu(models.cas_register(), h, frontier=2048)
    assert a["valid?"] is True and a["analyzer"] == "tpu-wgl-dense"
    s = analysis_tpu(models.cas_register(), h, frontier=2048,
                     engine="sort")
    assert s["valid?"] is True and s["analyzer"] == "tpu-wgl"
    assert analysis_host(models.cas_register(), h)["valid?"] is True
    bad = synth.corrupt(h)
    a2 = analysis_tpu(models.cas_register(), bad, frontier=2048)
    assert a2["valid?"] is False


def test_packed_and_unpacked_dedup_agree():
    """P=16 with small values packs the config into one u32 sort key;
    P=64 forces the multi-word path. Same verdicts either way (pinned
    to the sort engine — auto would route these to the dense kernel)."""
    for seed in (1, 2):
        h = synth.register_history(300, concurrency=5, values=4,
                                   crash_rate=0.02, seed=seed)
        packed = analysis_tpu(models.cas_register(), h, frontier=256,
                              slots=16, engine="sort")
        wide = analysis_tpu(models.cas_register(), h, frontier=256,
                            slots=64, engine="sort")
        assert packed["valid?"] is wide["valid?"] is True
        bad = synth.corrupt(h)
        pb = analysis_tpu(models.cas_register(), bad, frontier=256,
                          slots=16, engine="sort")
        wb = analysis_tpu(models.cas_register(), bad, frontier=256,
                          slots=64, engine="sort")
        assert pb["valid?"] is wb["valid?"] is False
        assert pb["op-index"] == wb["op-index"]


def test_dense_and_sort_engines_agree_on_random_histories():
    for seed in (11, 12, 13):
        h = synth.register_history(250, concurrency=5, values=4,
                                   crash_rate=0.05, seed=seed)
        d = analysis_tpu(models.cas_register(), h)
        s = analysis_tpu(models.cas_register(), h, frontier=1024,
                         engine="sort")
        assert d["analyzer"] == "tpu-wgl-dense"
        assert d["valid?"] is s["valid?"]


def test_negative_register_values():
    """States below -1 must extend the packed/dense state range
    downward, not wrap the u32 key or fall off the dense table."""
    h = [op("invoke", "write", -3, 0), op("ok", "write", -3, 0),
         op("invoke", "read", None, 0), op("ok", "read", -3, 0)]
    for engine in ("dense", "sort"):
        a = analysis_tpu(models.cas_register(), History(h), engine=engine)
        assert a["valid?"] is True, (engine, a)
    bad = [op("invoke", "write", -3, 0), op("ok", "write", -3, 0),
           op("invoke", "read", None, 0), op("ok", "read", -2, 0)]
    for engine in ("dense", "sort"):
        a = analysis_tpu(models.cas_register(), History(bad), engine=engine)
        assert a["valid?"] is False, (engine, a)


# -- new device models: counter / g-set / unordered queue --------------------

@pytest.mark.parametrize("seed", range(4))
def test_counter_device_host_agreement(seed):
    h = synth.counter_history(120, concurrency=4, crash_rate=0.05,
                              seed=seed)
    d = analysis_tpu(m.counter(), h)
    host = analysis_host(m.counter(), h)
    assert d["valid?"] is host["valid?"] is True, (d, host)


def test_counter_catches_bad_read():
    h = [op("invoke", "add", 2, 0), op("ok", "add", 2, 0),
         op("invoke", "read", None, 0), op("ok", "read", 5, 0)]
    d = analysis_tpu(m.counter(), History(h))
    host = analysis_host(m.counter(), History(h))
    assert d["valid?"] is host["valid?"] is False


def test_counter_concurrent_add_read_window():
    # a read overlapping an add may see either value
    h = [op("invoke", "add", 1, 0),
         op("invoke", "read", None, 1), op("ok", "read", 1, 1),
         op("ok", "add", 1, 0),
         op("invoke", "read", None, 1), op("ok", "read", 1, 1)]
    assert analysis_tpu(m.counter(), History(h))["valid?"] is True
    h2 = [op("invoke", "add", 1, 0),
          op("invoke", "read", None, 1), op("ok", "read", 0, 1),
          op("ok", "add", 1, 0)]
    assert analysis_tpu(m.counter(), History(h2))["valid?"] is True


@pytest.mark.parametrize("seed", range(4))
def test_gset_device_host_agreement(seed):
    h = synth.gset_history(120, concurrency=4, seed=seed)
    d = analysis_tpu(m.gset(), h)
    host = analysis_host(m.gset(), h)
    assert d["valid?"] is host["valid?"] is True, (d, host)


def test_gset_catches_phantom_and_lost_elements():
    lost = [op("invoke", "add", 3, 0), op("ok", "add", 3, 0),
            op("invoke", "read", None, 0), op("ok", "read", [], 0)]
    assert analysis_tpu(m.gset(), History(lost))["valid?"] is False
    phantom = [op("invoke", "add", 3, 0), op("ok", "add", 3, 0),
               op("invoke", "read", None, 0),
               op("ok", "read", [3, 4], 0)]
    assert analysis_tpu(m.gset(), History(phantom))["valid?"] is False


def test_gset_large_elements_fall_back_to_host():
    from jepsen_tpu.checker.linear import linearizable
    h = [op("invoke", "add", 1000, 0), op("ok", "add", 1000, 0),
         op("invoke", "read", None, 0), op("ok", "read", [1000], 0)]
    r = linearizable(m.gset()).check({}, History(h), {})
    assert r["valid?"] is True
    assert r["analyzer"] == "host-jit-linear"


@pytest.mark.parametrize("seed", range(4))
def test_uqueue_device_host_agreement(seed):
    h = synth.uqueue_history(120, concurrency=4, seed=seed)
    d = analysis_tpu(m.unordered_queue(), h)
    host = analysis_host(m.unordered_queue(), h)
    assert d["valid?"] is host["valid?"] is True, (d, host)


def test_uqueue_catches_phantom_dequeue():
    h = [op("invoke", "enqueue", 1, 0), op("ok", "enqueue", 1, 0),
         op("invoke", "dequeue", None, 1), op("ok", "dequeue", 2, 1)]
    d = analysis_tpu(m.unordered_queue(), History(h))
    host = analysis_host(m.unordered_queue(), History(h))
    assert d["valid?"] is host["valid?"] is False


def test_uqueue_unordered_ok():
    # dequeue order need not match enqueue order
    h = [op("invoke", "enqueue", 1, 0), op("ok", "enqueue", 1, 0),
         op("invoke", "enqueue", 2, 0), op("ok", "enqueue", 2, 0),
         op("invoke", "dequeue", None, 1), op("ok", "dequeue", 2, 1),
         op("invoke", "dequeue", None, 1), op("ok", "dequeue", 1, 1)]
    assert analysis_tpu(m.unordered_queue(), History(h))["valid?"] is True


def test_uqueue_crashed_dequeue_falls_back_to_host():
    from jepsen_tpu.checker.linear import linearizable
    h = [op("invoke", "enqueue", 1, 0), op("ok", "enqueue", 1, 0),
         op("invoke", "dequeue", None, 1), op("info", "dequeue", None, 1)]
    r = linearizable(m.unordered_queue()).check({}, History(h), {})
    assert r["valid?"] in (True, False)
    assert r["analyzer"] == "host-jit-linear"


def test_counter_negative_read_value_not_confused_with_nil():
    """An observed read of -1 must constrain the search (it is NOT the
    NIL 'unconstrained' sentinel) — false-valid regression."""
    bad = [op("invoke", "read", None, 0), op("ok", "read", -1, 0)]
    d = analysis_tpu(m.counter(), History(bad))
    host = analysis_host(m.counter(), History(bad))
    assert d["valid?"] is host["valid?"] is False
    good = [op("invoke", "add", -1, 0), op("ok", "add", -1, 0),
            op("invoke", "read", None, 0), op("ok", "read", -1, 0)]
    assert analysis_tpu(m.counter(), History(good))["valid?"] is True


def test_uqueue_multiplicity_overflow_falls_back_to_host():
    """16+ outstanding copies of one value would saturate the device
    digit and report a false invalid — must fall back to the host."""
    from jepsen_tpu.checker.linear import linearizable
    h = []
    for i in range(20):
        h.append(op("invoke", "enqueue", 1, 0))
        h.append(op("ok", "enqueue", 1, 0))
    r = linearizable(m.unordered_queue()).check({}, History(h), {})
    assert r["valid?"] is True
    assert r["analyzer"] == "host-jit-linear"


def test_gset_out_of_range_initial_state_falls_back():
    from jepsen_tpu.checker.linear import linearizable
    model = m.GSet(frozenset({40}))
    h = [op("invoke", "read", None, 0), op("ok", "read", [40], 0)]
    r = linearizable(model).check({}, History(h), {})
    assert r["valid?"] is True
    assert r["analyzer"] == "host-jit-linear"


def test_out_of_int32_values_fall_back_to_host():
    """Values beyond int32 can't encode; the checker must fall back,
    not crash with OverflowError."""
    from jepsen_tpu.checker.linear import linearizable
    h = [op("invoke", "add", 2**31, 0), op("ok", "add", 2**31, 0),
         op("invoke", "read", None, 0), op("ok", "read", 2**31, 0)]
    r = linearizable(m.counter()).check({}, History(h), {})
    assert r["valid?"] is True
    assert r["analyzer"] == "host-jit-linear"


def test_uqueue_initial_multiplicity_cap():
    from jepsen_tpu.checker.linear import linearizable
    model = m.UnorderedQueue(frozenset((1, i) for i in range(16)))
    h = [op("invoke", "dequeue", None, 0), op("ok", "dequeue", 1, 0)]
    r = linearizable(model).check({}, History(h), {})
    assert r["valid?"] is True
    assert r["analyzer"] == "host-jit-linear"


def test_forced_dense_engine_error_still_surfaces():
    """engine='dense' on an ineligible history must raise, not be
    silently downgraded to the host search."""
    h = synth.register_history(50, concurrency=3, values=3,
                               crash_rate=0.0, seed=2)
    big = [dict(o) for o in h.ops]
    big[0] = {**big[0], "value": 10**6}
    big[1] = {**big[1], "value": 10**6}
    with pytest.raises(ValueError, match="dense"):
        analysis_tpu(m.cas_register(), History(big), engine="dense")
    # the batch path honors the same contract for single- and multi-key
    # batches (both raise inside _plan_groups)
    with pytest.raises(ValueError, match="dense"):
        analysis_tpu_batch(m.cas_register(), [History(big)],
                           engine="dense")
    with pytest.raises(ValueError, match="dense"):
        analysis_tpu_batch(m.cas_register(), [History(big), h],
                           engine="dense")


# -- merged-step stream edge cases -------------------------------------------

def test_steps_merge_tail_completions():
    """A history ending in a run of completions gets a final mask-only
    step; merged and unmerged streams agree on the verdict."""
    from jepsen_tpu.checker.wgl import build_steps, event_count
    h = History([
        op("invoke", "write", 1, 0),
        op("invoke", "write", 2, 1),
        op("invoke", "read", None, 2),
        op("ok", "read", 1, 2),
        op("ok", "write", 1, 0),
        op("ok", "write", 2, 1)])
    ops = encode_ops_for_model(m.cas_register(), h)
    merged = build_steps(ops, 8)
    unmerged = build_steps(ops, 8, merge=False)
    assert merged.n < unmerged.n
    assert unmerged.n == event_count(ops)
    # the merged tail step completes the trailing run, no invoke
    assert merged.x[merged.n - 1][1] == -1
    assert merged.x[merged.n - 1][0] != 0
    a = analysis_tpu(m.cas_register(), h, **SMALL)
    assert a["valid?"] is True


def test_all_crashed_ops_verify():
    """Nothing ever completes: every op pends forever; any subset may
    have applied, so the history is trivially linearizable — and the
    stream contains no completion steps at all."""
    h = History([op("invoke", "write", i, i) for i in range(4)]
                + [op("info", "write", i, i) for i in range(4)])
    a = analysis_tpu(m.cas_register(), h, **SMALL)
    assert a["valid?"] is True


def test_blame_matches_host_oracle_on_corrupted_histories():
    """The unmerged blame re-run must name the same culprit op the
    host oracle finds, across engines."""
    for seed in (3, 4, 5):
        h = synth.corrupt(synth.register_history(
            120, concurrency=4, values=4, crash_rate=0.02, seed=seed),
            seed)
        host = analysis_host(m.cas_register(), h)
        # corrupt() writes an out-of-range value, so dense is
        # ineligible here (dense blame is covered by the corpus
        # diagnosis tests); auto routes to the sort engine
        for engine in ("auto", "sort"):
            a = analysis_tpu(m.cas_register(), h, frontier=4096,
                             engine=engine, explain=False)
            assert a["valid?"] is False is host["valid?"]
            assert a["op-index"] == host["op-index"], (engine, seed)


def test_linearizable_checker_passes_engine_options_through():
    """The checker factory exposes the device-engine tunables (the
    knossos plan.md wish: search heuristics as user options)."""
    from jepsen_tpu.checker.linear import linearizable
    from jepsen_tpu.checker.synth import register_history

    h = register_history(120, concurrency=4, values=3, crash_rate=0.0,
                         seed=45100)
    for engine, marker in (("dense", "tpu-wgl-dense"), ("sort", "tpu-wgl")):
        chk = linearizable({"model": models.cas_register(),
                            "engine": engine})
        r = chk.check({"name": "t"}, h, {})
        assert r["valid?"] is True
        assert r["analyzer"] == marker, r
