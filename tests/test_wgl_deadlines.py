"""Deadlines for crashed ops (wgl.release_crashed), checked in rounds by
both the batch path and the single-history path (analysis_tpu), and
the batch plan's split of a slot bucket by key length
(wgl._length_parts, applied by wgl._plan_groups): every verdict stays
the host oracle's, a history is checked again only when a deadline may
have cost it its verdict, and only skewed lengths split a group."""

import random

import numpy as np
import pytest

from jepsen_tpu import models as m
from jepsen_tpu import telemetry
from jepsen_tpu.checker import synth, wgl
from jepsen_tpu.checker.linear import analysis_host
from jepsen_tpu.history import KIND_INFO, History, OpArray


def op(type, f, value, process=0):
    return {"type": type, "f": f, "value": value, "process": process}


def crashed(f, value, process):
    return [op("invoke", f, value, process), op("info", f, value, process)]


def ok(f, value, process, read=None):
    return [op("invoke", f, read if f == "read" else value, process),
            op("ok", f, value, process)]


def _rechecked():
    snap = telemetry.snapshot(prefix="jepsen_tpu_wgl_batch_rechecked")
    return sum(snap.get("jepsen_tpu_wgl_batch_rechecked_keys_total",
                        {}).values())


def _deadline_checks():
    """{(site, outcome): count} of jepsen_tpu_wgl_deadline_checks_total."""
    snap = telemetry.snapshot(prefix="jepsen_tpu_wgl_deadline_checks")
    out = {}
    for labels, v in snap.get("jepsen_tpu_wgl_deadline_checks_total",
                              {}).items():
        kv = dict(x.split("=") for x in labels.split(","))
        out[kv["site"], kv["outcome"]] = v
    return out


def _delta(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _crashy(seed, n=40, conc=3, values=2, crash_rate=0.35):
    """A register history, linearizable by construction, whose crashed
    writes land at their invocation, some ops later, or never. With two
    values most crashes repeat a kind, and a late landing may come after
    the next crash of its kind was invoked."""
    rng = random.Random(seed)
    h, pending, landing = [], {}, []
    value, proc = None, list(range(conc))
    for _ in range(n):
        for land in landing:
            land[0] -= 1
            if land[0] == 0:
                value = land[1]
        s = rng.randrange(conc)
        if s in pending:
            h.append(pending.pop(s))
            continue
        p, f = proc[s], rng.choice(["read", "write", "write", "cas"])
        if f == "read":
            h.append(op("invoke", "read", None, p))
            pending[s] = op("ok", "read", value, p)
        elif f == "cas":
            old, new = rng.randrange(values), rng.randrange(values)
            h.append(op("invoke", "cas", [old, new], p))
            pending[s] = op("ok" if value == old else "fail", "cas",
                            [old, new], p)
            if value == old:
                value = new
        else:
            v = rng.randrange(values)
            if rng.random() < crash_rate:
                h += crashed("write", v, p)
                effect = rng.choice(["applied", "lost", "late"])
                if effect == "applied":
                    value = v
                elif effect == "late":
                    landing.append([rng.randint(1, 12), v])
                proc[s] += conc
            else:
                h.append(op("invoke", "write", v, p))
                pending[s] = op("ok", "write", v, p)
                value = v
    h += [pending[s] for s in sorted(pending)]
    return History(h)


def test_a_crash_is_open_until_the_next_of_its_kind():
    h = History(ok("write", 0, 0) + crashed("write", 1, 1)
                + crashed("cas", [1, 2], 2) + crashed("write", 1, 3)
                + crashed("write", 2, 4) + crashed("write", 1, 5)
                + crashed("cas", [1, 2], 6))
    ops = wgl.encode_ops_for_model(m.cas_register(), h)
    one, released = wgl.release_crashed(ops, 1)
    assert released
    kinds = one.kind.tolist()
    assert kinds == [0, wgl.KIND_RELEASED, wgl.KIND_RELEASED,
                     wgl.KIND_RELEASED, KIND_INFO, KIND_INFO, KIND_INFO]
    # each deadline is the invocation of the next crash of the same kind
    assert one.ret[1:4].tolist() == [ops.inv[3], ops.inv[6], ops.inv[5]]
    # at most one crash of each of the three kinds holds a slot at once
    assert wgl.required_slots(one) == 3 < wgl.required_slots(ops) == 6
    steps = wgl.build_steps(one, wgl.required_slots(one))
    releases = steps.x[:steps.n, steps.w + 1] == wgl.F_RELEASE
    assert releases.sum() == 3
    assert (steps.ret_row[:steps.n][releases] == -1).all()
    assert wgl.event_count(one) == len(ops) + 1 + 3
    two, released = wgl.release_crashed(ops, 2)
    assert released and two.kind.tolist().count(wgl.KIND_RELEASED) == 1
    every, released = wgl.release_crashed(ops, 3)
    assert not released and every is ops


def _two_after_a_write(n_reads=2):
    """Two crashed writes of 1, then a completed write of 0 and reads of
    1 around writes of 2: with the first crash closed at the second's
    invocation, one crash of 1 is left for the reads, which need
    `n_reads` of them."""
    h = crashed("write", 1, 1) + crashed("write", 1, 2) + ok("write", 0, 0)
    for k in range(n_reads):
        if k:
            h += ok("write", 2, 0)
        h += ok("read", 1, 3)
    return History(h)


def _read_past_a_deadline():
    """A read of a value never written, after the first deadline."""
    return History(crashed("write", 1, 1) + crashed("write", 1, 2)
                   + ok("read", 3, 3))


_PAST_A_DEADLINE = [
    # dies past the deadline; the prefix lives, so the deadlines go
    (lambda: _two_after_a_write(2), True, 2),
    (lambda: _two_after_a_write(3), False, 2),
    # dies past the deadline, and the prefix convicts it
    (_read_past_a_deadline, False, 1)]


@pytest.mark.parametrize("via,key,valid,again", [
    pytest.param(via, *case, id=f"{pre}key{n}-{case[1]}-{case[2]}")
    for via, pre in (("batch", ""), ("offline", "offline-"))
    for n, case in enumerate(_PAST_A_DEADLINE)])
def test_a_key_failing_past_a_deadline_is_checked_again(via, key, valid,
                                                        again):
    assert analysis_host(m.cas_register(), key())["valid?"] is valid
    before, checks = _rechecked(), _deadline_checks()
    if via == "offline":
        r = wgl.analysis_tpu(m.cas_register(), key())
        assert r["valid?"] is valid
        assert r["analyzer"] == "tpu-wgl-dense"
        # the batch's re-check counter counts only batch rounds
        assert _rechecked() == before
        assert _delta(checks, _deadline_checks()).get(
            ("offline", "again")) == again
        sort = wgl.analysis_tpu(m.cas_register(), key(), engine="sort")
        assert sort["valid?"] is valid
        return
    plain = synth.register_history(30, concurrency=3, values=3, seed=1)
    hists = [key(), plain]
    rs = wgl.analysis_tpu_batch(m.cas_register(), hists)
    assert [r["valid?"] for r in rs] == [valid, True]
    assert {r["analyzer"] for r in rs} == {"tpu-wgl-batch"}
    assert _rechecked() - before == again
    sort = wgl.analysis_tpu_batch(m.cas_register(), hists, engine="sort")
    assert [r["valid?"] for r in sort] == [valid, True]


def test_a_key_that_dies_before_its_first_deadline_is_not_checked_again():
    bad = History(ok("write", 0, 0) + ok("read", 3, 1)
                  + crashed("write", 1, 2) + crashed("write", 1, 3)
                  + ok("read", 1, 1))
    ops = wgl.encode_ops_for_model(m.cas_register(), bad)
    assert wgl.release_crashed(ops, 1)[1]
    before = _rechecked()
    rs = wgl.analysis_tpu_batch(m.cas_register(), [bad, _two_after_a_write()])
    assert [r["valid?"] for r in rs] == [False, True]
    assert rs[0]["op-index"] == 2          # the read of 3
    assert _rechecked() - before == 2      # only the other key


@pytest.mark.parametrize("via,seed", [
    pytest.param(via, seed, id=f"{pre}{seed}")
    for via, pre in (("batch", ""), ("offline", "offline-"))
    for seed in range(6)])
def test_crash_heavy_batches_agree_with_the_host(via, seed):
    """Many crashed writes over two values, so most kinds repeat: valid
    and corrupted keys side by side, each as the host oracle decides,
    whether checked as one batch or one history at a time."""
    hists = []
    for s in range(4):
        h = synth.register_history(36, concurrency=4, values=2,
                                   crash_rate=0.3, seed=100 * seed + s)
        hists.append(synth.corrupt(h, seed + s) if s % 2 else h)
    if via == "batch":
        rs = wgl.analysis_tpu_batch(m.cas_register(), hists)
    else:
        rs = [wgl.analysis_tpu(m.cas_register(), h) for h in hists]
    want = [analysis_host(m.cas_register(), h)["valid?"] for h in hists]
    assert [r["valid?"] for r in rs] == want
    assert False in want


@pytest.mark.parametrize("seed", range(8))
def test_crash_heavy_histories_agree_with_the_host(seed):
    """Lost, applied and late-landing crashes, a third of the histories
    corrupted (a read of a value never written, anywhere in the
    history): each history, checked alone, answers as the host oracle
    does, and the checks that stood under deadlines are counted."""
    hists = [_crashy(10 * seed + s) for s in range(3)]
    hists[1] = synth.corrupt(hists[1], seed, value=2)
    before = _deadline_checks()
    rs = [wgl.analysis_tpu(m.cas_register(), h) for h in hists]
    want = [analysis_host(m.cas_register(), h)["valid?"] for h in hists]
    assert [r["valid?"] for r in rs] == want
    assert want[1] is False
    assert all(r["op-count"] == len(wgl.encode_ops_for_model(
        m.cas_register(), h)) for r, h in zip(rs, hists))
    counted = _delta(before, _deadline_checks())
    assert {site for site, _ in counted} == {"offline"}
    assert sum(counted.values()) >= len(hists)


def test_a_crash_landing_after_the_next_of_its_kind_is_valid_at_m2():
    """Two crashed writes of 1 read after a later write: the first may
    not take effect past the second's invocation, so the check under
    deadlines fails, its prefix lives, and the round at m = 2 (no
    deadline left) answers True."""
    h = _two_after_a_write(2)
    ops = wgl.encode_ops_for_model(m.cas_register(), h)
    assert not wgl.release_crashed(ops, 2)[1]
    before = _deadline_checks()
    r = wgl.analysis_tpu(m.cas_register(), h)
    assert r["valid?"] is True
    assert r["op-count"] == len(ops)
    assert _delta(before, _deadline_checks()) == {
        ("offline", "again"): 2, ("offline", "none"): 1}


def test_deadline_checks_count_by_site_and_outcome():
    no_deadline = History(ok("write", 0, 0) + crashed("write", 1, 1)
                          + ok("read", 0, 2))
    stood = History(crashed("write", 1, 1) + crashed("write", 1, 2)
                    + ok("read", 1, 3))
    hists = [no_deadline, stood, _two_after_a_write(2)]
    before, rechecked = _deadline_checks(), _rechecked()
    assert [wgl.analysis_tpu(m.cas_register(), h)["valid?"]
            for h in hists] == [True] * 3
    assert _delta(before, _deadline_checks()) == {
        ("offline", "none"): 2, ("offline", "stood"): 1,
        ("offline", "again"): 2}
    assert _rechecked() == rechecked
    before = _deadline_checks()
    rs = wgl.analysis_tpu_batch(m.cas_register(), hists)
    assert [r["valid?"] for r in rs] == [True] * 3
    assert _delta(before, _deadline_checks()) == {
        ("batch", "none"): 2, ("batch", "stood"): 1, ("batch", "again"): 2}
    assert _rechecked() - rechecked == 2


@pytest.mark.parametrize("knobs", [{"engine": "sort"},
                                   {"dense_slot_cap": 0}],
                         ids=["sort", "auto-capped"])
@pytest.mark.parametrize("seed", range(3))
def test_the_sort_family_never_steps_a_release(monkeypatch, knobs, seed):
    """Forced or picked (no slot may go dense), the sort kernel checks
    crashed ops pending forever: no F_RELEASE step is built, and the
    verdicts are the host's."""
    built = []
    real = wgl.build_steps

    def spy(ops, p, merge=True):
        steps = real(ops, p, merge)
        built.append(bool((steps.x[:steps.n, steps.w + 1]
                           == wgl.F_RELEASE).any()))
        return steps

    monkeypatch.setattr(wgl, "build_steps", spy)
    hists = [_crashy(100 + seed), synth.corrupt(_crashy(200 + seed), seed,
                                                value=2),
             _two_after_a_write(2), _read_past_a_deadline()]
    rs = [wgl.analysis_tpu(m.cas_register(), h, **knobs) for h in hists]
    assert [r["valid?"] for r in rs] == [
        analysis_host(m.cas_register(), h)["valid?"] for h in hists]
    assert {r["analyzer"] for r in rs} == {"tpu-wgl"}
    assert built and not any(built)


@pytest.mark.parametrize("seed", range(2))
def test_a_crashed_10k_shaped_history_runs_dense_at_p_10_or_less(
        monkeypatch, seed):
    """5 clients and 9 writes that crash evenly and never land, the
    crashed-10k cell's shape at 2k ops: 13-14 slots with every crash
    held, at most 10 with deadlines, and the dense table is that wide."""
    h = synth.adversarial_register_history(2000, concurrency=5,
                                           crashed_writes=9, seed=seed)
    ops = wgl.encode_ops_for_model(m.cas_register(), h)
    assert wgl.required_slots(ops) >= 13
    widths = []
    real = wgl._dense_kernel

    def spy(name, s_lo, S, P, E, **kw):
        widths.append(P)
        return real(name, s_lo, S, P, E, **kw)

    monkeypatch.setattr(wgl, "_dense_kernel", spy)
    before = _deadline_checks()
    r = wgl.analysis_tpu(m.cas_register(), h)
    assert r["valid?"] is True and r["analyzer"] == "tpu-wgl-dense"
    assert widths and max(widths) <= 10
    assert max(widths) == wgl.required_slots(wgl.release_crashed(ops, 1)[0])
    assert _delta(before, _deadline_checks()) == {("offline", "stood"): 1}


def test_the_batch_scalar_fallback_checks_a_round_once(monkeypatch):
    """A key past the batch's slot budget on the sort family goes to the
    single-history search with the round's ops: one search a round, no
    rounds of its own, and the key's exact answer."""
    calls = []
    real = wgl._search

    def spy(model, hist, ops, *a, **kw):
        calls.append(len(ops))
        return real(model, hist, ops, *a, **kw)

    monkeypatch.setattr(wgl, "_search", spy)
    for key, valid, rounds in ((_two_after_a_write(2), True, 1),
                               (_read_past_a_deadline(), False, 2)):
        calls.clear()
        before = _deadline_checks()
        r, = wgl.analysis_tpu_batch(m.cas_register(), [key], slots=2,
                                    dense_slot_cap=0)
        assert r["valid?"] is valid is analysis_host(
            m.cas_register(), key)["valid?"]
        assert r["analyzer"] == "tpu-wgl"
        counted = _delta(before, _deadline_checks())
        assert {site for site, _ in counted} == {"batch"}
        assert sum(counted.values()) == len(calls) == rounds


def _keys(*events):
    """Stand-in encoded keys, all ops completed, with these event counts."""
    out = []
    for e in events:
        n = e // 2
        z = np.zeros(n, np.int32)
        out.append(OpArray(f=z, a=z, b=z, kind=z, inv=np.arange(n),
                           ret=np.arange(n), process=z, index=np.arange(n)))
    return out


def test_length_parts_split_only_skewed_lengths():
    # keyed-50k-like: 72-87 ops a key, one part however they straddle 128
    even = _keys(*([140] * 40 + [170] * 300))
    assert wgl._length_parts(list(range(len(even))), even) == \
        [list(range(len(even)))]
    # Zipf-like: many short keys and a few long ones part ways
    skew = _keys(*([100] * 700 + [240] * 200 + [4000] * 3))
    parts = wgl._length_parts(list(range(len(skew))), skew)
    assert parts == [list(range(700)), list(range(700, 900)),
                     list(range(900, 903))]
    # a handful of middling keys rides with the longer ones
    few = _keys(*([400] * 3 + [4000] * 2))
    assert wgl._length_parts([0, 1, 2, 3, 4], few) == [[3, 4, 0, 1, 2]]


def test_a_batch_frontier_escalation_runs_no_rounds_of_its_own(monkeypatch):
    """Keys whose sort-family frontier overflows are checked again at 4x
    the frontier with the round's ops, not as new histories with rounds
    of their own: one deadline check a key, and the host's verdicts."""
    frontiers = []
    real = wgl._check_group

    def spy(model, hists, ops, group, frontier, **kw):
        frontiers.append(frontier)
        return real(model, hists, ops, group, frontier, **kw)

    monkeypatch.setattr(wgl, "_check_group", spy)
    hists = [_crashy(3), _crashy(5)]
    before = _deadline_checks()
    rs = wgl.analysis_tpu_batch(m.cas_register(), hists, frontier=2,
                                dense_slot_cap=0)
    assert [r["valid?"] for r in rs] == [
        analysis_host(m.cas_register(), h)["valid?"] for h in hists]
    assert max(frontiers) > 2
    assert sum(_delta(before, _deadline_checks()).values()) == len(hists)
