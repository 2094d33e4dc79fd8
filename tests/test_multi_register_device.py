"""The multi-register device model (models.MultiRegister, wgl's
"multi-register"): its step, codec and state range, the dense kernel's
image closure at wide S, the crash kind its deadlines take, and verdicts
against the host oracle, alone and under independent.checker, on small
domains (2 subkeys x values 0-2)."""

import itertools
import random

import numpy as np
import pytest

from jepsen_tpu import independent, models, telemetry
from jepsen_tpu.checker import wgl
from jepsen_tpu.checker.linear import analysis_host, linearizable
from jepsen_tpu.history import DeviceEncodingError, History
from jepsen_tpu.independent import KV

DM = wgl.DEVICE_MODELS["multi-register"]
N_STATES = models.MR_RADIX ** models.MR_SUBKEYS


def _txn(rng, subkeys=3, values=5, kinds=("r", "w")):
    return [[rng.choice(kinds), rng.randrange(subkeys),
             rng.choice([None] + list(range(values)))]
            for _ in range(rng.randint(1, 4))]


def _unpack(state):
    """{subkey: value} of a packed state, by the layout's definition."""
    out = {}
    for k in range(models.MR_SUBKEYS):
        d = state // models.MR_RADIX ** k % models.MR_RADIX
        if d:
            out[k] = d - 1
    return out


def test_every_step_agrees_with_the_model():
    """Kernel step, device_step_multi_register and MultiRegister.step
    agree on every state for random micro-op lists, reads after writes
    within a transaction included."""
    import jax.numpy as jnp

    rng = random.Random(7)
    states = np.arange(N_STATES, dtype=np.int32)
    for _ in range(120):
        txn = _txn(rng)
        f, a, b = wgl.multi_register_codec({"value": txn})
        legal, new = DM.step(jnp.asarray(states), f, a, b)
        legal, new = np.asarray(legal), np.asarray(new)
        for s in states.tolist():
            want = models.multi_register(_unpack(s)).step({"value": txn})
            ok, s2 = models.device_step_multi_register(s, f, a, b)
            assert bool(legal[s]) == ok == (not models.is_inconsistent(want))
            if ok:
                assert s2 == int(new[s]) == want.device_state(), (s, txn)


def test_codec_round_trips_and_refuses_what_does_not_pack():
    rng = random.Random(3)
    for _ in range(200):
        txn = [["w", k, rng.randrange(5)] for k in
               rng.sample(range(3), rng.randint(1, 3))]
        f, a, b = wgl.multi_register_codec({"value": txn})
        assert f == wgl.MR_WRITE and a == 0
        back = {k: ((b >> (3 * k)) & 7) - 1 for k in range(3)
                if b >> (9 + k) & 1}
        assert back == {k: v for _, k, v in txn}
        reads = [["r", k, rng.choice([None, 0, 1, 2, 3, 4])]
                 for k in rng.sample(range(3), rng.randint(1, 3))]
        f, a, b = wgl.multi_register_codec({"value": reads})
        assert f == wgl.MR_READ and b == 0
        assert {k: ((a >> (3 * k)) & 7) - 1 for k in range(3)
                if a >> (9 + k) & 1} == \
            {k: v for _, k, v in reads if v is not None}
    for bad in ([["w", 3, 0]], [["w", 0, 5]], [["r", "x", 1]],
                [["w", 0, -1]], [["cas", 0, 1]], [["w", True, 1]]):
        with pytest.raises(DeviceEncodingError):
            wgl.multi_register_codec({"value": bad})


def test_state_range_follows_the_subkeys_touched():
    def rng_of(txns):
        rows = [wgl.multi_register_codec({"value": t}) for t in txns]
        f, a, b = (np.asarray(c) for c in zip(*rows))
        return DM.state_range(0, f, a, b)

    assert rng_of([[["w", 0, 4]], [["r", 0, 1]]]) == (0, 5)
    assert rng_of([[["w", 1, 0]]]) == (0, 35)
    assert rng_of([[["r", 2, None]], [["r", 2, 3]]]) == (0, N_STATES - 1)
    assert DM.state_range(models.mr_pack({2: 0}), np.zeros(0, int),
                          np.zeros(0, int), np.zeros(0, int)) \
        == (0, N_STATES - 1)


def test_out_of_domain_histories_go_to_the_flagged_host_path():
    hist = [{"type": "invoke", "f": "write", "value": [["w", 0, 9]],
             "process": 0},
            {"type": "ok", "f": "write", "value": [["w", 0, 9]],
             "process": 0},
            {"type": "invoke", "f": "read", "value": [["r", 0, None]],
             "process": 1},
            {"type": "ok", "f": "read", "value": [["r", 0, 9]],
             "process": 1}]
    r = linearizable(models.multi_register()).check({}, History(hist), {})
    assert r["valid?"] is True and not r["analyzer"].startswith("tpu")
    keyed = History([dict(o, value=KV(k, o["value"])) for k in (0, 1)
                     for o in hist])
    r = independent.checker(linearizable(models.multi_register())).check(
        {}, keyed, {})
    assert r["valid?"] is True and "device-fallback" in r


def history(seed, keys=1, subkeys=2, values=3, crash=0.35, threads=4,
            limit=12, corrupt=None, max_ops=400):
    """A keyed multi-register history: `threads` a key, half reading, each
    op taking effect at its invocation; a write crashes by a coin of odds
    `crash`, landing or not with even odds, and landing either at once or
    after the next crash is invoked; a key ends when it would need a
    (limit + 1)-th process, or at `max_ops` invocations. `corrupt`:
    rewrite one :ok read micro-op."""
    rng = random.Random(seed)
    ops = []
    for key in range(keys):
        regs, procs = {}, list(range(threads))
        pending, late = {}, []
        used = set(procs)
        for _ in range(max_ops):
            th = rng.randrange(threads)
            if th in pending:
                ops.append(pending.pop(th))
                continue
            if len(used) > limit:
                break
            p = procs[th]
            sub = rng.sample(range(subkeys), rng.randint(1, subkeys))
            if th < threads // 2:
                inv = [["r", k, None] for k in sub]
                ops.append({"type": "invoke", "f": "read",
                            "value": KV(key, inv), "process": p})
                pending[th] = {"type": "ok", "f": "read", "process": p,
                               "value": KV(key, [["r", k, regs.get(k)]
                                                 for k in sub])}
                continue
            txn = [["w", k, rng.randrange(values)] for k in sub]
            ops.append({"type": "invoke", "f": "write",
                        "value": KV(key, txn), "process": p})
            if rng.random() < crash:
                for t in late:                # lands after this invoke
                    regs.update({k: v for _, k, v in t})
                late = []
                fate = rng.random()
                if fate < 0.3:
                    regs.update({k: v for _, k, v in txn})
                elif fate < 0.6:
                    late.append(txn)
                ops.append({"type": "info", "f": "write",
                            "value": KV(key, txn), "process": p})
                procs[th] = p + threads
                used.add(procs[th])
                continue
            regs.update({k: v for _, k, v in txn})
            pending[th] = {"type": "ok", "f": "write", "process": p,
                           "value": KV(key, txn)}
        for comp in pending.values():
            ops.append(comp)
    if corrupt is not None:
        reads = [i for i, o in enumerate(ops)
                 if o["type"] == "ok" and o["f"] == "read"]
        i = rng.choice(reads)
        k, txn = ops[i]["value"]
        txn = [list(m) for m in txn]
        m = rng.randrange(len(txn))
        txn[m][2] = rng.choice([v for v in range(values) if v != txn[m][2]])
        ops[i] = dict(ops[i], value=KV(k, txn))
    for i, o in enumerate(ops):
        o["time"] = i
    return ops


def _one_key(ops):
    return History([dict(o, value=o["value"][1]) for o in ops])


SEEDS = [list(range(s, s + 26)) for s in range(0, 156, 26)]


@pytest.mark.parametrize("seeds", SEEDS, ids=lambda s: f"seeds{s[0]}")
def test_device_verdicts_agree_with_the_host(seeds):
    """analysis_tpu on each history, and independent.checker over them as
    keys of one history, agree with analysis_host: valid and corrupted
    histories with 8 or more crashed writes, some landing only after the
    next crash was invoked."""
    model = models.multi_register()
    keyed, want = [], []
    for n, seed in enumerate(seeds):
        ops = history(seed, crash=0.5,
                      corrupt=seed if seed % 3 == 0 else None)
        assert sum(o["type"] == "info" for o in ops) >= 8
        h = _one_key(ops)
        want.append(analysis_host(model, h)["valid?"])
        got = wgl.analysis_tpu(model, h)
        assert got["valid?"] == want[-1], seed
        assert got["analyzer"].startswith("tpu-wgl")
        assert got.get("closure", wgl.CLOSURE_IMAGE) == wgl.CLOSURE_IMAGE
        keyed += [dict(o, value=KV(n, o["value"][1])) for o in ops]
    assert set(want) == {True, False}
    r = independent.checker(linearizable(model), strict_device=True).check(
        {}, History(keyed), {})
    for n, seed in enumerate(seeds):
        assert r["results"][n]["valid?"] == want[n], seed
        assert r["results"][n]["analyzer"] == "tpu-wgl-batch"


@pytest.mark.parametrize("step_cells,chunk", [(0, 4096), (0, 16),
                                              (1 << 11, 64)])
def test_packed_lanes_agree_with_the_host(step_cells, chunk, monkeypatch):
    """Keys that share a lane, one after another (_pack_lanes), get the
    host's verdicts: invalid keys before, between and after valid ones,
    whose lanes start their next key afresh, found dead at a chunk's
    boundary (chunk 16) or after the last chunk (chunk 4096). Step cells
    0 packs every key into one lane."""
    monkeypatch.setattr(wgl, "DENSE_STEP_CELLS", step_cells)
    monkeypatch.setattr(wgl, "PACKED_CHUNK", chunk)
    packs = []
    real = wgl._lane_steps

    def spy(lanes, *a):
        packs.append(lanes)
        return real(lanes, *a)

    monkeypatch.setattr(wgl, "_lane_steps", spy)
    model = models.multi_register()
    hists = [_one_key(history(s, crash=0.4, limit=10, max_ops=120,
                              corrupt=s if s % 3 == 1 else None))
             for s in range(12)]
    want = [analysis_host(model, h)["valid?"] for h in hists]
    got = wgl.analysis_tpu_batch(model, hists)
    assert [r["valid?"] for r in got] == want
    assert {True, False} <= set(want)
    assert max(len(lane) for lanes in packs for lane in lanes) > 1
    for r in got:
        assert r["analyzer"] == "tpu-wgl-batch"
        assert r["valid?"] is True or r["op"]["type"] == "ok"


def test_lanes_take_the_longest_keys_first_to_the_least_loaded():
    """_pack_lanes is LPT over event counts, at the power-of-two lane
    count whose longest lane costs least; a step's fixed cost of 0 gives
    one lane, a large one a lane a key."""
    model = models.multi_register()
    pre = [wgl.encode_ops_for_model(model, _one_key(
        history(s, max_ops=20 + 15 * s, crash=0.0))) for s in range(6)]
    size = [wgl.event_count(o) for o in pre]
    assert len(set(size)) == 6
    keys = list(range(6))
    old = wgl.DENSE_STEP_CELLS
    try:
        wgl.DENSE_STEP_CELLS = 0
        assert wgl._pack_lanes(keys, pre, 64, 4)[1] == [[5, 4, 3, 2, 1, 0]]
        wgl.DENSE_STEP_CELLS = 1 << 40
        _, wide = wgl._pack_lanes(keys, pre, 64, 4)
        wgl.DENSE_STEP_CELLS = 64 << 4
        _, lanes = wgl._pack_lanes(keys, pre, 64, 4)
    finally:
        wgl.DENSE_STEP_CELLS = old
    # steps dear: as few lanes as keep the longest key the longest lane
    assert max(sum(size[k] for k in lane) for lane in wide) == size[5]
    assert len(wide) < len(keys)
    for packing in (wide, lanes):
        assert sorted(k for lane in packing for k in lane) == keys
        for lane in packing:
            assert [size[k] for k in lane] == sorted(
                (size[k] for k in lane), reverse=True)
    assert 1 < len(lanes) < len(keys)
    loads = [sum(size[k] for k in lane) for lane in lanes]
    assert max(loads) - min(loads) <= max(size)


def test_a_bucket_joins_the_next_wider_where_one_group_costs_less(
        monkeypatch):
    """_plan_groups packs a multi-register batch's slot buckets: a bucket
    joins the next wider where one packed group costs less than two
    (steps dear), and stays apart where the doubled table costs more.
    The keys are never split by length."""
    model = models.multi_register()
    pre = [wgl.encode_ops_for_model(model, _one_key(
        history(s, crash=0.4, limit=10, max_ops=120))) for s in range(12)]
    pre = [wgl.release_crashed(o, 1, DM.crash_kind)[0] for o in pre]
    p = wgl.required_slots_batch(pre)
    assert len(set(p)) > 1
    for cells, n in ((1 << 40, 1), (0, len(set(p)))):
        monkeypatch.setattr(wgl, "DENSE_STEP_CELLS", cells)
        groups = wgl._plan_groups(model, pre, p, "auto")
        assert len(groups) == n
        assert sorted(i for g in groups for i in g.keys) == list(range(12))
        for g in groups:
            assert max(p[i] for i in g.keys) == g.dense[2]


def test_a_key_then_a_reset_frees_every_slot_in_the_initial_state():
    """_then_reset releases a key's crashed writes after its last event
    and appends an op every state takes to the initial one."""
    model = models.multi_register({0: 2})
    h = _one_key(history(3, crash=0.6, max_ops=40))
    ops = wgl.encode_ops_for_model(model, h)
    assert (ops.kind == wgl.KIND_INFO).any()
    fab = DM.reset(model.device_state())
    out = wgl._then_reset(ops, fab)
    assert len(out) == len(ops) + 1
    assert not (out.kind == wgl.KIND_INFO).any()
    end = out.inv[-1]
    assert (out.ret[:-1][ops.kind == wgl.KIND_INFO] == end).all()
    assert end > max(ops.inv.max(), ops.ret[ops.ret != wgl.PENDING_RET].max())
    assert wgl.required_slots(out) == wgl.required_slots(ops)
    for st in range(N_STATES):
        assert models.device_step_multi_register(st, *fab) == \
            (True, model.device_state())


@pytest.mark.parametrize("subkeys", [1, 2, 3])
def test_the_image_closure_is_the_product_closure(subkeys, monkeypatch):
    """The dense kernel decides by each transaction's image as by the
    transition product, at every width the subkeys give (S = 8, 64,
    256)."""
    import dataclasses

    model = models.multi_register()
    hists = [_one_key(history(s, subkeys=subkeys, crash=0.2, limit=8,
                              corrupt=s if s % 2 else None))
             for s in range(6)]
    pre = [wgl.encode_ops_for_model(model, h) for h in hists]
    S = wgl._bucket(wgl._state_range("multi-register", model, pre)[1] + 1,
                    lo=4)
    assert S == {1: 8, 2: 64, 3: 256}[subkeys]
    want = [analysis_host(model, h)["valid?"] for h in hists]

    def verdicts():
        wgl._dense_kernel.cache_clear()
        try:
            return [r["valid?"] for r in wgl.analysis_tpu_batch(
                model, hists, engine="dense")]
        finally:
            wgl._dense_kernel.cache_clear()

    assert wgl.closure_engine("multi-register", S, 8) == wgl.CLOSURE_IMAGE
    image = verdicts()
    monkeypatch.setitem(wgl.DEVICE_MODELS, "multi-register",
                        dataclasses.replace(DM, image=None))
    assert wgl.closure_engine("multi-register", S, 8) != wgl.CLOSURE_IMAGE
    assert image == verdicts() == want


def _cells():
    snap = telemetry.snapshot(prefix="jepsen_tpu_wgl_batch_table_cells")
    return sum(snap.get("jepsen_tpu_wgl_batch_table_cells_total",
                        {}).values())


def _lane_steps(kind):
    snap = telemetry.snapshot(prefix="jepsen_tpu_wgl_batch_lane_steps")
    return sum(v for k, v in snap.get(
        "jepsen_tpu_wgl_batch_lane_steps_total", {}).items()
        if k == f"family=dense,kind={kind}")


def test_table_cells_count_lanes_states_columns_and_steps(monkeypatch):
    """jepsen_tpu_wgl_batch_table_cells_total grows by lanes x S x 2^P x
    steps advanced at each chunk dispatch of a dense group."""
    model = models.multi_register()
    hists = [_one_key(history(s, crash=0.0, max_ops=60)) for s in range(3)]
    groups = []
    real = wgl._check_group

    def spy(model, hists, ops, group, *a, **kw):
        groups.append(group)
        return real(model, hists, ops, group, *a, **kw)

    monkeypatch.setattr(wgl, "_check_group", spy)
    c0, s0 = _cells(), _lane_steps("dispatched")
    rs = wgl.analysis_tpu_batch(model, hists, engine="dense",
                                chunk_entries=16)
    assert all(r["valid?"] is True for r in rs)
    (g,) = groups
    _, S, P = g.dense
    lanes = wgl._bucket(len(g.keys), lo=1)
    steps = (_lane_steps("dispatched") - s0) // lanes
    assert steps > 16                 # more than one chunk
    assert _cells() - c0 == lanes * S * (1 << P) * steps


def test_cas_register_crash_kinds_are_unchanged():
    """The default crash kind is (f, a, b): a cas-register crash gets its
    deadline only at a later crash of the same op."""
    ops = []
    for p, (f, v) in enumerate([("write", 1), ("write", 2), ("write", 1),
                                ("cas", [1, 2]), ("cas", [1, 2])]):
        ops += [{"type": "invoke", "f": f, "value": v, "process": p},
                {"type": "info", "f": f, "value": v, "process": p}]
    enc = wgl.encode_ops_for_model(models.cas_register(), History(ops))
    dm = wgl.DEVICE_MODELS["cas-register"]
    assert dm.crash_kind(1, 2, 3) == (1, 2, 3)
    for kind in (dm.crash_kind, wgl.DeviceModel.crash_kind):
        out, released = wgl.release_crashed(enc, 1, kind)
        assert released
        assert (out.ret == wgl.release_crashed(enc, 1)[0].ret).all()
        # rows 0 and 3 are released at the next of their own kind
        assert out.kind.tolist() == [wgl.KIND_RELEASED, 1,
                                     1, wgl.KIND_RELEASED, 1]
        assert out.ret[0] == enc.inv[2] and out.ret[3] == enc.inv[4]
    # the multi-register's kind is f alone: every crashed write is one
    mr = [{"type": t, "f": "write", "value": [["w", k, v]], "process": p}
          for p, (k, v) in enumerate([(0, 1), (1, 2), (2, 3)])
          for t in ("invoke", "info")]
    enc = wgl.encode_ops_for_model(models.multi_register(), History(mr))
    out, released = wgl.release_crashed(enc, 1, DM.crash_kind)
    assert released and out.ret[:2].tolist() == enc.inv[1:].tolist()
    assert not wgl.release_crashed(enc, 1)[1]


def test_the_cost_model_prices_the_image_closure():
    """A model with an image takes the dense family wherever its table
    fits, by reason `image`: the sort family's frontier would have to
    hold the table. A model without one is priced as before."""
    for p in (10, 11, 14):
        dec = wgl.select_engine((0, N_STATES - 1), p, 3000,
                                model="multi-register", frontier=1024)
        assert dec.family == "dense" and dec.dense == (0, 256, p)
        assert "image" in dec.reason
    # past the table's caps the sort family takes it
    assert wgl.select_engine((0, N_STATES - 1), 15, 3000,
                             model="multi-register").family == "sort"
    assert wgl.select_engine((0, 215), 11, 3000, model="cas-register",
                             frontier=1024).family == "sort"
    with pytest.raises(TypeError):
        wgl.select_engine((0, 215), 11, 3000)       # the model is required


@pytest.mark.parametrize("subkeys", [1, 3])
def test_packed_states_match_the_model(subkeys):
    for vals in itertools.product([None, 0, 4], repeat=subkeys):
        m = models.multi_register({k: v for k, v in enumerate(vals)
                                   if v is not None})
        assert _unpack(m.device_state()) == dict(m.values)


def test_a_stream_refuses_an_image_model():
    """WglStream has no stream for a model with an image yet, declared
    state range or not; an online run's target builders, local and the
    service's, decline it, so the offline check decides."""
    from jepsen_tpu import service
    from jepsen_tpu.checker import streaming

    model = models.multi_register()
    for kw in ({}, {"state_range": (0, N_STATES - 1), "engine": "dense"}):
        with pytest.raises(ValueError, match="no stream"):
            streaming.WglStream(model, **kw)
    test = {"online": True, "concurrency": 4,
            "checker": linearizable(model)}
    assert streaming.maybe_online(test) is None
    assert "linear" not in service.targets_spec(test)


def test_a_fetched_word_table_verifies_in_any_layout():
    """abft.verify_carry counts a dense word table's bits however the
    fetch laid it out (a device fetch may hand back a strided array)."""
    from jepsen_tpu.checker import abft

    words = np.zeros((256, 8), np.uint32)
    words[3, 1], words[200, 7] = 0b1011, 1 << 31
    for table in (words, np.asfortranarray(words), words.T.copy().T):
        carry = (np.int32(5), table, np.zeros(6, np.int32),
                 np.zeros(6, np.int32), np.zeros(6, np.int32),
                 np.zeros(6, bool), np.int32(0), np.int32(4), np.int32(4))
        abft.verify_carry("test", abft.carry_digest_host(carry), carry)
    with pytest.raises(Exception, match="count"):
        bad = carry[:7] + (np.int32(5), np.int32(5))
        abft.verify_carry("test", abft.carry_digest_host(bad), bad)
