"""Pallas hash-dedup kernel (checker/wgl_dedup.py): kernel-level
exactness, interpret-mode parity with the XLA sort path across the
offline and streaming entries, and the engine cost-model autoselect.

The parity matrix pins the module contract: on shapes where the sort
path does not overflow, the hash-dedup kernel family produces the same
summaries (valid?, max-frontier) and the same blame certificates
(op-index) — offline, batched, mesh-sharded, and through
`check_stream_chunk`.
Shapes are kept small and shared (tier-1 budget); the broader sweep is
marked slow.
"""

from __future__ import annotations

import numpy as np
import pytest

from jepsen_tpu import models
from jepsen_tpu.checker import streaming, synth, wgl, wgl_dedup
from jepsen_tpu.history import History

MODEL = models.cas_register()

# one sort-family shape shared by every device test here: F=256, P=16
FRONTIER = 256
SLOTS = 16


def _hist(n=120, conc=4, seed=0, crash=0.02):
    return synth.register_history(n, concurrency=conc, values=4,
                                  crash_rate=crash, seed=seed)


def _corrupt_packed(h, seed=0):
    """synth.corrupt, but with a small out-of-domain value (9 instead
    of 10**6) so the state range stays narrow enough to pack — the
    corrupted run must exercise the HASH dedup's blame path, not fall
    back to the multi-word sort."""
    import random
    rng = random.Random(seed)
    ops = [dict(o) for o in h.ops]
    reads = [i for i, o in enumerate(ops)
             if o["type"] == "ok" and o["f"] == "read"]
    ops[rng.choice(reads)]["value"] = 9
    return History(ops)


def _run(h, pallas, **kw):
    return wgl.analysis_tpu(MODEL, h, frontier=FRONTIER, slots=SLOTS,
                            engine="sort", pallas=pallas, **kw)


# -- kernel-level exactness -------------------------------------------------

def test_kernel_dedup_first_seen_order_and_new_flags():
    N, F = 64, 16
    fn = wgl_dedup.dedup_fn(N, F, interpret=True)
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 12, N).astype(np.int32)
    keys[rng.random(N) < 0.25] = wgl_dedup.EMPTY
    out, new, cnt, dig = map(np.asarray, fn(keys))
    # reference: first-seen order over valid keys
    seen: dict = {}
    for i, k in enumerate(keys.tolist()):
        if k >= 0 and k not in seen:
            seen[k] = i >= F
    want = list(seen.items())
    assert out[:len(want)].tolist() == [k for k, _ in want]
    assert new[:len(want)].tolist() == [n for _, n in want]
    assert int(cnt) == len(want)
    assert (out[len(want):] == wgl_dedup.EMPTY).all()
    assert not new[len(want):].any()
    # the table-occupancy XOR digest matches a host recompute over the
    # distinct keys — the cross-check wgl.dedup_hash folds into att
    exp = 0
    for k, _ in want:
        exp ^= k
    exp ^= (len(want) * wgl_dedup.DIGEST_COUNT_MIX) & 0xFFFFFFFF
    exp &= 0xFFFFFFFF
    if exp >= 1 << 31:
        exp -= 1 << 32
    assert int(dig) == exp


def test_kernel_dedup_overflow_counts_all_distinct():
    N, F = 64, 8
    fn = wgl_dedup.dedup_fn(N, F, interpret=True)
    keys = np.arange(N, dtype=np.int32)          # all distinct
    out, new, cnt, _dig = map(np.asarray, fn(keys))
    assert int(cnt) == N                         # > F: overflow signal
    assert out.tolist() == list(range(F))        # first F kept
    assert (~new[:F]).sum() == F                 # all old-segment rows


def test_kernel_dedup_all_empty():
    fn = wgl_dedup.dedup_fn(32, 8, interpret=True)
    out, new, cnt, dig = map(np.asarray, fn(np.full(32, -1, np.int32)))
    assert int(cnt) == 0 and (out == wgl_dedup.EMPTY).all()
    assert int(dig) == 0


def test_eligibility_bounds():
    assert wgl_dedup.eligible(256, 16)
    assert wgl_dedup.eligible(1024, 16)
    # F=65536 x P=32: ~2.1M keys + 8.4M-slot table blow the VMEM gate
    assert not wgl_dedup.eligible(65536, 32)
    # capacity accounting: keys + table + 2 output buffers
    n = 1024 * 17
    assert wgl_dedup.table_size(n) == 2 * 32768


# -- interpret-mode parity matrix vs the sort path --------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_parity_valid_histories(seed):
    h = _hist(seed=seed)
    a = _run(h, pallas=False)
    b = _run(h, pallas=True)
    assert a["dedup"] == wgl.DEDUP_SORT
    assert b["dedup"] == wgl.DEDUP_PALLAS
    assert a["valid?"] is b["valid?"] is True
    # no overflow on this shape: frontiers are set-equal, so the peak
    # count matches exactly
    assert a["max-frontier"] == b["max-frontier"]


@pytest.mark.parametrize("seed", [0, 1])
def test_parity_blame_identity(seed):
    bad = _corrupt_packed(_hist(seed=seed), seed=seed)
    a = _run(bad, pallas=False)
    b = _run(bad, pallas=True)
    assert b["dedup"] == wgl.DEDUP_PALLAS   # still packed
    assert a["valid?"] is b["valid?"] is False
    assert a.get("op-index") == b.get("op-index")
    assert a.get("op") == b.get("op")


def test_parity_mutex_model():
    ops = []
    for i in range(12):
        p = i % 3
        ops += [{"type": "invoke", "f": "acquire", "value": None,
                 "process": p, "time": 2 * i},
                {"type": "ok", "f": "acquire", "value": None,
                 "process": p, "time": 2 * i + 1},
                {"type": "invoke", "f": "release", "value": None,
                 "process": p, "time": 2 * i + 1},
                {"type": "ok", "f": "release", "value": None,
                 "process": p, "time": 2 * i + 2}]
    h = History(ops)
    a = wgl.analysis_tpu(models.mutex(), h, frontier=FRONTIER,
                         slots=SLOTS, engine="sort", pallas=False)
    b = wgl.analysis_tpu(models.mutex(), h, frontier=FRONTIER,
                         slots=SLOTS, engine="sort", pallas=True)
    assert a["valid?"] is b["valid?"] is True
    assert a["max-frontier"] == b["max-frontier"]


def test_unpacked_shapes_keep_the_sort():
    """Wide masks (P=64 -> W=2) have no packed key: pallas=True must
    transparently keep the lexicographic sort, same verdict."""
    h = _hist(seed=2)
    a = wgl.analysis_tpu(MODEL, h, frontier=FRONTIER, slots=64,
                         engine="sort", pallas=True)
    assert a["dedup"] == wgl.DEDUP_SORT
    assert a["valid?"] is True


def test_hash_dedup_tighter_under_frontier_pressure():
    """The documented divergence: sorted duplicate runs make the sort
    path overflow conservatively; the hash path only overflows when
    the distinct count itself exceeds F — so at a tight frontier the
    hash path may keep MORE configs, never fewer, and 'valid' verdicts
    agree."""
    h = synth.register_history(120, concurrency=5, values=4,
                               crash_rate=0.05, seed=7)
    a = _run(h, pallas=False)
    b = _run(h, pallas=True)
    assert a["valid?"] is b["valid?"] is True
    assert b["max-frontier"] >= a["max-frontier"]


def test_batch_parity():
    hs = [_hist(seed=s) for s in (0, 1)]
    hs.append(_corrupt_packed(hs[0], seed=0))
    a = wgl.analysis_tpu_batch(MODEL, hs, frontier=FRONTIER,
                               slots=SLOTS, engine="sort", pallas=False)
    b = wgl.analysis_tpu_batch(MODEL, hs, frontier=FRONTIER,
                               slots=SLOTS, engine="sort", pallas=True)
    assert [r["valid?"] for r in a] == [r["valid?"] for r in b] \
        == [True, True, False]
    assert [r.get("op-index") for r in a] == \
        [r.get("op-index") for r in b]
    assert b[0]["dedup"] == wgl.DEDUP_PALLAS


def test_sharded_parity_and_group_info():
    """check_batch_sharded threads the same knobs: dedup on/off agrees
    per key, and return_info reports which family/dedup each dispatch
    group ran (the bench config-4 artifact). Same (F, P) shape as the
    rest of the module so the kernels are shared."""
    hs = [_hist(seed=s) for s in (0, 1)] + \
        [_corrupt_packed(_hist(seed=0), seed=0)]
    kw = dict(frontier=FRONTIER, slots=SLOTS, engine="sort")
    all_a, per_a = wgl.check_batch_sharded(MODEL, hs, pallas=False,
                                           **kw)
    all_b, per_b, info = wgl.check_batch_sharded(
        MODEL, hs, pallas=True, return_info=True, **kw)
    assert all_a is all_b is False
    assert per_a.tolist() == per_b.tolist() == [True, True, False]
    assert info["groups"] and all(
        g["family"] == "sort" and g["dedup"] == wgl.DEDUP_PALLAS
        for g in info["groups"])
    assert sum(g["keys"] for g in info["groups"]) == len(hs)


# -- streaming entry (check_stream_chunk) -----------------------------------

def test_stream_chunk_resume_verdict_and_blame_identity():
    """A declared state range packs the online sort stream; dedup
    on/off must produce identical streamed verdicts and blame across
    chunk boundaries."""
    h = synth.register_history(300, concurrency=4, values=4,
                               crash_rate=0.02, seed=11)
    kw = dict(chunk_entries=128, slots=8, state_range=(-1, 3))
    r_on = streaming.stream_check(MODEL, h, pallas=True, **kw)
    r_off = streaming.stream_check(MODEL, h, pallas=False, **kw)
    assert r_on["dedup"] == wgl.DEDUP_PALLAS
    assert r_off["dedup"] == wgl.DEDUP_SORT
    assert r_on["valid?"] is r_off["valid?"] is True
    assert r_on["chunks"] == r_off["chunks"] > 1

    # the corrupt value (9) stays inside a wider declared range, so
    # the packed stream never range-escapes and blame stays on-device
    bad = _corrupt_packed(h, seed=4)
    kw_bad = dict(chunk_entries=128, slots=8, state_range=(-1, 9))
    b_on = streaming.stream_check(MODEL, bad, pallas=True, **kw_bad)
    b_off = streaming.stream_check(MODEL, bad, pallas=False, **kw_bad)
    assert b_on["dedup"] == wgl.DEDUP_PALLAS
    assert b_on["valid?"] is b_off["valid?"] is False
    assert b_on.get("op-index") == b_off.get("op-index")


def test_stream_range_escape_downgrades_packed_sort():
    """Values outside the declared range must drop the packed key (and
    its hash dedup) and replay on the unpacked sort kernel — verdict
    preserved."""
    h = synth.register_history(80, concurrency=4, values=6,
                               crash_rate=0.0, seed=5)
    r = streaming.stream_check(MODEL, h, chunk_entries=64, slots=8,
                               state_range=(-1, 2), pallas=True)
    assert r["valid?"] is True
    assert r["dedup"] == wgl.DEDUP_SORT


# -- engine autoselect (cost model) -----------------------------------------

def _select(srange, p, n, **kw):
    return wgl.select_engine(srange, p, n, model=MODEL.device_model, **kw)


def test_select_engine_prefers_dense_on_small_tables():
    d = _select((-1, 4), 8, 1000)
    assert d.family == "dense" and d.dense is not None
    assert d.dedup == wgl.DEDUP_NONE


def test_select_engine_routes_big_tables_to_sort():
    # S=512 x 2^13 fits the dense caps but its modeled closure work
    # dwarfs the sort family's — the cost model must route it away
    d = _select((0, 400), 13, 10_000)
    assert d.family == "sort"
    assert "dense" in d.reason


def test_select_engine_dense_slot_cap():
    d = _select((-1, 4), 8, 1000, dense_slot_cap=6)
    assert d.family == "sort" and "dense_slot_cap" in d.reason
    with pytest.raises(ValueError):
        _select((-1, 4), 8, 1000, engine="dense", dense_slot_cap=6)


def test_select_engine_forced_families():
    assert _select((-1, 4), 8, 100, engine="dense").family == "dense"
    assert _select((-1, 4), 8, 100, engine="sort").family == "sort"
    with pytest.raises(ValueError):
        _select((-1, 4), 8, 100, engine="nope")
    # forced dense past the caps still raises (offline contract)
    with pytest.raises(ValueError):
        _select((0, 10 ** 6), 8, 100, engine="dense")


def test_checker_options_flow_through_linearizable():
    """Linearizable(engine=..., dense_slot_cap=..., pallas=...) — the
    doc/plan.md 'Checkers' graduation — reaches the device engine."""
    from jepsen_tpu.checker.linear import Linearizable

    h = _hist(n=60, seed=3)
    c = Linearizable(MODEL, engine="sort", frontier=FRONTIER,
                     slots=SLOTS, pallas=True)
    r = c.check({}, h, {})
    assert r["valid?"] is True and r["dedup"] == wgl.DEDUP_PALLAS
    c2 = Linearizable(MODEL, dense_slot_cap=2)
    r2 = c2.check({}, h, {})
    assert r2["valid?"] is True and r2["analyzer"] == "tpu-wgl"


def test_env_gate_flips_next_call(monkeypatch):
    """JEPSEN_TPU_PALLAS_DEDUP resolves outside the kernel cache — the
    wgl_pallas closure contract, applied to the dedup gate."""
    h = _hist(n=60, seed=4)
    monkeypatch.setenv("JEPSEN_TPU_PALLAS_DEDUP", "1")
    a = _run(h, pallas=None)
    assert a["dedup"] == wgl.DEDUP_PALLAS
    monkeypatch.setenv("JEPSEN_TPU_PALLAS_DEDUP", "0")
    b = _run(h, pallas=None)
    assert b["dedup"] == wgl.DEDUP_SORT
    assert a["valid?"] is b["valid?"] is True


def _pretend_tpu(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_hash_dedup_off_by_default_on_tpu(monkeypatch):
    """On a TPU backend the sort dedup is the default (Mosaic refuses
    the hash kernel today); the closure kernel stays on."""
    monkeypatch.delenv(wgl.PALLAS_DEDUP_ENV, raising=False)
    monkeypatch.delenv(wgl.PALLAS_CLOSURE_ENV, raising=False)
    _pretend_tpu(monkeypatch)
    assert wgl._pallas_enabled(wgl.PALLAS_DEDUP_ENV) == (False, True)
    assert wgl._pallas_enabled(wgl.PALLAS_CLOSURE_ENV) == (True, True)
    pack = wgl._pack_params((-1, 3), SLOTS)
    assert pack is not None and wgl._hash_gate(FRONTIER, SLOTS, pack)
    assert wgl.dedup_engine(FRONTIER, SLOTS, pack) == wgl.DEDUP_SORT


def test_hash_dedup_opt_in_raises_on_refused_compile(monkeypatch):
    """JEPSEN_TPU_PALLAS_DEDUP=1 on a TPU builds the Mosaic kernel, and
    a refused compile raises out of the kernel call instead of quietly
    switching to the sort dedup. (Off the chip, the non-interpret
    Pallas call is refused just the same.)"""
    monkeypatch.setenv(wgl.PALLAS_DEDUP_ENV, "1")
    _pretend_tpu(monkeypatch)
    pack = wgl._pack_params((-1, 3), SLOTS)
    assert wgl.dedup_engine(FRONTIER, SLOTS, pack) == wgl.DEDUP_PALLAS
    k = wgl._kernel(MODEL.device_model, FRONTIER, SLOTS, 64, pack)
    steps = wgl.build_steps(wgl.encode_ops_for_model(MODEL, _hist(n=20)),
                            SLOTS).pad_to(64)
    monkeypatch.undo()   # dispatch on the real (CPU) backend
    with pytest.raises(Exception):
        k.check(steps.x, np.int32(steps.n),
                np.int32(MODEL.device_state()))
    wgl._kernel.cache_clear()


# -- broader sweep: excluded from tier-1 ------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("conc,crash", [(4, 0.02), (5, 0.03)])
def test_parity_sweep(seed, conc, crash):
    # same (FRONTIER, SLOTS) shape as the tier-1 matrix so the sweep
    # reuses its compiled kernels, and kept below the overflow regime:
    # interpret-mode pallas is serial per key, so an escalation (F x4
    # recompiles + 4x-wider serial dedup loops) would blow the CI
    # budget — high-pressure shapes are the hardware round's job
    h = synth.register_history(160, concurrency=conc, values=4,
                               crash_rate=crash, seed=100 + seed)
    for hist in (h, _corrupt_packed(h, seed=seed)):
        a = _run(hist, pallas=False)
        b = _run(hist, pallas=True)
        assert a["valid?"] == b["valid?"]
        assert a.get("op-index") == b.get("op-index")
