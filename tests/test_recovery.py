"""Device-fault recovery: the checkers' recovery ladders.

The contract under test (checker/wgl.py, checker/streaming.py,
_platform.py): a classified backend fault — OOM, device loss,
compile failure, a wedged sync — mid-check yields a *resumed verdict*
identical to an uninterrupted run's, carrying a 'recovered' trail,
instead of the old terminal {'valid?': unknown, 'degraded': True}.
Faults are injected deterministically via _platform.fault_hook /
JEPSEN_TPU_FAULT_INJECT; no hardware is involved.

Shapes are shared with tests/test_streaming.py (chunk 128, 8 slots,
seed-13 histories that fit 8 slots without a rebuild) so tier-1 pays
each kernel compile once.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jepsen_tpu._platform as plat
from jepsen_tpu import models, telemetry
from jepsen_tpu.checker import (Checker, Compose, UNKNOWN, check_safe,
                                linear, streaming, synth, wgl)
import jepsen_tpu.control.retry as retry

MODEL = models.cas_register()
CHUNK = 128
SLOTS = 8   # seed-13 histories need 6 slots: no mid-stream rebuild,
            # so carry checkpoints survive to the injected fault


@pytest.fixture(autouse=True)
def _fast_deterministic_faults(monkeypatch):
    """Zero the recovery backoff (the ladders sleep between retries in
    production) and isolate each test's injection schedule."""
    monkeypatch.setattr(retry, "backoff",
                        lambda *a, **k: iter([0.0] * 1000))
    plat.reset_fault_injection()
    yield
    plat.fault_hook = None
    plat.reset_fault_injection()


def _hist(seed=13, n=400, conc=4):
    return synth.register_history(n, concurrency=conc, values=5,
                                  seed=seed)


def _one_shot(kind, site, at=1):
    """fault_hook raising InjectedFault(kind) at the at-th dispatch on
    site, once — a transient fault, like a real one."""
    state = {"n": 0}

    def hook(s):
        if s == site:
            state["n"] += 1
            if state["n"] == at:
                raise plat.InjectedFault(kind, s, state["n"])
    return hook


def _always(kind, site):
    """fault_hook raising on every dispatch on site — a dead backend."""
    def hook(s):
        if s == site:
            raise plat.InjectedFault(kind, s, 0)
    return hook


# -- classify_backend_error -------------------------------------------------

@pytest.mark.parametrize("msg,bucket", [
    ("RESOURCE_EXHAUSTED: out of memory allocating 2g", "oom"),
    ("INTERNAL: failed to allocate device buffer", "oom"),
    ("UNAVAILABLE: device lost, preempted by scheduler", "device-lost"),
    ("INTERNAL: Mosaic lowering failed", "compile"),
    ("DEADLINE_EXCEEDED: collective timed out", "wedged"),
    ("INTERNAL: something opaque", "wedged"),   # xla but unmatched
])
def test_classifier_buckets_xla_errors(msg, bucket):
    from jax.errors import JaxRuntimeError
    assert plat.classify_backend_error(JaxRuntimeError(msg)) == bucket


def test_compilation_cache_uses_the_environment_dir(monkeypatch, tmp_path):
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert plat.use_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compilation_cache_defaults_to_the_checkout(monkeypatch):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert plat.use_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_classifier_rejects_ordinary_exceptions():
    # a checker bug raised as RuntimeError must NOT classify — even
    # with an OOM-looking message — or recovery would mask real bugs
    assert plat.classify_backend_error(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory")) is None
    assert plat.classify_backend_error(ValueError("oom")) is None


def test_classifier_accepts_plain_backend_init_failures():
    # ...except backend-init failures, which jax's xla_bridge raises
    # as PLAIN RuntimeErrors — unambiguously the device falling over
    assert plat.classify_backend_error(RuntimeError(
        "Unable to initialize backend 'tpu': UNAVAILABLE")) \
        == plat.FAULT_DEVICE_LOST
    assert plat.classify_backend_error(RuntimeError(
        "INTERNAL: Failed to initialize TPU system")) \
        == plat.FAULT_DEVICE_LOST
    # subclasses don't get the carve-out (they aren't xla_bridge's)
    class MyError(RuntimeError):
        pass
    assert plat.classify_backend_error(MyError(
        "unable to initialize backend")) is None


def test_classifier_recognizes_module_fault_types():
    for kind in plat.FAULT_KINDS:
        e = plat.InjectedFault(kind, "t", 1)
        assert plat.classify_backend_error(e) == kind
    assert plat.classify_backend_error(
        plat.WedgedDeviceSync("blocked")) == plat.FAULT_WEDGED


# -- the injection shim -----------------------------------------------------

def test_env_spec_fires_once_at_nth_dispatch(monkeypatch):
    monkeypatch.setenv(plat.FAULT_INJECT_ENV, "oom@here:2")
    plat.maybe_inject_fault("here")           # dispatch 1: clean
    plat.maybe_inject_fault("elsewhere")      # other site: never
    with pytest.raises(plat.InjectedFault) as ei:
        plat.maybe_inject_fault("here")       # dispatch 2: fires
    assert ei.value.kind == "oom"
    plat.maybe_inject_fault("here")           # dispatch 3: spent


def test_env_spec_default_seq_and_reset(monkeypatch):
    monkeypatch.setenv(plat.FAULT_INJECT_ENV, "device-lost@s")
    with pytest.raises(plat.InjectedFault):
        plat.maybe_inject_fault("s")          # :n defaults to 1
    plat.maybe_inject_fault("s")
    plat.reset_fault_injection()
    with pytest.raises(plat.InjectedFault):
        plat.maybe_inject_fault("s")          # counters rewound


# -- the watchdog -----------------------------------------------------------

def test_wedged_sync_watchdog(monkeypatch):
    import time

    import jax
    monkeypatch.setattr(jax, "device_get",
                        lambda x: time.sleep(30) or x)
    with pytest.raises(plat.WedgedDeviceSync) as ei:
        plat.guarded_device_get(1, deadline_s=0.05, site="test sync")
    assert plat.classify_backend_error(ei.value) == plat.FAULT_WEDGED


def test_watchdog_disabled_without_deadline(monkeypatch):
    monkeypatch.delenv(plat.SYNC_DEADLINE_ENV, raising=False)
    assert plat.guarded_device_get(np.int32(7)) == 7


# -- offline entry: analysis_tpu --------------------------------------------

@pytest.fixture(scope="module")
def offline_baseline():
    return wgl.analysis_tpu(MODEL, _hist())


@pytest.mark.parametrize("kind", plat.FAULT_KINDS)
def test_offline_fault_recovers_with_identical_verdict(
        kind, offline_baseline):
    plat.fault_hook = _one_shot(kind, "offline")
    a = wgl.analysis_tpu(MODEL, _hist())
    assert a["valid?"] == offline_baseline["valid?"] is True
    assert a["recovered"] == {"faults": [kind], "retries": 1}
    assert not a.get("degraded")


def test_offline_exhausted_budget_decides_on_host(offline_baseline):
    plat.fault_hook = _always("device-lost", "offline")
    a = wgl.analysis_tpu(MODEL, _hist(), max_recovery_retries=1)
    assert a["valid?"] == offline_baseline["valid?"] is True
    assert a["recovered"]["fallback"] == "host"
    assert a["recovered"]["faults"] == ["device-lost"] * 2
    assert "host" in a["analyzer"]


def test_offline_exhausted_budget_over_host_cap_degrades(monkeypatch):
    monkeypatch.setattr(wgl, "HOST_FALLBACK_MAX_OPS", 0)
    plat.fault_hook = _always("wedged", "offline")
    a = wgl.analysis_tpu(MODEL, _hist(), max_recovery_retries=1)
    assert a["valid?"] is UNKNOWN
    assert a["degraded"] is True
    assert a["recovery-failed"]["faults"] == ["wedged"] * 2


def test_offline_checker_bug_is_not_absorbed():
    # a plain RuntimeError from inside the entry must escape the
    # ladder untouched (classify returns None)
    def hook(site):
        if site == "offline":
            raise RuntimeError("a checker bug, not a device fault")
    plat.fault_hook = hook
    with pytest.raises(RuntimeError, match="checker bug"):
        wgl.analysis_tpu(MODEL, _hist())


def test_offline_env_knob_end_to_end(monkeypatch):
    monkeypatch.setenv(plat.FAULT_INJECT_ENV, "oom@offline:1")
    a = wgl.analysis_tpu(MODEL, _hist())
    assert a["valid?"] is True
    assert a["recovered"]["faults"] == ["oom"]


# -- batch + sharded entries ------------------------------------------------

BATCH_SEEDS = (10, 11, 12, 13)


def _batch_hists():
    return [_hist(seed=s, n=120, conc=3) for s in BATCH_SEEDS]


@pytest.fixture(scope="module")
def batch_baseline():
    return [r["valid?"] for r in
            wgl.analysis_tpu_batch(MODEL, _batch_hists())]


@pytest.mark.parametrize("kind", plat.FAULT_KINDS)
def test_batch_fault_recovers_with_identical_verdicts(
        kind, batch_baseline):
    plat.fault_hook = _one_shot(kind, "batch")
    rs = wgl.analysis_tpu_batch(MODEL, _batch_hists())
    assert [r["valid?"] for r in rs] == batch_baseline
    assert any(r.get("recovered") for r in rs)
    assert not any(r.get("degraded") for r in rs)


def _batch_deadline_checks():
    snap = telemetry.snapshot(prefix="jepsen_tpu_wgl_deadline_checks")
    return sum(v for labels, v in snap.get(
        "jepsen_tpu_wgl_deadline_checks_total", {}).items()
        if "site=batch" in labels)


@pytest.mark.parametrize("kind", plat.FAULT_KINDS)
def test_a_single_group_batch_fault_retries_only_its_group(
        kind, batch_baseline, monkeypatch):
    """A fault inside a batch that plans to one dispatch group retries
    that group alone: every history is encoded once and checked in one
    round, and the verdicts are the uninterrupted run's."""
    hists = _batch_hists()[1:]   # slot needs 5, 5, 5: one bucket
    ops = [wgl.release_crashed(wgl.encode_ops_for_model(MODEL, h), 1)[0]
           for h in hists]
    assert len(wgl._plan_groups(MODEL, ops, [wgl.required_slots(o)
                                             for o in ops], "auto")) == 1
    encoded = []
    real = wgl.encode_ops_for_model

    def spy(model, hist):
        encoded.append(hist)
        return real(model, hist)

    monkeypatch.setattr(wgl, "encode_ops_for_model", spy)
    before = _batch_deadline_checks()
    plat.fault_hook = _one_shot(kind, "batch")
    rs = wgl.analysis_tpu_batch(MODEL, hists)
    assert [r["valid?"] for r in rs] == batch_baseline[1:]
    assert all(r["recovered"]["faults"] == [kind] for r in rs)
    assert not any(r.get("degraded") for r in rs)
    assert len(encoded) == len(hists)
    assert _batch_deadline_checks() - before == len(hists)


def _oom_at(*dispatches):
    """fault_hook raising an OOM at the given batch dispatches."""
    state = {"n": 0}

    def hook(s):
        if s == "batch":
            state["n"] += 1
            if state["n"] in dispatches:
                raise plat.InjectedFault(plat.FAULT_OOM, s, state["n"])
    return hook


@pytest.mark.parametrize("seeds,dispatches", [
    ((10,), (1,)),            # a one-key batch
    ((11, 12), (1, 2)),       # the first half of a split group OOMs again
])
def test_a_lone_dense_key_that_ooms_moves_to_the_sort_family(
        seeds, dispatches):
    """An OOM on a dense group of one key, which cannot split, retries
    that key on the sort family (dense_slot_cap 0), with the verdict of
    the uninterrupted run; the other keys keep the dense kernel."""
    hists = [_hist(seed=s, n=120, conc=3) for s in seeds]
    r0 = wgl.analysis_tpu_batch(MODEL, hists)
    assert all(r["dedup"] == "dense-table" for r in r0)
    plat.fault_hook = _oom_at(*dispatches)
    rs = wgl.analysis_tpu_batch(MODEL, hists)
    assert [r["valid?"] for r in rs] == [r["valid?"] for r in r0]
    assert not any(r.get("degraded") for r in rs)
    lone, *rest = rs
    assert lone["dedup"] == "xla-sort"
    assert "dense_slot_cap=0" in lone["engine-reason"]
    assert lone["recovered"]["faults"] == [plat.FAULT_OOM] * len(dispatches)
    assert all(r["dedup"] == "dense-table" for r in rest)


@pytest.fixture(scope="module")
def sharded_baseline():
    ok, pk = wgl.check_batch_sharded(MODEL, _batch_hists())
    return ok, pk


@pytest.mark.parametrize("kind", plat.FAULT_KINDS)
def test_sharded_fault_recovers_with_identical_verdicts(
        kind, sharded_baseline):
    ok0, pk0 = sharded_baseline
    plat.fault_hook = _one_shot(kind, "sharded")
    ok, pk, info = wgl.check_batch_sharded(MODEL, _batch_hists(),
                                           return_info=True)
    assert ok == ok0 and (pk == pk0).all()
    rec = info["recovered"]
    assert rec["faults"][0] == kind
    if kind == plat.FAULT_OOM:
        # the OOM rung splits the key batch and recovers each half
        assert rec["split"] is True


def test_sharded_undecided_keys_are_not_fabricated_anomalies(monkeypatch):
    # every entry faults forever AND the host mirror is capped out:
    # the fallback cannot decide any key. per_key False then means
    # 'unverified' — the info must say so, not claim recovery
    monkeypatch.setattr(wgl, "HOST_FALLBACK_MAX_OPS", 0)

    def hook(site):
        if site in ("sharded", "batch"):
            raise plat.InjectedFault("wedged", site, 0)
    plat.fault_hook = hook
    ok, pk, info = wgl.check_batch_sharded(
        MODEL, _batch_hists(), return_info=True,
        max_recovery_retries=0)
    assert ok is False and not pk.any()
    assert info["degraded"] is True
    assert info["unknown-keys"] == list(range(len(pk)))
    assert "recovered" not in info
    assert info["recovery-failed"]["faults"] == ["wedged"]


def test_sharded_exhausted_budget_falls_back_to_batch(sharded_baseline):
    ok0, pk0 = sharded_baseline
    plat.fault_hook = _always("device-lost", "sharded")
    ok, pk, info = wgl.check_batch_sharded(
        MODEL, _batch_hists(), return_info=True,
        max_recovery_retries=0)
    assert ok == ok0 and (pk == pk0).all()
    assert info["recovered"]["fallback"] == "batch"


# -- streaming: checkpointed carry + resume ---------------------------------

def _stream(hist, family, hook=None, checkpoint_every=2, **kw):
    plat.fault_hook = hook
    try:
        s = streaming.WglStream(
            MODEL, chunk_entries=CHUNK, slots=SLOTS,
            checkpoint_every=checkpoint_every, engine=family,
            state_range=(-1, 4) if family == "dense" else None, **kw)
        for op in hist.ops:
            s.feed(op)
        return s, s.finish()
    finally:
        plat.fault_hook = None


def _stream_bytes(s):
    return (np.concatenate(s._steps_log) if s._steps_log
            else np.zeros((0, 1), np.int32))


@pytest.fixture(scope="module")
def stream_baselines():
    # computed once per family; the fault runs below must match these
    out = {}
    for family in ("sort", "dense"):
        plat.reset_fault_injection()
        s, r = _stream(_hist(), family)
        out[family] = (r, _stream_bytes(s))
    return out


@pytest.mark.parametrize("family", ["sort", "dense"])
@pytest.mark.parametrize("kind", plat.FAULT_KINDS)
def test_stream_mid_chunk_fault_resumes_identically(
        kind, family, stream_baselines):
    """The acceptance matrix: a fault killed at chunk 3 (checkpoint
    cadence 2) resumes from the chunk-2 carry checkpoint and produces
    a byte-identical step stream and identical verdict."""
    r0, bytes0 = stream_baselines[family]
    s, r = _stream(_hist(), family,
                   hook=_one_shot(kind, "stream-chunk", at=3))
    assert r["valid?"] == r0["valid?"] is True
    assert r["op-count"] == r0["op-count"]
    rec = r["recovered"]
    assert rec["faults"] == [kind] and rec["retries"] == 1
    b = _stream_bytes(s)
    assert b.shape == bytes0.shape and (b == bytes0).all()
    if family == "dense" and kind == plat.FAULT_OOM:
        # dense OOM re-selects onto the sort family; its checkpoint
        # cannot seed a sort carry, so the resume replays cold
        assert rec["resumed-from-chunk"] == 0
        assert "dense" not in r["analyzer"]
    else:
        assert rec["resumed-from-chunk"] == 2


@pytest.mark.parametrize("family", ["sort", "dense"])
def test_stream_fault_preserves_blame_certificate(family):
    bad = synth.corrupt(_hist(), seed=3)
    s0, r0 = _stream(bad, family)
    s1, r1 = _stream(bad, family,
                     hook=_one_shot("device-lost", "stream-chunk",
                                    at=3))
    assert r0["valid?"] is False and r1["valid?"] is False
    assert r1["op-index"] == r0["op-index"]
    assert r1["op"] == r0["op"]
    b0, b1 = _stream_bytes(s0), _stream_bytes(s1)
    assert b0.shape == b1.shape and (b0 == b1).all()


def test_stream_oom_backpressure_halves_chunk():
    s, r = _stream(_hist(), "sort",
                   hook=_one_shot("oom", "stream-chunk", at=3))
    assert s.chunk == CHUNK // 2
    assert r["valid?"] is True


def test_stream_exhausted_budget_disables_stream():
    # past the budget the stream reports None: core.run's offline
    # re-check path (whose own ladder ends at the host mirror) covers
    attempts = {"n": 0}
    dead = _always("device-lost", "stream-chunk")

    def hook(site):
        if site == "stream-chunk":
            attempts["n"] += 1
        dead(site)

    s, r = _stream(_hist(), "sort", hook=hook, max_recovery_retries=1)
    assert r is None
    assert s._failed is not None
    # once the budget is spent the drain stops: the initial dispatch
    # plus one retry, never one attempt per remaining tail chunk
    # against the dead backend
    assert attempts["n"] == 2


def test_stream_checkpoint_disabled_replays_cold():
    s, r = _stream(_hist(), "sort", checkpoint_every=0,
                   hook=_one_shot("wedged", "stream-chunk", at=3))
    assert r["valid?"] is True
    assert r["recovered"]["resumed-from-chunk"] == 0


# -- check_safe / Compose routing -------------------------------------------

class _Raises(Checker):
    def __init__(self, exc):
        self.exc = exc

    def check(self, test, hist, opts):
        raise self.exc


def test_check_safe_reports_classified_fault_as_degraded():
    r = check_safe(_Raises(plat.InjectedFault("oom", "t", 1)), {}, [])
    assert r["valid?"] is UNKNOWN
    assert r["degraded"] is True and r["fault"] == "oom"


def test_check_safe_plain_runtime_error_is_not_degraded():
    r = check_safe(_Raises(RuntimeError("bug")), {}, [])
    assert r["valid?"] is UNKNOWN
    assert "degraded" not in r and "fault" not in r


class _Returns(Checker):
    def __init__(self, result):
        self.result = result

    def check(self, test, hist, opts):
        return dict(self.result)


def test_compose_surfaces_recovery_vs_degradation():
    r = Compose({
        "fine": _Returns({"valid?": True}),
        "healed": _Returns({"valid?": True,
                            "recovered": {"faults": ["oom"],
                                          "retries": 1}}),
        "lost": _Returns({"valid?": UNKNOWN, "degraded": True}),
    }).check({}, [], {})
    assert r["recovered-checkers"] == ["healed"]
    assert r["degraded-checkers"] == ["lost"]


def test_linearizable_threads_retry_budget_from_test_map():
    plat.fault_hook = _always("device-lost", "offline")
    c = linear.Linearizable(MODEL)
    r = c.check({"max-recovery-retries": 0}, _hist(n=100), {})
    assert r["valid?"] is True
    assert r["recovered"]["fallback"] == "host"


# -- OnlineChecker driver crash ---------------------------------------------

def test_online_driver_crash_degrades_streamed_results():
    class _Target:
        violation = False

        def feed(self, op):
            pass

        def finish(self):
            return {"valid?": True}

    oc = streaming.OnlineChecker({"lin": _Target()})
    oc.offer("not-an-op")   # AttributeError inside the driver thread
    out = oc.finalize(timeout_s=30.0)
    assert out["degraded"] is True
    assert "AttributeError" in out["error"]
    assert "lin" not in out   # crashed drivers report no verdicts


def test_online_target_crash_is_contained_per_target():
    class _Bad:
        violation = False

        def feed(self, op):
            raise ValueError("encoder bug")

        def finish(self):   # pragma: no cover — dead targets skip it
            return {"valid?": True}

    class _Good:
        violation = False

        def __init__(self):
            self.n = 0

        def feed(self, op):
            self.n += 1

        def finish(self):
            return {"valid?": True, "fed": self.n}

    oc = streaming.OnlineChecker({"bad": _Bad(), "good": _Good()})
    oc.offer({"type": "invoke", "process": 0})
    out = oc.finalize(timeout_s=30.0)
    assert "degraded" not in out      # the driver itself survived
    assert "bad" not in out
    assert out["good"]["fed"] == 1


# -- surfacing: report / web / core -----------------------------------------

def test_report_recovery_line():
    from jepsen_tpu import report
    assert report.recovery_line({}) == ""
    line = report.recovery_line(
        {"recovered": {"faults": ["oom", "wedged"], "retries": 2,
                       "resumed-from-chunk": 4}})
    assert "oom, wedged" in line
    assert "2 retries" in line and "chunk 4" in line


def test_web_recovery_note():
    from jepsen_tpu import web
    assert web.recovery_note({}) == ""
    assert web.recovery_note(
        {"lin": {"valid?": True,
                 "recovered": {"faults": ["oom"]}}}) == " (recovered)"
    # degradation outranks recovery: a missing verdict is the headline
    assert web.recovery_note(
        {"lin": {"recovered": {"faults": ["oom"]}},
         "other": {"degraded": True}}) == " (degraded)"


def test_log_results_distinguishes_recovery_from_degradation(caplog):
    import logging

    from jepsen_tpu import core
    with caplog.at_level(logging.INFO, logger="jepsen_tpu.core"):
        core.log_results({"results": {
            "valid?": True, "recovered-checkers": ["lin"],
            "lin": {"valid?": True,
                    "recovered": {"faults": ["oom"], "retries": 1}}}})
    assert any("recovered from backend faults" in m
               for m in caplog.messages)
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="jepsen_tpu.core"):
        core.log_results({"results": {
            "valid?": UNKNOWN, "degraded-checkers": ["lin"]}})
    assert any("DEGRADED" in m for m in caplog.messages)


def test_cli_exposes_max_recovery_retries():
    from jepsen_tpu import cli
    spec = cli.test_opt_spec()
    assert any(s["long"] == "--max-recovery-retries" for s in spec)
