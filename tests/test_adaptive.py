"""Adaptive overload control (ISSUE 12, doc/robustness.md `Adaptive
overload control`): the measured cost model (jepsen_tpu/calibrate.py),
the self-tuning AIMD ChunkBudget with suspicion-priority scheduling,
the per-stream degradation ladder, and the chaos/soak acceptance test
— sustained overload + injected faults, the service stays live, no
definite violation is missed at any ladder tier, and tier-full
verdicts stay byte-identical to solo runs.
"""

from __future__ import annotations

import json
import threading
import time

import pytest

from jepsen_tpu import calibrate, models, service, store, telemetry
from jepsen_tpu.checker import screen, synth, wgl

MODEL = models.cas_register()
CHUNK = 64
SLOTS = 8
FRONTIER = 128
CKPT = 2

TIMING = ("tail-latency-ms", "duration-ms", "violation-at-op")


@pytest.fixture(autouse=True)
def _reset_fault_injection():
    from jepsen_tpu import _platform
    _platform.reset_fault_injection()
    yield
    _platform.reset_fault_injection()


def _canon(x):
    return json.loads(json.dumps(x, default=store._json_default,
                                 sort_keys=True))


def _strip(d, extra=()):
    return _canon({k: v for k, v in d.items()
                   if k not in TIMING + tuple(extra)})


def _jops(h):
    return [json.loads(json.dumps(op, default=store._json_default))
            for op in h.ops]


def _wgl_spec(**over):
    sp = {"kind": "wgl", "model": service.model_spec(MODEL),
          "chunk-entries": CHUNK, "slots": SLOTS, "engine": "sort",
          "frontier": FRONTIER, "checkpoint-every": CKPT}
    sp.update(over)
    return sp


def _screen_spec():
    return {"kind": "screen", "model": service.model_spec(MODEL)}


def _solo(ops, **kw):
    from jepsen_tpu.checker import streaming
    params = dict(chunk_entries=CHUNK, slots=SLOTS, frontier=FRONTIER,
                  checkpoint_every=CKPT)
    params.update(kw)
    s = streaming.WglStream(MODEL, **params)
    for op in ops:
        s.feed(op)
    return s.finish()


def _counter(name: str) -> float:
    """Total over all label sets of one registry counter (the metrics
    are process-global and cumulative: tests compare deltas)."""
    snap = telemetry.snapshot(compact=True).get(name) or {}
    return sum(v for v in snap.values() if isinstance(v, (int, float)))


def _quiet_service(**kw):
    """A service whose ladder thread never ticks on its own — the
    controller tests drive _ladder_step with synthetic clocks."""
    kw.setdefault("ladder_tick_s", 3600.0)
    return service.VerificationService(**kw)


# ---------------------------------------------------------------------------
# ChunkBudget: AIMD capacity, wakeups, priority, aging
# ---------------------------------------------------------------------------

def test_budget_acquire_release_roundtrip():
    b = service.ChunkBudget(1.0)
    assert b.acquire(0.4, timeout_s=1.0)
    st = b.status()
    assert st["unit"] == "device-seconds"
    assert st["available"] == pytest.approx(0.6)
    b.release(0.4, clean=True, seconds=0.01)
    assert b.status()["available"] == pytest.approx(1.0)


def test_budget_over_capacity_cost_clamps():
    # a single over-budget chunk must always eventually dispatch
    b = service.ChunkBudget(1.0)
    assert b.acquire(50.0, timeout_s=1.0)
    assert b.status()["available"] == pytest.approx(0.0)
    b.release(50.0)
    assert b.status()["available"] == pytest.approx(1.0)


def test_budget_restore_wakes_blocked_waiter():
    """Satellite regression: an acquirer blocked against pre-halve
    capacity must be woken by release()'s notify_all when capacity
    restores — not left to its 100ms poll against a stale snapshot
    (starvation of a cheap stream behind a restored budget)."""
    b = service.ChunkBudget(1.0, hysteresis_s=0.0)
    assert b.acquire(1.0, timeout_s=1.0)      # drain the budget
    got = []

    def waiter():
        got.append(b.acquire(0.5, timeout_s=5.0))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.1)
    assert not got                            # genuinely blocked
    b.note_oom()                              # capacity halves to 0.5
    t0 = time.monotonic()
    b.release(1.0, clean=True, seconds=0.001)
    t.join(timeout=3.0)
    assert got == [True]
    # woken by the notify, not by a poll-timeout march
    assert time.monotonic() - t0 < 2.0


def test_budget_oom_halves_to_floor():
    b = service.ChunkBudget(1.0)
    for _ in range(20):
        b.note_oom()
    st = b.status()
    assert st["capacity"] == pytest.approx(st["floor"])
    assert st["capacity"] == pytest.approx(
        1.0 * service.BUDGET_FLOOR_FRACTION)
    assert st["ooms"] == 20


def test_budget_latency_blowout_cut_once_per_hysteresis():
    b = service.ChunkBudget(1.0, blowout_s=0.05, hysteresis_s=60.0)
    before = _counter("jepsen_tpu_service_budget_cuts_total")
    for _ in range(12):                       # p95 >> blowout
        b.acquire(0.01, timeout_s=1.0)
        b.release(0.01, clean=True, seconds=1.0)
    st = b.status()
    assert st["cuts"] == 1                    # hysteresis: one cut
    assert st["capacity"] == pytest.approx(0.5)
    assert _counter("jepsen_tpu_service_budget_cuts_total") \
        == before + 1


def test_budget_static_mode_never_latency_cuts():
    b = service.ChunkBudget(1.0, adaptive=False, blowout_s=0.05)
    for _ in range(12):
        b.acquire(0.01, timeout_s=1.0)
        b.release(0.01, clean=True, seconds=1.0)
    st = b.status()
    assert st["cuts"] == 0
    assert st["capacity"] == pytest.approx(1.0)


def test_budget_additive_restore_after_hysteresis():
    b = service.ChunkBudget(1.0, hysteresis_s=0.05, blowout_s=10.0)
    b.note_oom()                              # cut to 0.5
    # inside the hysteresis window: clean chunks do NOT restore
    b.acquire(0.01, timeout_s=1.0)
    b.release(0.01, clean=True, seconds=0.001)
    assert b.status()["capacity"] == pytest.approx(0.5)
    time.sleep(0.08)                          # hysteresis passed
    b.acquire(0.01, timeout_s=1.0)
    b.release(0.01, clean=True, seconds=0.001)
    st = b.status()
    assert st["capacity"] == pytest.approx(
        0.5 + service.BUDGET_RESTORE_STEP)
    # restore is additive and capped at max
    for _ in range(200):
        b.acquire(0.01, timeout_s=1.0)
        b.release(0.01, clean=True, seconds=0.001)
    assert b.status()["capacity"] == pytest.approx(1.0)


def test_budget_restored_capacity_is_spendable():
    """Regression: restore must grow the SPENDABLE pool, not just the
    reported capacity — a stored available-pool clamped at the cut
    conserved the halved budget forever while status() showed max."""
    b = service.ChunkBudget(1.0, hysteresis_s=0.0, blowout_s=10.0)
    b.note_oom()                              # cut to 0.5
    for _ in range(200):                      # additive restore to max
        b.acquire(0.01, timeout_s=1.0)
        b.release(0.01, clean=True, seconds=0.001)
    assert b.status()["capacity"] == pytest.approx(1.0)
    # the restored seconds are actually acquirable in one piece
    assert b.acquire(1.0, timeout_s=1.0)
    b.release(1.0)
    assert b.status()["available"] == pytest.approx(1.0)


def test_budget_mid_latency_restores_at_half_step():
    """Clean chunks between the low-latency bar and half of blowout
    restore at half step — a fleet whose healthy latency sits there
    must not stay halved forever after one OOM."""
    b = service.ChunkBudget(1.0, hysteresis_s=0.0, blowout_s=10.0)
    b.note_oom()
    b.acquire(0.01, timeout_s=1.0)
    b.release(0.01, clean=True, seconds=4.0)  # 0.4x blowout: mid band
    assert b.status()["capacity"] == pytest.approx(
        0.5 + 0.5 * service.BUDGET_RESTORE_STEP)


def test_budget_aged_clean_waiter_blocks_young_suspects():
    """Regression: an aged priority-0 waiter reserves capacity against
    freshly-arriving priority-1 acquirers too — a steady suspect load
    must not starve a clean stream indefinitely."""
    b = service.ChunkBudget(1.0, aging_s=0.2)
    assert b.acquire(0.9, timeout_s=1.0)      # most capacity held
    got_clean = []

    def clean():
        got_clean.append(b.acquire(0.8, timeout_s=10.0, priority=0))

    t = threading.Thread(target=clean)
    t.start()
    time.sleep(0.4)                           # clean waiter aged
    # free room for the suspect but not for the aged clean waiter:
    # the young suspect fits, yet may NOT bypass the reservation
    b.release(0.1, seconds=0.001)
    assert not b.acquire(0.1, timeout_s=0.3, priority=1)
    b.release(0.8, seconds=0.001)             # now the clean one fits
    t.join(timeout=5.0)
    assert got_clean == [True]
    b.release(0.8)


def test_overloaded_ignores_supply_side_signals_without_demand():
    """A lone transient OOM (recent cut, halved capacity) with nobody
    waiting is NOT overload — climbing a clean stream off it would
    turn a deterministic verdict into a deferred one."""
    svc = _quiet_service()
    try:
        calm_after_cut = {"waiters": 0, "capacity": 0.5,
                          "initial": 1.0, "available": 0.5,
                          "p95_latency_s": 0.01,
                          "queue_depth_ewma": 0.0, "recent_cut": True}
        assert not svc.overloaded(calm_after_cut)
        assert svc.overloaded({**calm_after_cut, "waiters": 1})
    finally:
        svc.stop()


def test_status_transitions_counter_survives_worker_reaping():
    """status()['ladder']['transitions'] reads the service-lifetime
    counter, not a sum over (reapable) workers — it must never go
    backwards on a long-lived daemon."""
    svc = _quiet_service()
    try:
        w = svc.admit("s", {"linear": _wgl_spec()})
        assert w.set_tier(service.TIER_SAMPLED, "test")
        assert w.set_tier(service.TIER_FULL, "test")
        assert svc.status()["ladder"]["transitions"] == 2
        with svc._lock:
            svc.workers.clear()               # simulate reaping
        assert svc.status()["ladder"]["transitions"] == 2
    finally:
        svc.stop()


def test_budget_slow_chunks_do_not_restore():
    b = service.ChunkBudget(1.0, hysteresis_s=0.0, blowout_s=10.0)
    b.note_oom()
    b.acquire(0.01, timeout_s=1.0)
    # clean but NOT low-latency: above restore bar (0.25 * blowout)
    b.release(0.01, clean=True, seconds=9.0)
    assert b.status()["capacity"] == pytest.approx(0.5)


def test_budget_hungry_queue_doubles_restore():
    b = service.ChunkBudget(1.0, hysteresis_s=0.0, blowout_s=10.0)
    b.note_oom()
    for _ in range(40):                       # drive the EWMA deep
        b.note_queue_depth(service.BUDGET_HUNGRY_ROWS * 4)
    b.acquire(0.01, timeout_s=1.0)
    b.release(0.01, clean=True, seconds=0.001)
    assert b.status()["capacity"] == pytest.approx(
        0.5 + 2 * service.BUDGET_RESTORE_STEP)


def test_budget_priority_grants_ahead_of_fifo():
    """Suspect streams (priority 1) acquire ahead of clean (priority
    0) waiters that arrived FIRST."""
    b = service.ChunkBudget(1.0)
    assert b.acquire(1.0, timeout_s=1.0)      # saturate
    order = []

    def waiter(tag, prio):
        assert b.acquire(1.0, timeout_s=10.0, priority=prio)
        order.append(tag)
        b.release(1.0, seconds=0.001)

    t_clean = threading.Thread(target=waiter, args=("clean", 0))
    t_clean.start()
    time.sleep(0.15)                          # clean is queued first
    t_susp = threading.Thread(target=waiter, args=("suspect", 1))
    t_susp.start()
    time.sleep(0.15)
    b.release(1.0, seconds=0.001)
    t_susp.join(timeout=5.0)
    t_clean.join(timeout=5.0)
    assert order == ["suspect", "clean"]


def test_budget_aged_waiter_reserves_capacity():
    """Work-conserving bypass flips to reservation once a waiter ages:
    cheap chunks bypass a too-big waiter at first, then capacity is
    reserved so the big waiter cannot starve."""
    b = service.ChunkBudget(1.0, aging_s=0.3)
    assert b.acquire(0.6, timeout_s=1.0)      # avail 0.4
    got_big = []

    def big():
        got_big.append(b.acquire(1.0, timeout_s=10.0))

    t = threading.Thread(target=big)
    t.start()
    time.sleep(0.1)
    # young big waiter: a cheap chunk may still bypass it
    assert b.acquire(0.2, timeout_s=0.5)
    b.release(0.2, seconds=0.001)
    time.sleep(0.4)                           # big waiter aged
    assert not b.acquire(0.2, timeout_s=0.4)  # reserved for the aged
    b.release(0.6, seconds=0.001)             # avail 1.0: big grants
    t.join(timeout=5.0)
    assert got_big == [True]
    b.release(1.0, seconds=0.001)
    assert b.acquire(0.2, timeout_s=1.0)      # and the cheap one too


# ---------------------------------------------------------------------------
# Calibration: the measured cost model
# ---------------------------------------------------------------------------

def test_calibration_converges_to_observed_ratio():
    cal = calibrate.Calibration(platform="cpu")
    for _ in range(50):
        cal.observe("sort", 1e6, 2e-3)        # 2e-9 s/elementop
    assert cal.coeff("sort") == pytest.approx(2e-9, rel=0.05)
    assert cal.count("sort") == 50
    assert cal.seconds("sort", 1e6) == pytest.approx(2e-3, rel=0.05)


def test_calibration_clips_outliers():
    cal = calibrate.Calibration(platform="cpu")
    for _ in range(30):
        cal.observe("sort", 1e6, 1e-3)        # 1e-9 s/elementop
    # one wedged 600s chunk: bounded influence, not a 600000x jump
    cal.observe("sort", 1e6, 600.0)
    assert cal.coeff("sort") < 1e-9 * (1 + calibrate.CLIP_FACTOR)


def test_calibration_ready_gate_and_fallback():
    cal = calibrate.Calibration(platform="cpu")
    for _ in range(calibrate.MIN_OBSERVATIONS - 1):
        cal.observe("dense", 1e6, 1e-3)
    assert not cal.ready("dense")
    cal.observe("dense", 1e6, 1e-3)
    assert cal.ready("dense")
    assert not cal.ready("dense", "sort")     # sort never measured
    # unmeasured variant: geometric-mean fallback, not the nominal
    assert cal.coeff("sort") == pytest.approx(cal.coeff("dense"),
                                              rel=0.01)
    # a cold calibration prices at the nominal constant
    cold = calibrate.Calibration(platform="cpu")
    assert cold.coeff("sort") is None
    assert cold.seconds("sort", 1e9) == pytest.approx(
        1e9 * calibrate.NOMINAL_SECONDS_PER_ELEMENTOP)


def test_calibration_persistence_roundtrip(tmp_path):
    cal = calibrate.Calibration(platform="cpu")
    for _ in range(20):
        cal.observe("sort", 1e6, 1e-3)
        cal.observe("dense", 1e6, 5e-4)
    path = str(tmp_path / "calibration-cpu.json")
    cal.save(path)
    back = calibrate.Calibration.load(path, platform="cpu")
    assert back.coeff("sort") == pytest.approx(cal.coeff("sort"))
    assert back.count("dense") == 20
    assert back.ready("sort", "dense")
    # corrupt file: cold start, never an exception
    with open(path, "w") as fh:
        fh.write("{not json")
    assert calibrate.Calibration.load(path, platform="cpu") \
        .coefficients() == {}
    # platform mismatch: a cpu file must not price a tpu backend
    cal.save(path)
    assert calibrate.Calibration.load(path, platform="tpu") \
        .coefficients() == {}


def test_calibration_missing_file_starts_cold(tmp_path):
    cal = calibrate.Calibration.load(str(tmp_path / "nope.json"),
                                     platform="cpu")
    assert cal.coefficients() == {}


def test_observe_helper_is_noop_without_activation():
    calibrate.deactivate()
    calibrate.observe("sort", 1e6, 1.0)       # must not raise
    assert calibrate.active() is None
    cal = calibrate.activate(calibrate.Calibration(platform="cpu"))
    try:
        calibrate.observe("sort", 1e6, 1e-3)
        assert cal.count("sort") == 1
    finally:
        calibrate.deactivate()


# ---------------------------------------------------------------------------
# select_engine in measured device-seconds
# ---------------------------------------------------------------------------

# a shape the MODELED cost prices dense, and one it prices sort
DENSE_SHAPE = dict(srange=(0, 3), p=4, n=1000)
SORT_SHAPE = dict(srange=(0, 511), p=6, n=200)


def _select(shape, cal=None):
    return wgl.select_engine(shape["srange"], shape["p"], shape["n"],
                             model="cas-register",
                             slots=shape["p"], frontier=128,
                             calibration=cal)


def _skewed(bad: str, good: str) -> calibrate.Calibration:
    cal = calibrate.Calibration(platform="cpu")
    for _ in range(calibrate.MIN_OBSERVATIONS + 4):
        cal.observe(bad, 1e6, 1e3)            # measured terrible
        cal.observe(good, 1e6, 1e-6)          # measured great
    return cal


def test_select_engine_uncalibrated_unchanged():
    assert _select(DENSE_SHAPE).family == "dense"
    assert _select(SORT_SHAPE).family == "sort"
    assert _select(DENSE_SHAPE).seconds is None


def test_select_engine_flips_dense_to_sort_on_measurement():
    """The acceptance pin: skewed synthetic latency observations flip
    the engine choice — measured coefficients, not the modeled
    constants, decide."""
    dec = _select(DENSE_SHAPE, _skewed("dense", "sort"))
    assert dec.family == "sort"
    assert "measured" in dec.reason
    assert dec.seconds is not None
    assert dec.seconds["dense"] > dec.seconds["sort"]


def test_select_engine_flips_sort_to_dense_on_measurement():
    dec = _select(SORT_SHAPE, _skewed("sort", "dense"))
    assert dec.family == "dense"
    assert "measured" in dec.reason


def test_select_engine_half_calibrated_never_flips():
    """One noisy variant must not flip a decision: both compared
    variants need MIN_OBSERVATIONS."""
    cal = calibrate.Calibration(platform="cpu")
    for _ in range(calibrate.MIN_OBSERVATIONS + 4):
        cal.observe("dense", 1e6, 1e3)        # only dense measured
    dec = _select(DENSE_SHAPE, cal)
    assert dec.family == "dense"              # modeled decision holds
    assert dec.seconds is None


def test_chunk_cost_prices_in_device_seconds():
    from jepsen_tpu.checker.streaming import WglStream
    s = WglStream(MODEL, chunk_entries=CHUNK, slots=SLOTS,
                  frontier=FRONTIER)
    price = service.chunk_cost(s)
    assert isinstance(price, service.ChunkPrice)
    assert price.variant in ("dense", "sort", "hash")
    assert price.cost == pytest.approx(
        price.elementops * calibrate.NOMINAL_SECONDS_PER_ELEMENTOP)
    # calibrated: the same chunk priced at the measured coefficient
    cal = calibrate.Calibration(platform="cpu")
    for _ in range(20):
        cal.observe(price.variant, 1e6, 1e-3)
    cal_price = service.chunk_cost(s, cal)
    assert cal_price.cost == pytest.approx(
        cal_price.elementops * 1e-9, rel=0.1)


# ---------------------------------------------------------------------------
# suspicion propagation: ScreenStream -> worker metadata -> status()
# ---------------------------------------------------------------------------

# value 99 is outside every synth history's 0..4 domain and process
# 900/901 never collide with a generated history's process ids, so
# these four ops turn ANY prefix into a definite phantom-read
PHANTOM_OPS = [
    {"type": "invoke", "f": "write", "value": 1, "process": 900},
    {"type": "ok", "f": "write", "value": 1, "process": 900},
    {"type": "invoke", "f": "read", "value": None, "process": 901},
    {"type": "ok", "f": "read", "value": 99, "process": 901},
]


def _wait(pred, timeout_s=10.0, interval=0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def test_suspicion_flows_from_screen_to_status_and_metrics():
    before = _counter("jepsen_tpu_service_stream_events_total")
    svc = _quiet_service()
    try:
        w = svc.admit("susp", {"screen-linear": _screen_spec()})
        for op in PHANTOM_OPS:
            svc.offer("susp", op)
        assert _wait(lambda: w.suspicion_score
                     >= screen.ESCALATE_THRESHOLD)
        st = svc.status()["streams"]["susp"]
        assert st["suspicion"] >= screen.ESCALATE_THRESHOLD
        assert st["priority"] == 1
        assert st["violation"] is True
        assert w.scheduling_priority() == 1
        # the lifecycle metric counted the prioritization exactly once
        snap = telemetry.snapshot(compact=True)
        events = snap["jepsen_tpu_service_stream_events_total"]
        assert events.get("event=prioritized", 0) >= 1
        assert _counter("jepsen_tpu_service_stream_events_total") \
            > before
        svc.seal("susp")
        r = svc.result("susp", timeout_s=60)
        assert r["screen-linear"]["valid?"] is False
    finally:
        svc.stop()


def test_soft_suspicion_does_not_prioritize():
    """Crashed-mutator soft signals (0.02 each, capped 0.5) ride
    nearly every realistic history — below the escalation bar they
    must not outrank siblings or pin a stream to tier-full."""
    svc = _quiet_service()
    try:
        w = svc.admit("soft", {"screen-linear": _screen_spec()})
        ops = [
            {"type": "invoke", "f": "write", "value": 1, "process": 0},
            {"type": "info", "f": "write", "value": 1, "process": 0},
            {"type": "invoke", "f": "read", "value": None,
             "process": 1},
            {"type": "ok", "f": "read", "value": 1, "process": 1},
        ]
        for op in ops:
            svc.offer("soft", op)
        assert _wait(lambda: w.ops_fed == len(ops))
        w.refresh_suspicion()
        st = svc.status()["streams"]["soft"]
        assert 0 < st["suspicion"] < screen.ESCALATE_THRESHOLD
        assert st["priority"] == 0
        assert w.scheduling_priority() == 0
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# the degradation ladder
# ---------------------------------------------------------------------------

def test_ladder_climb_and_descend_with_hysteresis():
    """Controller unit test on a synthetic clock: sustained overload
    climbs ONE stream per hold (most expensive first), sustained calm
    descends one per (longer) hold, transitions land in telemetry."""
    before = _counter("jepsen_tpu_service_ladder_transitions_total")
    svc = _quiet_service(ladder_climb_hold_s=1.0,
                         ladder_descend_hold_s=3.0)
    try:
        cheap = svc.admit("cheap", {"linear": _wgl_spec()})
        exp = svc.admit("exp", {"linear": _wgl_spec(
            **{"chunk-entries": 256, "slots": 10})})
        assert exp.device_cost() > cheap.device_cost()

        overloaded = {"waiters": 3, "capacity": 0.1, "initial": 1.0,
                      "available": 0.0, "p95_latency_s": 0.5,
                      "queue_depth_ewma": 0.0, "recent_cut": True}
        calm = {"waiters": 0, "capacity": 1.0, "initial": 1.0,
                "available": 1.0, "p95_latency_s": 0.01,
                "queue_depth_ewma": 0.0, "recent_cut": False}
        assert svc.overloaded(overloaded)
        assert not svc.overloaded(calm)

        svc.budget.signals = lambda: overloaded
        svc._ladder_step(100.0)               # overload onset
        assert exp.current_tier() == service.TIER_FULL
        svc._ladder_step(101.5)               # hold passed: one climb
        assert exp.current_tier() == service.TIER_SAMPLED
        assert cheap.current_tier() == service.TIER_FULL  # ONE climb
        svc._ladder_step(103.0)               # lowest tier first:
        assert cheap.current_tier() == service.TIER_SAMPLED
        svc._ladder_step(104.5)               # then the expensive one
        assert exp.current_tier() == service.TIER_SCREEN

        svc.budget.signals = lambda: calm
        svc._ladder_step(105.0)               # calm onset
        svc._ladder_step(106.5)               # climb hold is NOT
        assert exp.current_tier() == service.TIER_SCREEN  # enough
        svc._ladder_step(108.5)               # descend hold passed:
        assert exp.current_tier() == service.TIER_SAMPLED  # worst 1st
        svc._ladder_step(112.0)               # tie: cheapest first
        assert cheap.current_tier() == service.TIER_FULL
        svc._ladder_step(115.5)
        assert exp.current_tier() == service.TIER_FULL

        assert _counter(
            "jepsen_tpu_service_ladder_transitions_total") \
            == before + 6
        st = svc.status()
        assert st["ladder"]["transitions"] == 6
    finally:
        svc.stop()


def test_ladder_never_climbs_suspect_streams():
    svc = _quiet_service(ladder_climb_hold_s=1.0)
    try:
        suspect = svc.admit("sus", {"linear": _wgl_spec(
            **{"chunk-entries": 256, "slots": 10}),
            "screen-linear": _screen_spec()})
        clean = svc.admit("cln", {"linear": _wgl_spec()})
        for op in PHANTOM_OPS:
            svc.offer("sus", op)
        assert _wait(lambda: suspect.scheduling_priority() == 1)
        svc.budget.signals = lambda: {
            "waiters": 3, "capacity": 0.1, "initial": 1.0,
            "available": 0.0, "p95_latency_s": 0.5,
            "queue_depth_ewma": 0.0, "recent_cut": True}
        svc._ladder_step(100.0)
        svc._ladder_step(101.5)
        # the suspect stream is the expensive one, but it keeps device
        # time; the clean one climbs instead
        assert suspect.current_tier() == service.TIER_FULL
        assert clean.current_tier() == service.TIER_SAMPLED
    finally:
        svc.stop()


def test_ladder_climb_to_shed_is_terminal():
    svc = _quiet_service()
    try:
        w = svc.admit("doomed", {"linear": _wgl_spec()})
        for t in range(service.TIER_FULL + 1, service.TIER_SHED + 1):
            w.set_tier(t, "test")
        assert w.done.wait(10.0)
        assert w.state == service.SHED
        assert "degradation ladder" in w.shed_reason
    finally:
        svc.stop()


def test_screen_only_tier_defers_device_verdict():
    """At screen-only, a clean stream's device verdict defers to
    offline (no 'valid?' key — the checkers' streamed-results reuse
    guard skips it) while its screen verdict is complete; the result
    carries the ladder stamp."""
    ops, _ = _jops(synth.register_history(
        200, concurrency=3, values=5, seed=77)), None
    svc = _quiet_service()
    try:
        w = svc.admit("deg", {"linear": _wgl_spec(),
                              "screen-linear": _screen_spec()})
        w.set_tier(service.TIER_SAMPLED, "test")
        w.set_tier(service.TIER_SCREEN, "test")
        for op in ops:
            svc.offer("deg", op)
        svc.seal("deg")
        r = svc.result("deg", timeout_s=120)
        assert r["linear"]["deferred"] is True
        assert r["linear"]["ladder-tier"] == "screen-only"
        assert "valid?" not in r["linear"]
        assert r["screen-linear"]["valid?"] is True   # screens ran
        assert r["ladder"]["max-tier"] == "screen-only"
        assert r["ladder"]["transitions"] == 2
        # pending chunks were never pumped under the gate
        st = svc.status()["streams"]["deg"]
        assert st["ladder-tier"] == "screen-only"
    finally:
        svc.stop()


def test_screen_only_finish_keeps_already_pumped_verdict():
    """A stream that finished its device work BEFORE the climb keeps
    its verdict: deferral is for unpumped chunks, not for device
    seconds already spent."""
    ops = _jops(synth.register_history(200, concurrency=3, values=5,
                                       seed=76))
    solo = _solo(ops)
    svc = _quiet_service()
    try:
        w = svc.admit("paid", {"linear": _wgl_spec()})
        for op in ops:
            svc.offer("paid", op)
        t = w.targets["linear"]
        assert _wait(lambda: w.ops_fed == len(ops)
                     and t.pending_chunks() == 0)
        w.set_tier(service.TIER_SAMPLED, "test")
        w.set_tier(service.TIER_SCREEN, "test")
        svc.seal("paid")
        r = svc.result("paid", timeout_s=120)
        assert r["linear"]["valid?"] is True      # verdict kept
        assert "deferred" not in r["linear"]
        assert r["ladder"]["max-tier"] == "screen-only"  # stamped
        assert _strip(r["linear"]) == _strip(solo)
    finally:
        svc.stop()


def test_violation_at_screen_only_tier_is_never_missed():
    """The no-missed-violation pin at the worst live tier: a stream
    forced to screen-only turns suspect the moment its screen sees a
    definite violation, descends to full, and its device verdict runs
    after all."""
    valid_ops = _jops(synth.register_history(
        120, concurrency=3, values=5, seed=78))
    svc = _quiet_service()
    try:
        w = svc.admit("v", {"linear": _wgl_spec(),
                            "screen-linear": _screen_spec()})
        w.set_tier(service.TIER_SAMPLED, "test")
        w.set_tier(service.TIER_SCREEN, "test")
        for op in valid_ops:
            svc.offer("v", op)
        for op in PHANTOM_OPS:                # definite violation
            svc.offer("v", op)
        svc.seal("v")
        r = svc.result("v", timeout_s=120)
        # suspicion descended the stream: full device verdict, invalid
        assert r["screen-linear"]["valid?"] is False
        assert r["linear"]["valid?"] is False
        assert "deferred" not in r["linear"]
        assert w.current_tier() == service.TIER_FULL
        assert svc.status()["streams"]["v"]["violation"] is True
    finally:
        svc.stop()


def test_tier_full_verdicts_unstamped_and_byte_identical():
    """Streams that never leave tier-full carry NO ladder stamp —
    byte-identical to solo runs."""
    ops = _jops(synth.register_history(200, concurrency=3, values=5,
                                       seed=79))
    solo = _solo(ops)
    svc = _quiet_service()
    try:
        svc.admit("full", {"linear": _wgl_spec()})
        for op in ops:
            svc.offer("full", op)
        svc.seal("full")
        r = svc.result("full", timeout_s=120)
        assert "ladder" not in r
        assert _strip(r["linear"]) == _strip(solo)
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# the chaos/soak acceptance test
# ---------------------------------------------------------------------------

def test_chaos_overload_faults_service_stays_live(monkeypatch):
    """ISSUE 12 acceptance: sustained overload (budget far below the
    offered load) with injected oom + wedged faults. The service keeps
    answering /healthz-shaped status() and socket verbs, the ladder
    climbs (transitions visible in telemetry), no definite violation
    is missed, and clean streams that stayed at tier-full deliver
    verdicts byte-identical to solo runs."""
    n = 240
    cheap_shape = {}
    exp_shape = {"chunk-entries": 256, "slots": 10}
    hists = {
        "c0": _jops(synth.register_history(n, concurrency=3,
                                           values=5, seed=801)),
        "c1": _jops(synth.register_history(n, concurrency=3,
                                           values=5, seed=802)),
        "e0": _jops(synth.register_history(n, concurrency=3,
                                           values=5, seed=803)),
        "f0": _jops(synth.register_history(n, concurrency=3,
                                           values=5, seed=804)),
        "f1": _jops(synth.register_history(n, concurrency=3,
                                           values=5, seed=805)),
    }
    shapes = {"c0": cheap_shape, "c1": cheap_shape, "e0": exp_shape,
              "f0": cheap_shape, "f1": cheap_shape}
    solos = {name: _solo(ops, **{k.replace("-", "_"): v
                                 for k, v in shapes[name].items()})
             for name, ops in hists.items()}
    # the violation leads the stream: v0 turns suspect on op 4, so
    # suspicion-priority protects it from climbing for the whole storm
    # — the deterministic tier-full stream the byte-identity pin rides
    viol = PHANTOM_OPS + _jops(synth.register_history(
        80, concurrency=3, values=5, seed=806))
    viol_solo = _solo(viol)

    before_climb = _counter(
        "jepsen_tpu_service_ladder_transitions_total")
    monkeypatch.setenv(
        "JEPSEN_TPU_FAULT_INJECT",
        "oom@stream-chunk/f0:2,wedged@stream-chunk/f1:2")
    svc = service.VerificationService(
        budget_elementops=1e5,     # ~every chunk over budget: overload
        adaptive=True,
        ladder_tick_s=0.05,
        ladder_climb_hold_s=0.25,
        ladder_descend_hold_s=0.75)
    bound = svc.serve("127.0.0.1:0")
    try:
        for name in hists:
            svc.admit(name, {"linear": _wgl_spec(**shapes[name]),
                             "screen-linear": _screen_spec()})
        svc.admit("v0", {"linear": _wgl_spec(),
                         "screen-linear": _screen_spec()})

        # liveness probes: the /healthz shape in-process AND the
        # status verb over the real socket, hammered through the storm
        stop = threading.Event()
        probe_lat: list = []
        probe_err: list = []

        def probe():
            try:
                sock = service._connect(bound)
                rf = sock.makefile("r", encoding="utf-8")
                while not stop.is_set():
                    t0 = time.monotonic()
                    sock.sendall(b'{"type": "status", "id": 1}\n')
                    line = rf.readline()
                    st = json.loads(line)["status"]
                    svc.status()              # the /healthz payload
                    probe_lat.append(time.monotonic() - t0)
                    assert st["state"] == "serving"
                    stop.wait(0.05)
                sock.close()
            except Exception as e:  # noqa: BLE001 — surfaced below
                probe_err.append(repr(e))

        prober = threading.Thread(target=probe, daemon=True)
        prober.start()

        results: dict = {}

        def feed(name, ops):
            for op in ops:
                svc.offer(name, op)
            svc.seal(name)
            results[name] = svc.result(name, timeout_s=600)

        feeds = [threading.Thread(target=feed, args=(nm, ops))
                 for nm, ops in list(hists.items()) + [("v0", viol)]]
        for t in feeds:
            t.start()
        for t in feeds:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in feeds), "verb starvation"
        stop.set()
        prober.join(timeout=10)

        # -- liveness: every probe answered, promptly, no errors
        assert not probe_err, probe_err
        assert probe_lat and max(probe_lat) < 5.0
        # -- the ladder climbed under sustained overload, visibly
        assert _counter(
            "jepsen_tpu_service_ladder_transitions_total") \
            > before_climb
        st = svc.status()
        assert st["ladder"]["transitions"] > 0

        # -- no definite violation missed: the suspect stream is
        # priority-protected (never climbed), stays at tier-full, and
        # ends with a full invalid verdict
        assert st["streams"]["v0"]["ladder-max-tier"] == "full"
        assert results["v0"]["screen-linear"]["valid?"] is False
        assert results["v0"]["linear"]["valid?"] is False
        assert "ladder" not in results["v0"]  # tier-full: unstamped
        assert _strip(results["v0"]["linear"]) == _strip(viol_solo)

        # -- every stream delivered SOMETHING sound: a verdict (valid,
        # byte-identical if it stayed at tier-full), a ladder-stamped
        # deferral, or a shed (offline analyze covers it from the
        # journal) — never a wrong verdict, never a hang
        for nm in hists:
            sst = st["streams"][nm]
            r = results[nm]
            if sst["state"] == service.SHED:
                continue   # shed-to-offline: the pre-existing rung
            lin = r["linear"]
            if lin.get("deferred"):
                assert lin["ladder-tier"]      # stamped deferral
                assert "valid?" not in lin
                continue
            assert lin["valid?"] is True, (nm, lin)
            if sst["ladder-max-tier"] == "full" \
                    and nm not in ("f0", "f1"):  # faulted: recovery
                assert "ladder" not in r         # trail rides result
                assert _strip(lin) == _strip(solos[nm]), nm
            elif sst["ladder-max-tier"] != "full":
                assert "ladder" in r, nm         # degraded: stamped

        # -- calibration observed real chunks through the storm
        coeffs = st["calibration"]["coefficients"]
        assert coeffs.get("sort", {}).get("observations", 0) > 0
    finally:
        stop.set()
        svc.stop()


def test_drain_persists_calibration(tmp_path):
    svc = _quiet_service()
    path = str(tmp_path / "calibration-cpu.json")
    svc.calibration_path = path
    for _ in range(20):
        svc.calibration.observe("sort", 1e6, 1e-3)
    svc.drain(timeout_s=10)
    svc.stop()
    back = calibrate.Calibration.load(path, platform=None)
    assert back.count("sort") == 20


def test_service_status_cli_renders(capsys):
    from jepsen_tpu import cli
    svc = _quiet_service()
    try:
        bound = svc.serve("127.0.0.1:0")
        svc.admit("s0", {"screen-linear": _screen_spec()})
        assert cli._service_status(bound) == 0
        out = capsys.readouterr().out
        assert "service serving" in out
        assert "s0" in out
        assert "tier=full" in out
        assert "budget:" in out
        assert "calibration" in out
    finally:
        svc.stop()


def test_report_lines_surface_ladder():
    from jepsen_tpu import report
    line = report.service_line({
        "state": "serving",
        "streams": {"a": {"state": "streaming",
                          "ladder-tier": "screen-only"},
                    "b": {"state": "streaming",
                          "ladder-tier": "full"}},
        "budget": {"initial": 1.0, "capacity": 0.25, "ooms": 1,
                   "cuts": 3},
        "ladder": {"transitions": 5}})
    assert "1 ladder-degraded" in line
    assert "3 AIMD cuts" in line
    assert "5 ladder transitions" in line
    # older status dicts (pre-ladder) still render
    legacy = report.service_line({
        "state": "serving",
        "streams": {"a": {"state": "verdict"}},
        "budget": {"initial": 1e9, "capacity": 5e8, "ooms": 1}})
    assert "1 OOM backpressure events" in legacy
    assert "ladder" not in legacy

    tline = report.telemetry_line({
        "linear": {"deferred": True, "ladder-tier": "screen-only",
                   "history-len": 10},
        "ladder": {"tier": "screen-only", "max-tier": "screen-only",
                   "transitions": 2}})
    assert "ladder tier screen-only" in tline
    assert "1 device verdict deferred" in tline
    # older results without the fields stay silent
    assert report.telemetry_line({"valid?": True}) == ""
