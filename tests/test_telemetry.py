"""The observability substrate (jepsen_tpu/telemetry.py + the trace
exporter rework): registry semantics, Prometheus exposition, the
/metrics + /healthz HTTP endpoints against a live verification
service, chunk-level span threading (one trace id run -> stream ->
chunk), the async trace flusher, the checker's phase sections and
sync timer, and the CLI-started profiler."""

from __future__ import annotations

import json
import re
import socket as _socket
import threading
import time
import urllib.request

import pytest

from jepsen_tpu import telemetry, trace

CHUNK = 64
SLOTS = 8
FRONTIER = 128


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Zero every metric's accumulated values between tests (metric
    declarations are module-level and survive)."""
    telemetry.reset()
    telemetry.set_enabled(True)
    yield
    telemetry.reset()
    telemetry.set_enabled(True)


@pytest.fixture(autouse=True)
def _reset_fault_injection():
    from jepsen_tpu import _platform
    _platform.reset_fault_injection()
    yield
    _platform.reset_fault_injection()


# -- registry semantics ------------------------------------------------------

def test_counter_labels_and_idempotent_registration():
    c = telemetry.counter("jepsen_tpu_run_lint_test_total", "t",
                          ("kind",))
    c2 = telemetry.counter("jepsen_tpu_run_lint_test_total", "t",
                           ("kind",))
    assert c is c2          # get-or-create, one family per name
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc(5)
    snap = telemetry.snapshot()["jepsen_tpu_run_lint_test_total"]
    assert snap == {"kind=a": 3.0, "kind=b": 5.0}
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)
    with pytest.raises(ValueError):   # type change is a bug
        telemetry.gauge("jepsen_tpu_run_lint_test_total", "t",
                        ("kind",))
    with pytest.raises(ValueError):   # label change is a bug
        telemetry.counter("jepsen_tpu_run_lint_test_total", "t",
                          ("other",))


def test_gauge_and_unlabeled_passthrough():
    g = telemetry.gauge("jepsen_tpu_run_lint_gauge_info", "t")
    g.set(4.5)
    g.inc()
    g.dec(2)
    assert telemetry.snapshot()[
        "jepsen_tpu_run_lint_gauge_info"][""] == 3.5


def test_histogram_buckets_sum_count():
    h = telemetry.histogram("jepsen_tpu_run_lint_hist_seconds", "t",
                            buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    snap = telemetry.snapshot()["jepsen_tpu_run_lint_hist_seconds"][""]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(56.05)
    assert snap["buckets"] == {"0.1": 1, "1.0": 2, "10.0": 1,
                               "+Inf": 1}


def test_histogram_time_context_manager():
    h = telemetry.histogram("jepsen_tpu_run_lint_timer_seconds", "t")
    with h.time():
        time.sleep(0.01)
    snap = telemetry.snapshot()["jepsen_tpu_run_lint_timer_seconds"][""]
    assert snap["count"] == 1
    assert snap["sum"] >= 0.01


def test_concurrent_increments_are_exact():
    c = telemetry.counter("jepsen_tpu_run_lint_race_total", "t")
    h = telemetry.histogram("jepsen_tpu_run_lint_race_seconds", "t")
    n, threads = 5000, 8

    def work():
        for _ in range(n):
            c.inc()
            h.observe(0.001)

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = telemetry.snapshot()
    assert snap["jepsen_tpu_run_lint_race_total"][""] == n * threads
    assert snap["jepsen_tpu_run_lint_race_seconds"][""]["count"] \
        == n * threads


def test_set_enabled_turns_mutations_into_noops():
    c = telemetry.counter("jepsen_tpu_run_lint_off_total", "t")
    prev = telemetry.set_enabled(False)
    try:
        c.inc(100)
    finally:
        telemetry.set_enabled(prev)
    c.inc(1)
    assert telemetry.snapshot()["jepsen_tpu_run_lint_off_total"][""] \
        == 1


# -- Prometheus exposition ---------------------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+$")


def _assert_prometheus_parseable(text: str) -> dict:
    """Every non-comment line must be `name{labels} value`; returns
    {name: [line, ...]} for content assertions."""
    by_name: dict = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ")), line
            continue
        assert _PROM_LINE.match(line), f"unparseable: {line!r}"
        by_name.setdefault(line.split("{")[0].split(" ")[0],
                           []).append(line)
    return by_name


def test_prometheus_text_format():
    c = telemetry.counter("jepsen_tpu_run_lint_fmt_total", "t",
                          ("kind",))
    c.labels(kind='we"ird\nvalue').inc()
    h = telemetry.histogram("jepsen_tpu_run_lint_fmt_seconds", "t",
                            buckets=(1.0, 5.0))
    h.observe(0.5)
    h.observe(2.0)
    h.observe(99.0)
    text = telemetry.prometheus_text()
    lines = _assert_prometheus_parseable(text)
    assert "# TYPE jepsen_tpu_run_lint_fmt_total counter" \
        in text.splitlines()
    assert "# TYPE jepsen_tpu_run_lint_fmt_seconds histogram" \
        in text.splitlines()
    # label escaping round-trips quotes/newlines
    [counter_line] = lines["jepsen_tpu_run_lint_fmt_total"]
    assert '\\"' in counter_line and "\\n" in counter_line
    # histogram buckets are cumulative and +Inf equals the count
    bkt = lines["jepsen_tpu_run_lint_fmt_seconds_bucket"]
    assert [ln.rsplit(" ", 1)[1] for ln in bkt] == ["1", "2", "3"]
    assert lines["jepsen_tpu_run_lint_fmt_seconds_count"][0] \
        .endswith(" 3")
    # HELP/TYPE appear for registered metrics even with no series yet
    telemetry.counter("jepsen_tpu_run_lint_empty_total", "t",
                      ("kind",))
    assert "# TYPE jepsen_tpu_run_lint_empty_total counter" \
        in telemetry.prometheus_text()


def test_metric_name_lint_is_clean():
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    try:
        # the metrics analyzer migrated into tools/staticcheck; the
        # live registry must lint clean against the naming convention
        from tools.staticcheck.metrics import lint_registry
        problems, n = lint_registry(str(root))
        assert problems == [] and n > 0
    finally:
        sys.path.remove(str(root))


# -- the instrumented pipeline ----------------------------------------------

def _hist(seed, n=300, corrupt=False):
    from jepsen_tpu.checker import synth
    h = synth.register_history(n, concurrency=3, values=5, seed=seed)
    if corrupt:
        h = synth.corrupt(h, seed=seed + 1)
    return h


def test_offline_analysis_populates_wgl_metrics():
    from jepsen_tpu import models
    from jepsen_tpu.checker.wgl import analysis_tpu

    a = analysis_tpu(models.cas_register(), _hist(7, n=400),
                     chunk_entries=64)
    assert a["valid?"] is True
    snap = telemetry.snapshot()
    assert sum(snap["jepsen_tpu_wgl_checked_ops_total"].values()) > 0
    assert sum(snap["jepsen_tpu_wgl_engine_decisions_total"]
               .values()) >= 1
    chunk = snap["jepsen_tpu_wgl_chunk_seconds"]
    assert any(v["count"] > 0 for v in chunk.values())
    # attestation is default-on: staged-buffer digests verified
    assert sum(snap["jepsen_tpu_abft_verifications_total"]
               .values()) > 0


def test_recovery_rung_counter_counts_injected_faults(monkeypatch):
    from jepsen_tpu import models
    from jepsen_tpu.checker.wgl import analysis_tpu

    monkeypatch.setenv("JEPSEN_TPU_FAULT_INJECT", "oom@offline:1")
    a = analysis_tpu(models.cas_register(), _hist(11, n=200))
    assert a["valid?"] is True
    assert a["recovered"]["faults"] == ["oom"]
    snap = telemetry.snapshot()["jepsen_tpu_wgl_recovery_rungs_total"]
    assert snap.get("kind=oom,site=offline") == 1


def test_screen_metrics_and_escalation_reasons():
    from jepsen_tpu import models
    from jepsen_tpu.checker import screen

    sc = screen.screen_history(models.cas_register(), _hist(13))
    assert sc["valid?"] is True
    esc, why = screen.should_escalate({"screenable": False})
    assert esc and why == "unscreened-model"
    esc, why = screen.should_escalate({"suspicion": 2.0})
    assert esc and why == "suspicion"
    snap = telemetry.snapshot()
    assert sum(snap["jepsen_tpu_screen_screened_ops_total"]
               .values()) >= sc["op-count"]
    e = snap["jepsen_tpu_screen_escalations_total"]
    assert e.get("why=unscreened-model") == 1
    assert e.get("why=suspicion") == 1


# -- span threading: run -> stream -> chunk -> recovery-retry ---------------

def test_stream_spans_thread_one_trace_id(tmp_path, monkeypatch):
    from jepsen_tpu import models
    from jepsen_tpu.checker import streaming

    trace.tracing(str(tmp_path / "spans.jsonl"))
    try:
        monkeypatch.setenv("JEPSEN_TPU_FAULT_INJECT",
                           "oom@stream-chunk:2")
        h = _hist(17, n=400, corrupt=True)
        r = streaming.stream_check(
            models.cas_register(), h, chunk_entries=CHUNK,
            slots=SLOTS, frontier=FRONTIER, checkpoint_every=2)
        assert r["valid?"] is False
        assert r["recovered"]["faults"] == ["oom"]
        tid = r["trace-id"]
        assert tid
        tr = trace.tracer()
        chunks = tr.spans("wgl.stream.chunk")
        assert chunks and all(s["traceID"] == tid for s in chunks)
        retries = tr.spans("wgl.stream.recovery-retry")
        assert retries and all(s["traceID"] == tid for s in retries)
        [stream_span] = tr.spans("wgl.stream")
        assert stream_span["traceID"] == tid
        # chunks parent to the stream span — the run->stream->chunk
        # thread a Jaeger UI renders as one tree
        assert all(s["parentSpanID"] == stream_span["spanID"]
                   for s in chunks)
        # the violation tagged the stream span
        tags = {t["key"]: t["value"] for t in stream_span["tags"]}
        assert tags.get("violation") == "true"
    finally:
        trace.tracing(None)


def test_untraced_stream_has_no_trace_id():
    from jepsen_tpu import models
    from jepsen_tpu.checker import streaming

    r = streaming.stream_check(models.cas_register(), _hist(19),
                               chunk_entries=CHUNK, slots=SLOTS)
    assert r["valid?"] is True
    assert "trace-id" not in r


# -- the async trace flusher -------------------------------------------------

def _slow_collector():
    """A TCP listener that accepts but never answers — the shape of a
    wedged Jaeger collector (connects succeed, responses never come)."""
    srv = _socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    stop = threading.Event()
    conns = []

    def loop():
        srv.settimeout(0.2)
        while not stop.is_set():
            try:
                c, _ = srv.accept()
                conns.append(c)
            except OSError:
                continue

    t = threading.Thread(target=loop, daemon=True)
    t.start()

    def teardown():
        stop.set()
        t.join(2)
        for c in conns:
            c.close()
        srv.close()

    return srv.getsockname()[1], teardown


def test_slow_collector_does_not_stall_span_creation():
    port, teardown = _slow_collector()
    tr = trace.Tracer(f"http://127.0.0.1:{port}/api/traces")
    try:
        t0 = time.monotonic()
        for i in range(100):
            with tr.span(f"hot-{i}"):
                pass
        create_s = time.monotonic() - t0
        # the old exporter paid a synchronous POST (1 s timeout) per
        # span: 100 spans against this collector took >100 s; the
        # batched flusher makes creation pure enqueue
        assert create_s < 1.0, \
            f"span creation stalled {create_s:.2f}s on a slow collector"
        assert len(tr.spans()) == 100
        t0 = time.monotonic()
        tr.close()
        assert time.monotonic() - t0 < 5.0, "close() unbounded"
    finally:
        teardown()


def test_unreachable_collector_and_queue_bound():
    # nothing listens here: connects fail fast, spans still record
    tr = trace.Tracer("http://127.0.0.1:9/api/traces")
    try:
        for i in range(trace.EXPORT_QUEUE_LIMIT + 50):
            with tr.span("x"):
                pass
        with tr.lock:
            assert len(tr._q) <= trace.EXPORT_QUEUE_LIMIT
    finally:
        tr.close()


def test_file_exporter_still_synchronous(tmp_path):
    p = tmp_path / "t.jsonl"
    tr = trace.Tracer(str(p))
    with tr.span("a"):
        trace_id = tr.context()
    tr.close()
    [doc] = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert doc["operationName"] == "a"
    del trace_id


# -- sections, phases and the profiler ---------------------------------------

class _Annotations:
    """Stands in for jax.profiler.TraceAnnotation: records each
    section's name as it is entered and left, and its arguments."""

    def __init__(self):
        self.events = []
        self.args = []

    def __call__(self, name, **args):
        rec = self
        rec.args.append((name, args))

        class _Ann:
            def __enter__(self):
                rec.events.append(("enter", name))

            def __exit__(self, *exc):
                rec.events.append(("exit", name))

        return _Ann()


def _phases():
    return {k.split("=", 1)[1]: v["count"] for k, v in telemetry.snapshot()
            .get("jepsen_tpu_wgl_phase_seconds", {}).items()}


def _syncs():
    return {k.split("=", 1)[1]: v["count"] for k, v in telemetry.snapshot()
            .get("jepsen_tpu_wgl_sync_seconds", {}).items()}


def test_section_observes_phase_and_opens_annotation(monkeypatch):
    import jax.profiler
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    with telemetry.section("wgl.encode"):
        assert ann.events == [("enter", "wgl.encode")]
    with telemetry.section("wgl.offline.chunk"):
        pass
    assert ann.events == [("enter", "wgl.encode"), ("exit", "wgl.encode"),
                          ("enter", "wgl.offline.chunk"),
                          ("exit", "wgl.offline.chunk")]
    # a phase is timed; a chunk site is left to the chunk histogram
    assert _phases() == {"wgl.encode": 1}
    snap = telemetry.snapshot()["jepsen_tpu_wgl_phase_seconds"]
    assert snap["phase=wgl.encode"]["sum"] >= 0.0
    with pytest.raises(RuntimeError):
        with telemetry.section("wgl.blame"):
            raise RuntimeError("the block's error passes through")
    assert _phases() == {"wgl.encode": 1, "wgl.blame": 1}
    assert ann.events[-1] == ("exit", "wgl.blame")


def test_section_reads_no_env_and_starts_no_trace(tmp_path, monkeypatch):
    import os

    import jax.profiler
    from jepsen_tpu import models
    from jepsen_tpu.checker.linear import linearizable

    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **kw: started.append(a))
    monkeypatch.setenv("JEPSEN_TPU_PROFILE", str(tmp_path))

    class _Env(dict):
        reads = []

        def __getitem__(self, k):
            self.reads.append(k)
            return super().__getitem__(k)

        def get(self, k, default=None):
            self.reads.append(k)
            return super().get(k, default)

    env = _Env(os.environ)
    monkeypatch.setattr(os, "environ", env)
    for name in ("wgl.encode", "wgl.batch.chunk"):
        with telemetry.section(name):
            pass
    assert env.reads == []
    monkeypatch.undo()
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **kw: started.append(a))
    monkeypatch.setenv("JEPSEN_TPU_PROFILE", str(tmp_path))
    a = linearizable(models.cas_register(), chunk_entries=64).check(
        {}, _hist(5, n=300), {})
    assert a["valid?"] is True
    assert started == []            # only the CLI starts a trace


def test_cli_starts_and_stops_the_profiler_once(tmp_path, monkeypatch):
    import atexit

    import jax.profiler
    from jepsen_tpu import cli

    calls, at_exit = [], []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    monkeypatch.setattr(atexit, "register", at_exit.append)
    monkeypatch.setattr(cli, "_profile_dir", None)
    monkeypatch.delenv(cli.PROFILE_ENV, raising=False)
    cli._start_profiler()
    assert calls == [] and at_exit == []
    monkeypatch.setenv(cli.PROFILE_ENV, str(tmp_path))
    cli._start_profiler()
    cli._start_profiler()
    assert calls == [("start", str(tmp_path))]
    assert len(at_exit) == 1
    at_exit[0]()
    assert calls == [("start", str(tmp_path)), ("stop",)]


def test_invalid_history_fills_phases_and_named_syncs():
    from jepsen_tpu import models
    from jepsen_tpu.checker.linear import linearizable

    a = linearizable(models.cas_register(), chunk_entries=64).check(
        {}, _hist(13, n=300, corrupt=True), {})
    assert a["valid?"] is False and "op-index" in a
    phases = _phases()
    for p in ("wgl.encode", "wgl.steps", "wgl.stage", "wgl.summarize",
              "wgl.blame", "wgl.explain", "wgl.assemble"):
        assert phases.get(p, 0) >= 1, (p, phases)
    assert "wgl.split" not in phases
    syncs = _syncs()
    for site in ("offline steps attest", "offline liveness",
                 "offline carry attest", "offline summarize",
                 "offline blame"):
        assert syncs.get(site, 0) >= 1, (site, syncs)
    assert "device-sync" not in syncs   # every checker site is named


def test_keyed_check_splits_once_per_check_not_per_key():
    from jepsen_tpu import independent, models
    from jepsen_tpu.checker.linear import linearizable
    from jepsen_tpu.history import History
    from jepsen_tpu.independent import KV

    ops = []
    for k in range(5):
        sub = _hist(20 + k, n=60, corrupt=k == 3)
        ops += [{**o, "value": KV(k, o["value"]),
                 "process": o["process"] + 10 * k} for o in sub.ops]
    c = independent.checker(linearizable(models.cas_register()))
    for n in (1, 2):
        r = c.check({}, History(ops), {})
        assert r["valid?"] is False and r["failures"] == [3]
        phases = _phases()
        assert phases["wgl.split"] == n
        assert phases["wgl.assemble"] == n
        assert phases["wgl.blame"] >= n
    syncs = _syncs()
    assert syncs.get("batch blame", 0) >= 2
    assert "device-sync" not in syncs


def test_every_device_sync_names_its_own_fixed_site():
    """jepsen_tpu_wgl_sync_seconds is labelled by site: each
    guarded_device_get call in the package passes a string literal of
    its own, so the label set stays bounded and each series is one
    place in the code."""
    import ast
    from pathlib import Path

    pkg = Path(telemetry.__file__).resolve().parent
    sites = []
    for path in sorted(pkg.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            func = getattr(node, "func", None)
            if getattr(func, "id", getattr(func, "attr", None)) \
                    != "guarded_device_get":
                continue
            site = {k.arg: k.value for k in node.keywords}.get("site")
            assert isinstance(site, ast.Constant) \
                and isinstance(site.value, str), (path, node.lineno)
            sites.append(site.value)
    assert len(sites) >= 20
    assert len(set(sites)) == len(sites), sorted(sites)


# -- the batch engine's dispatch groups and their padding --------------------

def _sequential(n_ops, first_read=None):
    """One key's history of n_ops writes, one at a time: slot need 1 and
    n_ops + 1 merged steps (one per invoke, one for the last return). A
    first read of `first_read` from the nil register kills the key's
    frontier at its first step."""
    ops = []
    if first_read is not None:
        ops += [{"type": "invoke", "f": "read", "value": None, "process": 0},
                {"type": "ok", "f": "read", "value": first_read,
                 "process": 0}]
    for i in range(n_ops - len(ops) // 2):
        ops += [{"type": "invoke", "f": "write", "value": i % 5,
                 "process": 0},
                {"type": "ok", "f": "write", "value": i % 5, "process": 0}]
    return ops


def _concurrent(n_ops):
    """n_ops writes all in flight at once: slot need n_ops, n_ops + 1
    merged steps."""
    return ([{"type": "invoke", "f": "write", "value": i % 5, "process": i}
             for i in range(n_ops)] +
            [{"type": "ok", "f": "write", "value": i % 5, "process": i}
             for i in range(n_ops)])


def _lane_steps():
    snap = telemetry.snapshot().get("jepsen_tpu_wgl_batch_lane_steps_total",
                                    {})
    out = {"staged": 0, "dispatched": 0, "useful": 0}
    for labels, v in snap.items():
        out[labels.rsplit("kind=", 1)[1]] += v
    return out


def _groups():
    return telemetry.snapshot().get("jepsen_tpu_wgl_batch_groups_total", {})


def _batch(hists, **kw):
    from jepsen_tpu import models
    from jepsen_tpu.checker.wgl import analysis_tpu_batch

    return analysis_tpu_batch(models.cas_register(), hists, engine="dense",
                              **kw)


def test_batch_lane_steps_count_each_groups_lanes_and_keys():
    """Slot needs 1, 1, 1 and 6 bucket into a P=4 group of three keys
    (4 lanes, 4/6/10 steps) and a P=6 group of one key (1 lane, 7
    steps): dispatched = lanes x longest key, useful = the keys' steps."""
    rs = _batch([_sequential(3), _sequential(9), _sequential(5),
                 _concurrent(6)])
    assert [r["valid?"] for r in rs] == [True] * 4
    assert _lane_steps() == {"staged": 4 * 64 + 1 * 64,
                             "dispatched": 4 * 10 + 1 * 7,
                             "useful": (4 + 10 + 6) + 7}
    assert _groups() == {"family=dense": 2}


def test_batch_of_equal_lengths_pads_only_by_lane_rounding():
    rs = _batch([_sequential(5)] * 3)
    assert all(r["valid?"] is True for r in rs)
    steps = _lane_steps()
    assert steps == {"staged": 4 * 64, "dispatched": 4 * 6, "useful": 3 * 6}
    assert 1 - steps["useful"] / steps["dispatched"] == 1 - 3 / 4
    assert _groups() == {"family=dense": 1}


def test_an_all_dead_break_counts_only_the_steps_advanced():
    """Both keys die at their first step; the loop sees it one chunk
    late and stops after its second chunk of 4, far short of 41; both
    lanes were staged with all 128 rows."""
    rs = _batch([_sequential(40, first_read=3)] * 2, chunk_entries=4)
    assert [r["valid?"] for r in rs] == [False, False]
    assert _lane_steps() == {"staged": 2 * 128, "dispatched": 2 * 8,
                             "useful": 2 * 8}


def test_batch_group_span_wraps_each_group_under_a_trace(monkeypatch):
    import jax.profiler

    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    _batch([_sequential(3), _sequential(9), _sequential(5), _concurrent(6)])
    groups = [a for n, a in ann.args if n == "wgl.batch.group"]
    assert groups == [{"P": 4, "lanes": 4, "keys": 3, "steps": 10},
                      {"P": 6, "lanes": 1, "keys": 1, "steps": 7}]
    # each group span holds its own stage, chunks and summarize
    inside, depth = set(), 0
    for kind, name in ann.events:
        if name == "wgl.batch.group":
            depth += 1 if kind == "enter" else -1
        elif depth and kind == "enter":
            inside.add(name)
    assert depth == 0
    assert {"wgl.stage", "wgl.batch.chunk", "wgl.summarize"} <= inside
    # annotation only: no phase seconds of its own
    assert "wgl.batch.group" not in _phases()


# -- /metrics + /healthz e2e against a live service -------------------------

def _get(url: str) -> tuple[int, str]:
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def test_metrics_http_e2e_two_fake_etcd_streams(tmp_path, monkeypatch):
    """The acceptance drive: a service with --metrics-port serving two
    concurrent fake-etcd runs; /metrics returns Prometheus-parseable
    text carrying chunk-latency histograms and recovery/escalation/
    attest counters, /healthz the status() JSON (uptime_s + telemetry
    sub-map)."""
    import random

    from fake_etcd import FakeEtcd

    import jepsen_tpu.db
    import jepsen_tpu.os_
    from jepsen_tpu import core, generator as gen, models, service
    from jepsen_tpu.checker import linearizable
    from jepsen_tpu.suites import etcd

    # one deterministic recovery fault on run 0's stream, so the
    # recovery-rung counter has a live series to expose
    monkeypatch.setenv(
        "JEPSEN_TPU_FAULT_INJECT",
        "oom@stream-chunk/etcd-metrics-0/now0:1")

    svc = service.VerificationService()
    addr = svc.serve("127.0.0.1:0")
    msrv = telemetry.serve_metrics(0, host="127.0.0.1",
                                   healthz=svc.status)
    mport = msrv.server_address[1]

    fakes = [FakeEtcd(), FakeEtcd()]
    for f in fakes:
        f.port = f.start()

    def make_test(i, fake):
        rng = random.Random(4200 + i)
        return {
            "name": f"etcd-metrics-{i}",
            "start-time": f"now{i}",
            "nodes": ["n1", "n2", "n3"],
            "ssh": {"dummy": True},
            "db": jepsen_tpu.db.noop,
            "os": jepsen_tpu.os_.noop,
            "client": etcd.EtcdClient(),
            "client-url-fn":
                lambda node: f"http://127.0.0.1:{fake.port}",
            "concurrency": 4,
            "store-dir": str(tmp_path / "store"),
            "checker": linearizable(models.cas_register()),
            "service": addr,
            "online-chunk-entries": CHUNK,
            "online-checkpoint-every": 2,
            "generator": gen.clients(gen.limit(150, gen.mix([
                lambda: {"f": "read"},
                lambda: {"f": "write",
                         "value": rng.randint(0, 4)},
                lambda: {"f": "cas",
                         "value": [rng.randint(0, 4),
                                   rng.randint(0, 4)]},
            ]))),
        }

    done: dict = {}

    def run_one(i, fake):
        done[i] = core.run(make_test(i, fake))

    ths = [threading.Thread(target=run_one, args=(i, f))
           for i, f in enumerate(fakes)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(300)
    for f in fakes:
        f.stop()
    try:
        assert sorted(done) == [0, 1]
        for i in (0, 1):
            assert done[i]["results"]["valid?"] is True, \
                done[i]["results"]

        code, text = _get(f"http://127.0.0.1:{mport}/metrics")
        assert code == 200
        lines = _assert_prometheus_parseable(text)
        # chunk-latency histograms from the served streams
        assert any(
            'site="stream"' in ln and ln.rsplit(" ", 1)[1] != "0"
            for ln in lines.get("jepsen_tpu_wgl_chunk_seconds_count",
                                []))
        # recovery climbed a rung on the faulted stream
        assert any("kind=\"oom\"" in ln for ln in lines.get(
            "jepsen_tpu_wgl_recovery_rungs_total", []))
        # attestation verified staged buffers; escalation counter is
        # cataloged (HELP/TYPE) even when this run never escalated
        assert any(ln.rsplit(" ", 1)[1] != "0" for ln in lines.get(
            "jepsen_tpu_abft_verifications_total", []))
        assert "jepsen_tpu_screen_escalations_total" in text
        # two admitted streams reached verdicts
        assert any(
            'event="admitted"' in ln and ln.endswith(" 2")
            for ln in lines.get(
                "jepsen_tpu_service_stream_events_total", []))

        code, body = _get(f"http://127.0.0.1:{mport}/healthz")
        assert code == 200
        st = json.loads(body)
        assert st["uptime_s"] > 0
        assert "telemetry" in st
        assert len(st["streams"]) == 2

        # the socket 'metrics' verb answers the same registry
        host, _, port = addr.rpartition(":")
        conn = _socket.create_connection((host, int(port)))
        rf = conn.makefile("r")
        conn.sendall((json.dumps({"type": "metrics", "id": 1})
                      + "\n").encode())
        m = json.loads(rf.readline())
        assert m["ok"] is True
        assert "jepsen_tpu_service_stream_events_total" in m["metrics"]
        conn.close()
    finally:
        msrv.shutdown()
        svc.stop()


def test_service_status_carries_uptime_and_telemetry():
    from jepsen_tpu import service

    svc = service.VerificationService()
    st = svc.status()
    assert st["uptime_s"] >= 0
    assert isinstance(st["telemetry"], dict)


# -- surfacing ---------------------------------------------------------------

def test_report_telemetry_line():
    from jepsen_tpu import report

    line = report.telemetry_line({
        "linear": {"chunks": 12,
                   "recovered": {"faults": ["oom", "corrupt"],
                                 "retries": 2}},
        "elle": {"escalated": {"why": "suspicion"}},
    })
    assert "12 device chunks" in line
    assert "1 escalated" in line
    assert "2 recovery retries" in line
    assert "1 attest failures" in line
    # older stored results carry none of it
    assert report.telemetry_line({"valid?": True}) == ""
    assert report.telemetry_line({}) == ""
    assert report.telemetry_line(None) == ""


def test_web_metrics_route(tmp_path):
    from jepsen_tpu import web

    server = web.serve({"host": "127.0.0.1", "port": 0,
                        "store-dir": str(tmp_path)})
    port = server.server_address[1]
    try:
        code, home = _get(f"http://127.0.0.1:{port}/")
        assert code == 200 and "/metrics" in home
        code, text = _get(f"http://127.0.0.1:{port}/metrics")
        assert code == 200
        _assert_prometheus_parseable(text)
        assert "jepsen_tpu_web_requests_total" in text
    finally:
        server.shutdown()


def test_cli_service_has_metrics_port_option():
    from jepsen_tpu import cli

    longs = [o["long"]
             for o in cli.service_cmd()["service"]["opt_spec"]]
    assert "--metrics-port" in longs
