"""One run of one benchmark cell: build the cell's histories from the seed,
warm every one, check them back to back through the cell's entry point
for the measured window, then compare every verdict of the window with
the plain reference and print one JSON line.

Everything that belongs to one cell is found by name: the cell in
`BENCHMARK.json`, its configuration file, `traffic/<traffic>.json`, the
generator and entry point those name (`generators/<name>.py`,
`entries/<name>.py`), and one reader per family of metrics
(`metrics/<stem>.py`, the stem being the name up to its first dot:
`kernel_ms_per_kop.plain` is read by `metrics/kernel_ms_per_kop.py`).
Adding a cell, a configuration, a traffic mix or a metric adds files; no
code here changes.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path

from benchmark import relabel

ROOT = Path(__file__).resolve().parent.parent
CACHE = Path("benchmark") / ".jax_cache"    # fixed: the path keys the cache
TRACE = Path("benchmark") / ".trace"
TRACED_S = 3.0    # a traced run checks for 3 s only (PERF.md §3:
                  # stopping a trace takes ~30 s a traced second)
DEVICE_ANALYZER = "tpu-wgl"


class NoChip(Exception):
    """The machine lacks the accelerator or the chips the cell asks for."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """A module from its file, named after it (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    entry: object
    generator: object
    end_to_end: list
    per_layer: list
    metrics_dir: Path


def load_cell(root, name):
    """The cell `name` of `root`/BENCHMARK.json with everything it names."""
    root = Path(root)
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    here = root / "benchmark"
    config = load_json(root / conf["file"])
    traffic = load_json(here / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                entry=load_module(here / "entries" / f"{config['entry']}.py"),
                generator=load_module(
                    here / "generators" / f"{traffic['generator']}.py"),
                end_to_end=e2e, per_layer=per_layer,
                metrics_dir=here / "metrics")


def require_chips(n):
    """The devices, if JAX sees a TPU with at least `n` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devs)}")
    return devs


def use_cache(root):
    """JAX's persistent compilation cache at a fixed path in the checkout,
    in the config and in the environment (where the program's own
    `_platform.compilation_cache_dir` looks), keeping every compile,
    however short: the keyed path's dispatch-group kernels each compile in
    under JAX's default 1 s threshold."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    d = str(Path(root) / CACHE)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    return d


class Compiles:
    """JAX's compile events, from its monitoring hooks: backend compiles
    (XLA/Mosaic, or a fetch from the persistent cache) with their seconds,
    tracing/lowering seconds, and persistent-cache hits."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        from jax import monitoring

        self.backend_s = self.trace_s = 0.0
        self.backend_n = self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == self.BACKEND:
            self.backend_s += secs
            self.backend_n += 1
        elif event in self.TRACE:
            self.trace_s += secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def chunk_seconds():
    """(sum, count) over every series of the program's per-chunk
    dispatch + sync histogram."""
    from jepsen_tpu import telemetry

    snap = telemetry.snapshot(prefix="jepsen_tpu_wgl_chunk_seconds")
    series = snap.get("jepsen_tpu_wgl_chunk_seconds", {}).values()
    return (sum(s["sum"] for s in series), sum(s["count"] for s in series))


@dataclass
class Check:
    """One entry call of the window, reduced to what is compared."""
    history: int
    seconds: float
    answers: dict = field(default_factory=dict)
    analyzers: set = field(default_factory=set)
    flags: list = field(default_factory=list)
    error: str | None = None

    @property
    def failed(self):
        return bool(self.error or self.flags or any(
            a not in (True, False) for a in self.answers.values()))


def run_checks(cell, checker, pool, relabels, seconds, annotate):
    """Check histories back to back, cycling through the pool, each check
    relabelled anew, until `seconds` have passed and the check under way
    has ended, inside one `bench.window` span. Returns the checks and the
    window's length."""
    entry = cell.entry
    checks = []
    t0 = time.monotonic()

    def one():
        k = len(checks) % len(pool)
        with annotate("bench.between"):
            v = relabels.variant(len(pool) + len(checks))
            hist = entry.fresh(pool[k]["ops"], v)
        with annotate("bench.check"):
            tc = time.monotonic()
            try:
                result, err = entry.check(checker, hist), None
            except Exception as e:  # noqa: BLE001 — counted as failed
                traceback.print_exc()
                result, err = None, f"{type(e).__name__}: {e}"
            dt = time.monotonic() - tc
        with annotate("bench.between"):
            c = Check(history=k, seconds=dt, error=err)
            if result is not None:
                c.answers, c.analyzers, c.flags = entry.summary(result, v)
            checks.append(c)

    with annotate("bench.window"):
        one()
        while time.monotonic() - t0 < seconds:
            one()
    return checks, time.monotonic() - t0


LIMITS = {"wrong": 0, "unanswered": 0, "off_device": 0}


def compare(checks, references):
    """The numbers `correct` is decided on, each against its limit:
    wrong: answers (a history's verdict, and each key's) that differ from
    the reference's or are missing or extra; unanswered: checks that
    raised or answered neither true nor false; off_device: checks
    answered by other than a device analyzer, or through a fallback or
    recovery."""
    wrong = unanswered = off_device = 0
    for c in checks:
        if c.error or any(a not in (True, False)
                          for a in c.answers.values()):
            unanswered += 1
            continue
        ref = references[c.history]
        wrong += sum(c.answers.get(k) != v for k, v in ref.items())
        wrong += len(set(c.answers) - set(ref))
        if c.flags or not c.analyzers or any(
                not a.startswith(DEVICE_ANALYZER) for a in c.analyzers):
            off_device += 1
    numbers = {"wrong": wrong, "unanswered": unanswered,
               "off_device": off_device}
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}


@dataclass
class Run:
    """What the metric readers read."""
    setup_s: float
    window_s: float
    ops: int
    check_s: float            # host seconds inside entry calls
    chunk_s: float            # window delta of the program's chunk timer
    chunk_n: int
    compiles: int             # backend compiles inside the window
    trace: dict | None        # trace.reduce() of a traced run's window


def read_metrics(cell, run, specs):
    out = {}
    for m in specs:
        stem = m["name"].split(".")[0]
        v = load_module(cell.metrics_dir / f"{stem}.py").read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def start_trace(root):
    """A profiler trace into the checkout, without Python call events."""
    import jax

    d = Path(root) / TRACE
    shutil.rmtree(d, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)
    return d


def stop_trace():
    import jax

    t = time.monotonic()
    jax.profiler.stop_trace()
    print(f"trace: stopped in {time.monotonic() - t:.3f} s", file=sys.stderr)


def read_trace(trace_dir):
    """trace.reduce() of the trace under `trace_dir`, which is removed."""
    from benchmark import trace

    t = time.monotonic()
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    size = files[-1].stat().st_size if files else 0
    summary = trace.reduce(trace.read_planes(files[-1])) if files else None
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"trace: {size} bytes read in {time.monotonic() - t:.3f} s",
          file=sys.stderr)
    return summary


def main(argv=None, root=ROOT, t_start=None, chip_check=require_chips):
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(root, args.workload)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # not under /tmp
    import jax

    cache = use_cache(root)
    try:
        devs = chip_check(cell.chips)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    compiles = Compiles()

    t = time.monotonic()
    pool = cell.generator.pool(cell.config["shape"], cell.traffic, args.seed)
    relabels = relabel.Relabels(args.seed, cell.config["shape"], cell.traffic)
    generate_s = time.monotonic() - t
    checker = cell.entry.make()
    t = time.monotonic()
    for k, h in enumerate(pool):
        cell.entry.check(checker, cell.entry.fresh(h["ops"],
                                                   relabels.variant(k)))
    warmup_s = time.monotonic() - t
    setup = {"generate_s": generate_s, "warmup_s": warmup_s,
             "compile_s": compiles.backend_s, "compiles": compiles.backend_n,
             "trace_s": compiles.trace_s, "cache_hits": compiles.cache_hits,
             "cache": cache}

    # the pool and what set-up made stay alive all run: out of the
    # collector's way, so its full passes in the window scan only what
    # the checks make
    gc.collect()
    gc.freeze()
    n0 = compiles.backend_n
    s0, c0 = chunk_seconds()
    trace_dir = start_trace(root) if args.trace else None
    setup_s = time.monotonic() - t_start
    checks, window_s = run_checks(
        cell, checker, pool, relabels,
        min(args.seconds, TRACED_S) if args.trace else args.seconds,
        jax.profiler.TraceAnnotation)
    s1, c1 = chunk_seconds()
    window_compiles = compiles.backend_n - n0
    gc.unfreeze()
    if args.trace:
        stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])
    del checker
    gc.collect()

    t = time.monotonic()
    references = {k: cell.entry.reference(pool[k]["ops"])
                  for k in sorted({c.history for c in checks})}
    compared = compare(checks, references)
    reference_s = time.monotonic() - t

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    summary = read_trace(trace_dir) if args.trace else None
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    answered = [c for c in checks if not c.error]
    run = Run(setup_s=setup_s, window_s=window_s,
              ops=sum(pool[c.history]["n_ops"] for c in answered),
              check_s=sum(c.seconds for c in answered),
              chunk_s=s1 - s0, chunk_n=c1 - c0, compiles=window_compiles,
              trace=summary)
    metrics = read_metrics(cell, run,
                           cell.per_layer if args.trace else cell.end_to_end)
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    line = {"correct": correct, "attempted": len(checks),
            "failed": sum(c.failed for c in checks), "metrics": metrics,
            "device": device}
    if summary is not None:
        line["breakdown"] = summary["breakdown"]
    line["setup"] = setup
    took = sorted(c.seconds for c in checks)
    line["window"] = {"checks": len(checks), "ops": run.ops,
                      "seconds": window_s, "reference_s": reference_s,
                      "compiles": window_compiles,
                      "check_s": [took[0], took[len(took) // 2], took[-1]]}
    line["compared"] = compared
    print(f"setup: {json.dumps(setup)}", file=sys.stderr)
    print(f"window: {json.dumps(line['window'])}", file=sys.stderr)
    for k, v in compared.items():
        print(f"compared {k} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
