#!/usr/bin/env python
"""Chip smoke test: the checker stage end to end on one TPU, in one process.

Every phase goes through the public checker entry points at the sizes
users of the framework check (BASELINE.md configs 2, 4 and 5), builds its
histories from --seed with `jepsen_tpu.checker.synth`, and compares the
device verdict with an independent reference on the same history:

  headline     10k-op etcd-shape CAS register (+ a corrupted copy) through
               linearizable(algorithm="tpu"); reference linear.analysis_host
  adversarial  10k-op front-loaded crashed-writes register (dense engine,
               Pallas closure on TPU); valid by construction
  keyed        hazelcast-shape 100 keys x 500 ops in one keyed history
               through independent.checker(strict_device=True), one key
               corrupted; reference analysis_host per key
  elle         tidb-shape 100k-txn list-append, valid and with 64 injected
               G1c cycles; reference the same check on the host mirror
  orchestrator a hermetic core.run (atom DB, dummy remote, online
               streaming checker) of a few thousand ops; reference
               analysis_host on the recorded history

A phase fails when a verdict disagrees with its reference, a result
carries 'recovered' / 'degraded' / 'degraded-checkers', or its analyzer
is not a device analyzer; max_recovery_retries=0 makes any device fault
show up that way. Earlier lines print each phase's seconds, compile
seconds, the engine and dedup that ran, and the device's peak memory.
The last line is one JSON object: {"ok": true, "device": {...}} only on a
TPU with every phase passing. Without a TPU the full size is refused up
front; --small rehearses every phase at a CPU-sized scale and still ends
"ok": false with exit 1 — a rehearsal is never a pass.

--chips 4 runs only the sharded paths, each on a mesh of 4 devices and
on a mesh of 1 device: the keyed check (wgl.check_batch_sharded) and the
Elle check (mesh=), asserting equal verdicts and staged inputs spread
over all 4 devices.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


class PhaseFailed(Exception):
    pass


def _require(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def _clean(result, what):
    """A device verdict that went through a fallback is not a pass."""
    for key in ("recovered", "degraded", "degraded-checkers",
                "device-fallback"):
        _require(key not in result, f"{what}: result carries {key!r}: "
                 f"{result.get(key)}")


def _device_analyzer(result, what):
    a = str(result.get("analyzer", ""))
    _require(a.startswith("tpu-wgl"), f"{what}: analyzer {a!r} is not a "
             "device analyzer")


class CompileClock:
    """Seconds JAX spent compiling, from its monitoring events:
    `backend` (XLA/Mosaic compiles, or fetching them from the
    persistent cache — the part the cache saves) and `trace` (tracing
    to a jaxpr and lowering it; a jit traced inside another one is
    counted in both, so `trace` can exceed the wall time), and
    persistent-cache hits."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace",
              "/jax/core/compile/backend_compile_duration": "backend"}

    def __init__(self):
        from jax import monitoring

        self.trace = self.backend = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        kind = self.EVENTS.get(event)
        if kind is not None:
            setattr(self, kind, getattr(self, kind) + secs)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _keyed_history(per_key):
    """One keyed history: each key's ops with KV values, processes
    offset per key so no process id is shared across keys."""
    from jepsen_tpu.history import History
    from jepsen_tpu.independent import KV

    ops = []
    for k, h in enumerate(per_key):
        for o in h.ops:
            o = dict(o)
            o["value"] = KV(k, o["value"])
            o["process"] = o["process"] + k * 100_000
            ops.append(o)
    return History(ops)


# -- phases ---------------------------------------------------------------

def phase_headline(sz, seed, on_tpu):
    from jepsen_tpu import models
    from jepsen_tpu.checker import synth
    from jepsen_tpu.checker.linear import analysis_host, linearizable

    model = models.cas_register()
    h = synth.register_history(sz["register"], concurrency=5, values=5,
                               crash_rate=0.0005, seed=seed)
    bad = synth.corrupt(h, seed=seed)
    chk = linearizable(model, algorithm="tpu", max_recovery_retries=0)
    out = {}
    for name, hist, want in (("valid", h, True), ("corrupt", bad, False)):
        r = chk.check({}, hist, {})
        ref = analysis_host(model, hist)["valid?"]
        _clean(r, f"headline {name}")
        _device_analyzer(r, f"headline {name}")
        _require(r["valid?"] is want and ref is want,
                 f"headline {name}: device {r['valid?']}, host {ref}, "
                 f"expected {want}")
        out[name] = {"analyzer": r["analyzer"], "dedup": r.get("dedup"),
                     "engine-reason": r.get("engine-reason")}
    return out


def phase_adversarial(sz, seed, on_tpu):
    from jepsen_tpu import models
    from jepsen_tpu.checker import synth
    from jepsen_tpu.checker.linear import linearizable

    h = synth.adversarial_register_history(sz["register"], seed=seed)
    r = linearizable(models.cas_register(), algorithm="tpu",
                     max_recovery_retries=0).check({}, h, {})
    _clean(r, "adversarial")
    _require(r["analyzer"] == "tpu-wgl-dense",
             f"adversarial: analyzer {r['analyzer']!r}, expected the "
             "dense engine")
    if on_tpu:
        _require(r.get("closure") == "pallas-closure",
                 f"adversarial: closure {r.get('closure')!r} on TPU")
    _require(r["valid?"] is True,
             f"adversarial: {r['valid?']} on a valid-by-construction "
             "history")
    return {"analyzer": r["analyzer"], "closure": r.get("closure"),
            "engine-reason": r.get("engine-reason")}


def _keyed_inputs(sz, seed):
    from jepsen_tpu.checker import synth

    per_key = [synth.register_history(sz["key_ops"], concurrency=4,
                                      values=5, crash_rate=0.005,
                                      seed=seed + 1000 + i)
               for i in range(sz["keys"])]
    bad_key = sz["keys"] // 2
    # a read of 5, one past the written values: keeps the batch's state
    # range (and so its engine) what the valid keys need
    per_key[bad_key] = synth.corrupt(per_key[bad_key], seed=seed, value=5)
    return per_key, bad_key


def phase_keyed(sz, seed, on_tpu):
    from jepsen_tpu import independent, models
    from jepsen_tpu.checker.linear import analysis_host, linearizable

    model = models.cas_register()
    per_key, bad_key = _keyed_inputs(sz, seed)
    chk = independent.checker(
        linearizable(model, algorithm="tpu", max_recovery_retries=0),
        strict_device=True)
    r = chk.check({}, _keyed_history(per_key), {})
    _clean(r, "keyed")
    _require(r["valid?"] is False, f"keyed: aggregate {r['valid?']}")
    _require(r["failures"] == [bad_key],
             f"keyed: failing keys {r['failures']}, expected [{bad_key}]")
    analyzers = set()
    for k, h in enumerate(per_key):
        rk = r["results"][k]
        _clean(rk, f"keyed key {k}")
        _device_analyzer(rk, f"keyed key {k}")
        analyzers.add(rk["analyzer"])
        ref = analysis_host(model, h)["valid?"]
        _require(rk["valid?"] is ref,
                 f"keyed key {k}: device {rk['valid?']}, host {ref}")
    return {"analyzers": sorted(analyzers),
            "dedup": r["results"][0].get("dedup"),
            "keys": len(per_key), "bad-key": bad_key}


def _elle_inputs(sz, seed):
    from jepsen_tpu.checker import synth

    eh = synth.append_history(sz["txns"], seed=seed)
    return eh, synth.inject_append_cycles(eh, sz["cycles"], "G1c")


def _elle_host(hist):
    from jepsen_tpu.checker.elle import list_append

    os.environ["JEPSEN_TPU_ELLE_HOST"] = "1"
    try:
        return list_append.check(hist)
    finally:
        del os.environ["JEPSEN_TPU_ELLE_HOST"]


def phase_elle(sz, seed, on_tpu):
    from jepsen_tpu.checker.elle import list_append

    eh, bad = _elle_inputs(sz, seed)
    r = list_append.check(eh)
    _require(r["valid?"] is True, f"elle valid: {r['anomaly-types']}")
    _require(_elle_host(eh)["valid?"] is True,
             "elle valid: host mirror disagrees")
    rb = list_append.check(bad)
    _clean(rb, "elle cycles")
    _require(rb["valid?"] is False and "G1c" in rb["anomaly-types"],
             f"elle cycles: {rb['valid?']} {rb['anomaly-types']}")
    _require(rb.get("classifier") == "device",
             f"elle cycles: classifier {rb.get('classifier')!r}")
    host = _elle_host(bad)
    _require(host["anomaly-types"] == rb["anomaly-types"],
             f"elle cycles: device {rb['anomaly-types']}, host mirror "
             f"{host['anomaly-types']}")
    return {"anomaly-types": rb["anomaly-types"],
            "classifier": rb["classifier"], "txns": sz["txns"]}


def phase_orchestrator(sz, seed, on_tpu):
    import random
    import tempfile

    from jepsen_tpu import core, generator as gen, models, testkit
    from jepsen_tpu.checker.linear import analysis_host, linearizable

    state = testkit.AtomState()
    rng = random.Random(seed)
    model = models.cas_register(0)   # AtomDB.setup zeroes the cell
    with tempfile.TemporaryDirectory() as store:
        t = testkit.noop_test()
        t.update({
            "name": "chip smoke", "ssh": {"dummy": True},
            "store-dir": store,
            "db": testkit.atom_db(state),
            "client": testkit.atom_client(state, latency_s=0.0),
            "concurrency": 5, "online": True,
            "max-recovery-retries": 0,
            "checker": linearizable(model, algorithm="tpu"),
            "generator": gen.clients(gen.limit(sz["run_ops"], gen.mix([
                lambda: {"f": "read"},
                lambda: {"f": "write", "value": rng.randint(0, 4)},
                lambda: {"f": "cas", "value": [rng.randint(0, 4),
                                               rng.randint(0, 4)]},
            ]))),
        })
        done = core.run(t)
    res = done["results"]
    _clean(res, "orchestrator")
    _device_analyzer(res, "orchestrator")
    _require(res.get("streamed") is True,
             "orchestrator: the verdict was not streamed online")
    ref = analysis_host(model, done["history"])["valid?"]
    _require(res["valid?"] is True and ref is True,
             f"orchestrator: device {res['valid?']}, host {ref}")
    return {"analyzer": res["analyzer"], "ops": len(done["history"])}


# -- four chips -------------------------------------------------------------

def phase_sharded_keyed(sz, seed, meshes):
    from jepsen_tpu import models
    from jepsen_tpu.checker.wgl import check_batch_sharded

    per_key, bad_key = _keyed_inputs(sz, seed)
    verdicts, seconds = {}, {}
    for n, mesh in meshes.items():
        t0 = time.monotonic()
        all_ok, per_ok, info = check_batch_sharded(
            models.cas_register(), per_key, mesh=mesh, slots=16,
            return_info=True, max_recovery_retries=0)
        _clean(info, f"sharded keyed ({n} devices)")
        spread = {g["staged-devices"] for g in info["groups"]}
        _require(spread == {n}, f"sharded keyed: staged on {spread} "
                 f"devices of a {n}-device mesh")
        failing = [int(k) for k in (~per_ok).nonzero()[0]]
        _require(not all_ok and failing == [bad_key],
                 f"sharded keyed ({n} devices): failing keys {failing}")
        verdicts[n] = per_ok.tolist()
        seconds[n] = time.monotonic() - t0
    _require(len({json.dumps(v) for v in verdicts.values()}) == 1,
             "sharded keyed: verdicts differ across meshes")
    return {"keys": len(per_key), "bad-key": bad_key,
            "seconds-by-mesh": seconds}


def phase_sharded_elle(sz, seed, meshes):
    from jepsen_tpu.checker.elle import list_append

    _eh, bad = _elle_inputs(sz, seed)
    types, seconds = {}, {}
    for n, mesh in meshes.items():
        t0 = time.monotonic()
        r = list_append.check(bad, mesh=mesh)
        seconds[n] = time.monotonic() - t0
        _clean(r, f"sharded elle ({n} devices)")
        _require(r.get("classifier") == "device"
                 and r.get("classifier-devices") == n,
                 f"sharded elle: classifier {r.get('classifier')!r} on "
                 f"{r.get('classifier-devices')} devices of {n}")
        _require(r["valid?"] is False and "G1c" in r["anomaly-types"],
                 f"sharded elle ({n} devices): {r['anomaly-types']}")
        types[n] = r["anomaly-types"]
    _require(len({tuple(t) for t in types.values()}) == 1,
             f"sharded elle: anomaly types differ across meshes: {types}")
    return {"anomaly-types": types[max(types)], "seconds-by-mesh": seconds}


FULL = {"register": 10_000, "keys": 100, "key_ops": 500,
        "txns": 100_000, "cycles": 64, "run_ops": 3_000}
SMALL = {"register": 600, "keys": 8, "key_ops": 100,
         "txns": 2_000, "cycles": 8, "run_ops": 300}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=45100)
    ap.add_argument("--small", action="store_true",
                    help="CPU-sized rehearsal (never a pass)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    def finish(ok, device, reason=None):
        line = {"ok": ok, "device": device}
        if reason:
            line["reason"] = reason
        print(json.dumps(line), flush=True)
        return 0 if ok else 1

    try:
        import jax

        from jepsen_tpu._platform import use_compilation_cache
    except ImportError as e:
        return finish(False, None, f"cannot import the checker: {e}")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.small:
        return finish(False, device, "no TPU: the full size runs only "
                      "on the chip (--small rehearses on the CPU)")
    if len(devs) < args.chips:
        return finish(False, device, f"--chips {args.chips} needs "
                      f"{args.chips} devices, found {len(devs)}")
    print(f"cache: {use_compilation_cache()}", flush=True)
    clock = CompileClock()
    sz = SMALL if args.small else FULL

    if args.chips == 4:
        import numpy as np
        from jax.sharding import Mesh

        meshes = {n: Mesh(np.array(devs[:n]), ("keys",)) for n in (4, 1)}
        phases = [("sharded-keyed", lambda: phase_sharded_keyed(
                       sz, args.seed, meshes)),
                  ("sharded-elle", lambda: phase_sharded_elle(
                      sz, args.seed, meshes))]
    else:
        phases = [(name, lambda fn=fn: fn(sz, args.seed, on_tpu))
                  for name, fn in (("headline", phase_headline),
                                   ("adversarial", phase_adversarial),
                                   ("keyed", phase_keyed),
                                   ("elle", phase_elle),
                                   ("orchestrator", phase_orchestrator))]

    failures = []
    summary = {}
    t_all = time.monotonic()
    for name, run in phases:
        b0, r0, t0 = clock.backend, clock.trace, time.monotonic()
        try:
            info = run()
            status = "ok"
        except PhaseFailed as e:
            info, status = {}, f"FAILED: {e}"
            failures.append(f"{name}: {e}")
        except Exception as e:  # noqa: BLE001 — report, then fail the run
            traceback.print_exc()
            info, status = {}, f"ERROR: {type(e).__name__}: {e}"
            failures.append(f"{name}: {type(e).__name__}: {e}")
        info.update(seconds=time.monotonic() - t0,
                    compile_seconds=clock.backend - b0,
                    trace_seconds=clock.trace - r0, status=status)
        summary[name] = info
        print(f"phase {name}: {json.dumps(info, default=str)}", flush=True)

    stats = devs[0].memory_stats() or {}
    summary["total"] = {"seconds": time.monotonic() - t_all,
                        "compile_seconds": clock.backend,
                        "trace_seconds": clock.trace,
                        "cache_hits": clock.cache_hits,
                        "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    print(f"summary: {json.dumps(summary, default=str)}", flush=True)
    if failures:
        return finish(False, device, "; ".join(failures))
    if not on_tpu:
        return finish(False, device, "rehearsal on "
                      f"{device['platform']}: a CPU run is never a pass")
    return finish(True, device)


if __name__ == "__main__":
    sys.exit(main())
