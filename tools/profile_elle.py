#!/usr/bin/env python
"""Profile the elle list-append checker on a 100k-txn history.

Round-3 recorded 23,157 txns/s against round-2's 27,335 on the same
checker source; this harness exists to attribute that kind of movement
instead of arguing about it.  It reports:

  * a wall-clock breakdown of check()'s phases (history indexing,
    host graph build, device SCC/closure kernels, certificate
    reconstruction) — by re-running the phases the way check() composes
    them (`jepsen_tpu/checker/elle/list_append.py:243-274`);
  * best/median/worst of N full check() calls (run-to-run variance is
    the first suspect for a sub-10% delta);
  * optionally a jax.profiler trace (--trace DIR) for op-level
    attribution in TensorBoard/XProf.

Usage:
  python tools/profile_elle.py [--n 100000] [--repeat 5] [--trace DIR]
Writes a JSON summary to stdout (one line, like bench.py sections).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--trace", default=None,
                    help="directory for a jax.profiler trace of one run")
    args = ap.parse_args()

    import jax

    from jepsen_tpu.checker import synth
    from jepsen_tpu.checker.elle import kernels, list_append
    from jepsen_tpu.history import history as as_history

    out = {"n_txns": args.n,
           "platform": jax.devices()[0].platform,
           "device_kind": getattr(jax.devices()[0], "device_kind", "?")}

    t0 = time.monotonic()
    eh = synth.append_history(args.n, seed=45100)
    out["synth_s"] = round(time.monotonic() - t0, 3)

    # warm: compile every kernel shape this history exercises
    r = list_append.check(eh)
    assert r["valid?"] is True, r

    # ---- phase breakdown (mirrors check()'s composition) ----
    phases = {}
    t0 = time.monotonic()
    hist = as_history(eh).index()
    phases["index_history_s"] = round(time.monotonic() - t0, 3)

    t0 = time.monotonic()
    txns, edges, a, incompatible = list_append.graph(hist)
    phases["graph_build_s"] = round(time.monotonic() - t0, 3)

    t0 = time.monotonic()
    a.g1a_cases(), a.g1b_cases(), list_append.internal_cases(a.hist)
    phases["read_write_cases_s"] = round(time.monotonic() - t0, 3)

    t0 = time.monotonic()
    cyc = kernels.analyze_edges(len(txns), edges)
    phases["device_scc_closure_s"] = round(time.monotonic() - t0, 3)

    t0 = time.monotonic()
    kernels.certificates(txns, edges, cyc)
    phases["certificates_s"] = round(time.monotonic() - t0, 3)
    out["phases"] = phases
    out["edge_count"] = (int(edges.shape[0])
                         if hasattr(edges, "shape") else len(edges))

    # ---- full-call variance ----
    times = []
    for _ in range(args.repeat):
        t0 = time.monotonic()
        r = list_append.check(eh)
        times.append(time.monotonic() - t0)
        assert r["valid?"] is True
    out["check_s"] = {
        "best": round(min(times), 3),
        "median": round(statistics.median(times), 3),
        "worst": round(max(times), 3),
        "spread_pct": round(100 * (max(times) - min(times)) / min(times),
                            1),
    }
    out["txns_per_s_best"] = round(args.n / min(times), 1)

    if args.trace:
        with jax.profiler.trace(args.trace):
            list_append.check(eh)
        out["trace_dir"] = args.trace

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
