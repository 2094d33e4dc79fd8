"""Max-history-length probe: how large a register history the WGL
dense engine solves within a wall-clock budget on the current backend.

BASELINE.md's metric line is "ops verified/sec; max history length
solved < 300 s" — this tool produces that datapoint (the bench proper
stays at 10k/50k/100k so its runtime remains bounded).

Usage: python tools/scale_probe.py [--n 1000000] [--budget 280]
Prints one JSON line. Crash-free shape by construction: every crashed
mutating op permanently doubles the configuration space (the same
exponential wall the reference's knossos hits), so "max length" is
only well-defined on the crash-free workload.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--budget", type=float, default=280.0)
    ap.add_argument("--concurrency", type=int, default=6)
    args = ap.parse_args()

    from jepsen_tpu import models
    from jepsen_tpu.checker import synth
    from jepsen_tpu.checker.wgl import analysis_tpu

    model = models.cas_register()
    t0 = time.monotonic()
    h = synth.register_history(args.n, concurrency=args.concurrency,
                               values=5, crash_rate=0.0, seed=45100)
    synth_s = time.monotonic() - t0

    import jax
    backend = jax.devices()[0]
    t0 = time.monotonic()
    a = analysis_tpu(model, h, budget_s=args.budget)
    check_s = time.monotonic() - t0
    print(json.dumps({
        "n_ops": args.n,
        "platform": backend.platform,
        "device_kind": backend.device_kind,
        "synth_s": round(synth_s, 1),
        "check_s": round(check_s, 1),
        "ops_per_s": round(args.n / check_s, 1),
        "valid": a["valid?"] is True,
        "analyzer": a["analyzer"],
        "solved_in_budget": a["valid?"] is True and check_s <= args.budget,
    }))
    return 0 if a["valid?"] is True else 1


if __name__ == "__main__":
    raise SystemExit(main())
