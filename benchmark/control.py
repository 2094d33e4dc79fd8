#!/usr/bin/env python3
"""The control of `correct`, at a cell's own size: the plain reference
with the configuration's crashed-op guarantee broken (a crashed op is
taken to have completed at its :info) put in the program's place, its
answers judged by the harness's own comparison against the reference.
A sound comparison reads `wrong` > 0 on every seed. Not part of a
benchmark run.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3
"""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)

from benchmark import harness  # noqa: E402


def control_checks(cell, pool):
    """One check per history of the pool, answered by the control."""
    return [harness.Check(history=k, seconds=0.0,
                          answers=cell.entry.reference(h["ops"],
                                                       crashed="completed"),
                          analyzers={harness.DEVICE_ANALYZER + " (control)"})
            for k, h in enumerate(pool)]


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        pool = cell.generator.pool(cell.config["shape"], cell.traffic, seed)
        refs = {k: cell.entry.reference(h["ops"]) for k, h in enumerate(pool)}
        compared = harness.compare(control_checks(cell, pool), refs)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "compared": compared,
                          "seconds": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
