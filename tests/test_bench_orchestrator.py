"""End-to-end tests for bench.py's orchestration: the no-backend
error line, the rehearsal on a non-TPU backend, and the
one-parseable-JSON-line contract.

These run the real orchestrator as a subprocess at tiny scales
(BENCH_N_OPS/BENCH_N_TXNS), so they cover exactly the code the driver
executes: a missing backend must yield one diagnosable JSON error line
and a non-zero exit, never host numbers, a stack trace or a hang."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.perf  # ~3 min of subprocess pipelines

BENCH = str(Path(__file__).resolve().parent.parent / "bench.py")

FAST_ENV = {
    "BENCH_N_OPS": "300",
    "BENCH_N_TXNS": "2000",
    "BENCH_HOST_BUDGET_S": "2",
    "BENCH_PREFLIGHT_TIMEOUT_S": "30",
}


def _run_bench(extra_env: dict, timeout: int = 420):
    env = {**os.environ, **FAST_ENV, **extra_env}
    p = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, timeout=timeout, env=env)
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert lines, f"no stdout at all; stderr: {p.stderr[-500:]}"
    # the contract: exactly one line, and it parses
    assert len(lines) == 1, f"expected one JSON line, got {lines}"
    return p.returncode, json.loads(lines[0])


def test_no_backend_exits_nonzero_with_one_error_line():
    # an unknown platform makes the preflight probe fail fast and
    # deterministically: one JSON error line, no numbers, exit 1
    rc, out = _run_bench({"JAX_PLATFORMS": "no-such-platform"})
    assert rc != 0
    assert out["error"] == "tpu-backend-unavailable"
    assert out["value"] is None and out["vs_baseline"] is None
    assert list(out["extra"]) == ["preflight"]
    assert out["extra"]["preflight"]["rc"] != 0
    assert "no-such-platform" in out["extra"]["preflight"]["stderr_tail"]


def test_total_budget_exhaustion_soft_fails_with_final_json():
    """One hung/slow config must never turn the round into a run with
    no output: sections past the whole-run soft budget are marked
    {"ok": false, "timeout": true} and the final JSON line still lands
    (on the CPU it is a rehearsal, so it also says so and exits 1)."""
    rc, out = _run_bench({"JAX_PLATFORMS": "cpu",
                          "BENCH_TOTAL_BUDGET_S": "1"})
    import bench
    assert rc == 1
    assert out["error"] == (
        "not-a-chip-run: rehearsal on cpu; sections-over-budget: "
        + ", ".join(name for name, *_ in bench.SECTIONS))
    sections = out["extra"]["sections"]
    # every section accounted for (the orchestrator table), every one
    # soft-failed rather than silently dropped
    assert len(sections) == len(bench.SECTIONS)
    for name, meta in sections.items():
        assert meta == {"ok": False, "timeout": True,
                        "skipped": "total bench budget exhausted"}, \
            (name, meta)
    assert out["value"] is None


def test_cpu_rehearsal_runs_every_section_and_fails():
    # CPU platform: every section runs, but a CPU run is a rehearsal —
    # exit 1, and its headline number is not filed under the metric
    rc, out = _run_bench({"JAX_PLATFORMS": "cpu"}, timeout=900)
    assert rc == 1
    assert out["error"] == "not-a-chip-run: rehearsal on cpu"
    assert out["value"] is None and out["vs_baseline"] is None
    assert out["extra"]["rehearsal_value"] > 0
    cfg = out["extra"]["configs"]
    for key in ("1_register_200", "2_register_wgl_2k", "3_elle_wr_10k",
                "4_sharded_50k", "5_elle_append_100k"):
        assert key in cfg, f"missing section result {key}"
    assert cfg["5_elle_append_100k"]["with_64_injected_cycles_s"] > 0
    adv = out["extra"]["adversarial_10k"]
    assert adv["tpu"]["verdict"] == "True"
    assert out["extra"]["backend"]["platform"] == "cpu"
