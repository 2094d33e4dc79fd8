"""Metric-name analyzer (JTS01x) — the old tools/lint_metrics.py,
migrated onto the shared driver as a *whole-program* pass.

Imports every instrumented module (which registers its metrics at
import time) and asserts the ``jepsen_tpu_<layer>_<name>_<unit>``
convention from doc/observability.md over the live registry:

  JTS010  registry unavailable / empty (import failure)
  JTS011  name does not match jepsen_tpu_<layer>_<name>_<unit>
  JTS012  counter not ending in _total
  JTS013  _total on a non-counter
  JTS014  histogram not ending in a measurable unit

Findings carry the pseudo-path ``<metrics-registry>`` (a registered
metric has no single source line)."""

from __future__ import annotations

import os
import re
import sys

from .base import Analyzer, Finding, SourceFile

HISTOGRAM_UNITS = ("seconds", "rows", "bytes", "ops", "elementops")

# the instrumented modules — importing them registers their metrics
MODULES = (
    "jepsen_tpu.telemetry",
    "jepsen_tpu._platform",
    "jepsen_tpu.trace",
    "jepsen_tpu.checker.wgl",
    "jepsen_tpu.checker.streaming",
    "jepsen_tpu.checker.screen",
    "jepsen_tpu.checker.abft",
    "jepsen_tpu.service",
    "jepsen_tpu.web",
    "jepsen_tpu.search.driver",
    "jepsen_tpu.chaos.driver",
)

REGISTRY_PATH = "<metrics-registry>"


def lint_registry(repo: str) -> tuple[list[tuple[str, str, str]], int]:
    """[(code, metric-name, message)], metric count. Runs against the
    live process-wide registry after importing MODULES."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if repo not in sys.path:
        sys.path.insert(0, repo)
    import importlib
    try:
        for m in MODULES:
            importlib.import_module(m)
        from jepsen_tpu import telemetry
    except Exception as e:  # noqa: BLE001 — report, don't crash the gate
        return [("JTS010", "registry",
                 f"could not import the instrumented modules: {e}")], 0

    pat = re.compile(
        r"^jepsen_tpu_(%s)_[a-z0-9_]+_(%s)$"
        % ("|".join(telemetry.LAYERS), "|".join(telemetry.UNITS)))
    problems: list[tuple[str, str, str]] = []
    metrics = telemetry.REGISTRY.metrics()
    if not metrics:
        return [("JTS010", "registry",
                 "registry is empty — instrumented modules did not "
                 "register their metrics at import time")], 0
    for m in metrics:
        if not pat.match(m.name):
            problems.append((
                "JTS011", m.name,
                f"does not match jepsen_tpu_<layer>_<name>_<unit> "
                f"(layers {telemetry.LAYERS}, units "
                f"{telemetry.UNITS})"))
            continue
        if m.kind == "counter" and not m.name.endswith("_total"):
            problems.append(("JTS012", m.name,
                             "counters must end in _total"))
        if m.kind != "counter" and m.name.endswith("_total"):
            problems.append((
                "JTS013", m.name,
                f"_total is reserved for counters ({m.kind})"))
        if m.kind == "histogram" and \
                not m.name.endswith(HISTOGRAM_UNITS):
            problems.append((
                "JTS014", m.name,
                f"histograms must end in a measurable unit "
                f"{HISTOGRAM_UNITS}"))
    return problems, len(metrics)


class MetricsAnalyzer(Analyzer):
    name = "metrics"
    codes = ("JTS010", "JTS011", "JTS012", "JTS013", "JTS014")

    def __init__(self, repo: str):
        self.repo = repo
        self.metric_count = 0

    def check_program(self, files: list[SourceFile]) -> list[Finding]:
        # only meaningful when the instrumented package is a target
        if not any(sf.rel.startswith("jepsen_tpu/") for sf in files):
            return []
        problems, n = lint_registry(self.repo)
        self.metric_count = n
        return [Finding(REGISTRY_PATH, 0, code, f"{name}: {msg}")
                for code, name, msg in problems]
