"""The staticcheck driver: collect files, run analyzers, apply
suppressions and the committed baseline, report.

Usage (also via ``make lint`` / ``make staticcheck``)::

    python -m tools.staticcheck [targets...]
        [--only style,metrics,device-sync,locks,retrace]
        [--baseline PATH] [--write-baseline] [--summary-json]

Exit 0 when the tree is clean (or every finding is baselined);
exit 1 with one ``path:line: CODE message`` per finding otherwise —
the same contract as the old tools/lint.py, which this subsumes."""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from .base import Finding, SourceFile
from .devicesync import DeviceSyncAnalyzer
from .lockcheck import LockAnalyzer
from .metrics import MetricsAnalyzer
from .retrace import RetraceAnalyzer
from .style import StyleAnalyzer

ROOTS = ["jepsen_tpu", "tests", "tools", "bench.py",
         "__graft_entry__.py", "chip_smoke.py"]
ANALYZER_ORDER = ("style", "metrics", "device-sync", "locks",
                  "retrace")


def repo_root() -> Path:
    return Path(__file__).resolve().parent.parent.parent


def default_baseline() -> Path:
    return Path(__file__).resolve().parent / "baseline.txt"


def make_analyzers(only: set[str] | None = None,
                   repo: Path | None = None) -> list:
    repo = str(repo or repo_root())
    byname = {
        "style": StyleAnalyzer(),
        "metrics": MetricsAnalyzer(repo),
        "device-sync": DeviceSyncAnalyzer(),
        "locks": LockAnalyzer(),
        "retrace": RetraceAnalyzer(),
    }
    names = [n for n in ANALYZER_ORDER
             if only is None or n in only]
    unknown = (only or set()) - set(byname)
    if unknown:
        raise SystemExit(f"unknown analyzer(s): {sorted(unknown)} "
                         f"(choose from {list(ANALYZER_ORDER)})")
    return [byname[n] for n in names]


def collect_files(targets: list[str], repo: Path) -> list[SourceFile]:
    files: list[Path] = []
    for t in targets or ROOTS:
        p = (repo / t) if not Path(t).is_absolute() else Path(t)
        if p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
        elif p.suffix == ".py":
            files.append(p)
    return [SourceFile.load(f, repo) for f in files]


def load_baseline(path: Path) -> dict[str, int]:
    """Baseline entries as a multiset of `path: CODE message` keys."""
    out: dict[str, int] = {}
    if not path.exists():
        return out
    for line in path.read_text().splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        out[line] = out.get(line, 0) + 1
    return out


def write_baseline(path: Path, findings: list[Finding]) -> None:
    lines = [
        "# staticcheck baseline — pre-existing findings that do not",
        "# fail the gate. One `path: CODE message` per line (no line",
        "# numbers, so unrelated edits don't churn this file).",
        "# Regenerate: python -m tools.staticcheck --write-baseline",
    ]
    lines += sorted(f.baseline_key() for f in findings)
    path.write_text("\n".join(lines) + "\n")


def run(targets: list[str], only: set[str] | None = None,
        baseline_path: Path | None = None,
        repo: Path | None = None) -> dict:
    """Run the suite; returns the summary dict (see --summary-json).
    `repo` overrides the tree root (tests point it at a fixture
    tree)."""
    repo = repo or repo_root()
    analyzers = make_analyzers(only, repo=repo)
    files = collect_files(targets, repo)
    sf_by_rel = {sf.rel: sf for sf in files}

    findings: list[Finding] = []
    suppressed = 0
    for az in analyzers:
        scoped = [sf for sf in files if az.scope(sf)]
        raw: list[Finding] = []
        for sf in scoped:
            raw.extend(az.check_file(sf))
        raw.extend(az.check_program(files))
        for f in raw:
            sf = sf_by_rel.get(f.path)
            if sf is not None and sf.suppressed(
                    f, legacy=az.legacy_noqa):
                suppressed += 1
                continue
            findings.append(f)

    baseline = load_baseline(baseline_path or default_baseline())
    live: list[Finding] = []
    baselined = 0
    remaining = dict(baseline)
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.code)):
        key = f.baseline_key()
        if remaining.get(key, 0) > 0:
            remaining[key] -= 1
            baselined += 1
        else:
            live.append(f)

    by_code: dict[str, int] = {}
    for f in live:
        by_code[f.code] = by_code.get(f.code, 0) + 1
    return {
        "files": len(files),
        "analyzers": [az.name for az in analyzers],
        "findings": len(live),
        "baselined": baselined,
        "suppressed": suppressed,
        "by_code": dict(sorted(by_code.items())),
        "_live": live,
        "_all": findings,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.staticcheck",
        description="repo-specific static-analysis gate "
                    "(doc/static_analysis.md)")
    ap.add_argument("targets", nargs="*",
                    help=f"files/dirs to check (default: {ROOTS})")
    ap.add_argument("--only",
                    help="comma-separated analyzer subset "
                         f"(default: all of {list(ANALYZER_ORDER)})")
    ap.add_argument("--baseline", type=Path,
                    help="baseline file (default: "
                         "tools/staticcheck/baseline.txt)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "and exit 0")
    ap.add_argument("--summary-json", action="store_true",
                    help="emit one machine-readable JSON summary "
                         "line on stdout (bench.py embeds it)")
    args = ap.parse_args(argv)

    only = ({t.strip() for t in args.only.split(",") if t.strip()}
            if args.only else None)
    res = run(args.targets, only=only, baseline_path=args.baseline)

    if args.write_baseline:
        if args.only or args.targets:
            # a filtered run sees only a subset of findings; writing
            # it out would silently erase every baseline entry
            # belonging to the analyzers/files that did not run
            print("staticcheck: --write-baseline requires a full run "
                  "(no --only, no explicit targets)", file=sys.stderr)
            return 2
        path = args.baseline or default_baseline()
        write_baseline(path, res["_all"])
        print(f"staticcheck: wrote {len(res['_all'])} baseline "
              f"entr{'y' if len(res['_all']) == 1 else 'ies'} to "
              f"{path}", file=sys.stderr)
        return 0

    for f in res["_live"]:
        print(f.render())
    summary = (f"staticcheck: {res['files']} files, "
               f"{len(res['analyzers'])} analyzers, "
               f"{res['findings']} finding(s) "
               f"({res['baselined']} baselined, "
               f"{res['suppressed']} suppressed)")
    print(summary, file=sys.stderr)
    if args.summary_json:
        out = {k: v for k, v in res.items()
               if not k.startswith("_")}
        print(json.dumps(out))
    return 1 if res["findings"] else 0
