"""The harness end to end on the CPU: every file loads by name, a cell
added as data runs, and `correct` comes out false under the control and
under each fault a cell can have."""

import json

import pytest

from benchmark import harness

from conftest import ROOT

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def drive(root, workload, capsys, chip_check, trace=0, seconds=0.3):
    rc = harness.main(["--workload", workload, "--seed", "3000000019",
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, chip_check=chip_check)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)


def test_every_file_loads_by_name():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
    for w in spec["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert callable(cell.entry.check) and callable(cell.generator.pool)
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
    for m in spec["end_to_end"] + spec["per_layer"]:
        stem = m["name"].split(".")[0]
        path = ROOT / "benchmark" / "metrics" / f"{stem}.py"
        assert callable(harness.load_module(path).read), m["name"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(small_root, capsys, any_device, workload):
    rc, line = drive(small_root, workload, capsys, any_device)
    assert rc == 0 and line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 1
    names = {m["name"] for m in harness.load_cell(ROOT, workload).end_to_end}
    assert set(line["metrics"]) == names
    assert list(line)[-1] == "compared"


def test_traced_run_reports_per_layer_metrics(small_root, capsys,
                                              any_device):
    rc, line = drive(small_root, "hazelcast-cas-register.plain-10k", capsys,
                     any_device, trace=1)
    assert rc == 0 and line["correct"] is True
    # the CPU has no TPU plane: the trace's metrics are left out, the
    # program's and the host's are there
    assert set(line["metrics"]) == {"entry_host_ms_per_kop.plain",
                                    "engine_chunk_ms_per_kop.plain",
                                    "window_compiles.plain"}
    assert line["metrics"]["window_compiles.plain"]["value"] == 0


def test_a_cell_added_as_data_runs(small_root, capsys, any_device):
    spec = json.loads((small_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({
        "name": "hazelcast-cas-register.tiny", "config":
        "hazelcast-cas-register", "traffic": "tiny", "chips": 1,
        "why": "added by a test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "hazelcast-cas-register.plain-10k" in m.get("workloads", []):
            m["workloads"].append("hazelcast-cas-register.tiny")
    (small_root / "BENCHMARK.json").write_text(json.dumps(spec))
    (small_root / "benchmark" / "traffic" / "tiny.json").write_text(
        json.dumps({"generator": "register", "ops": 200, "pool": 1,
                    "crash": {"count": 1, "placement": "random",
                              "effect": "applied", "ops": "mutating"}}))
    rc, line = drive(small_root, "hazelcast-cas-register.tiny", capsys,
                     any_device)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"ops_per_s.plain", "setup_s"}


def test_a_cpu_backend_fails_without_a_result(small_root, capsys):
    rc, line = drive(small_root, "hazelcast-cas-register.plain-10k", capsys,
                     harness.require_chips)
    assert rc != 0 and line is None


def _patch_check(monkeypatch, root, workload, alter):
    """Run the real entry call, then `alter` its result in place."""
    cell = harness.load_cell(root, workload)
    real_load = harness.load_cell

    def load(r, name):
        c = real_load(r, name)
        check = c.entry.check

        def broken(checker, hist):
            result = check(checker, hist)
            alter(result, hist)
            return result
        monkeypatch.setattr(c.entry, "check", broken)
        return c
    monkeypatch.setattr(harness, "load_cell", load)
    return cell


def _control_result(cell):
    """The control in the program's place: the reference with crashed ops
    taken as completed, answered in the program's result shape."""
    def alter(result, hist):
        ctrl = cell.entry.reference(list(hist), crashed="completed")
        if "results" in result:
            for k, r in result["results"].items():
                r["valid?"] = ctrl[k]
            result["valid?"] = all(ctrl[k] for k in result["results"])
        else:
            result["valid?"] = ctrl[None]
    return alter


def _flip_one(result, hist):
    """An answer altered where it is produced."""
    if "results" in result:
        r = next(iter(result["results"].values()))
        r["valid?"] = not r["valid?"]
    else:
        result["valid?"] = not result["valid?"]


def _drop_half(result, hist):
    """Half of the batch left out."""
    keys = sorted(result["results"])
    for k in keys[: len(keys) // 2]:
        del result["results"][k]


FAULTS = [(w, "flip", _flip_one) for w in CELLS] + \
    [("etcd-register-keyed.50k", "drop-half", _drop_half)]


@pytest.mark.parametrize("workload,name,alter", FAULTS,
                         ids=[f"{w}-{n}" for w, n, _ in FAULTS])
def test_a_fault_makes_correct_false(small_root, capsys, any_device,
                                     monkeypatch, workload, name, alter):
    _patch_check(monkeypatch, small_root, workload, alter)
    rc, line = drive(small_root, workload, capsys, any_device)
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["wrong"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_makes_correct_false(small_root, capsys, any_device,
                                         monkeypatch, workload):
    cell = harness.load_cell(small_root, workload)
    _patch_check(monkeypatch, small_root, workload, _control_result(cell))
    rc, line = drive(small_root, workload, capsys, any_device)
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["wrong"]["value"] > 0


def test_a_raising_check_is_unanswered(small_root, capsys, any_device,
                                       monkeypatch):
    calls = []

    def boom(result, hist):
        calls.append(1)
        if len(calls) > 4:             # past the pool's four warm-up checks
            raise RuntimeError("device lost")
    _patch_check(monkeypatch, small_root,
                 "hazelcast-cas-register.plain-10k", boom)
    rc, line = drive(small_root, "hazelcast-cas-register.plain-10k",
                     capsys, any_device)
    assert line["correct"] is False and line["failed"] == line["attempted"]
    assert line["compared"]["unanswered"]["value"] == line["attempted"]


def test_a_run_never_checks_the_same_history_twice(small_root, capsys,
                                                   any_device, monkeypatch):
    """Warm-up and window: every check's history differs from every
    other's, beyond its times and keys, though the pool repeats."""
    seen = []

    def record(result, hist):
        seen.append(tuple((o["f"], o["type"], str(o["value"]),
                           o["process"]) for o in hist))
    for workload in CELLS:
        seen.clear()
        with monkeypatch.context() as mp:
            _patch_check(mp, small_root, workload, record)
            rc, line = drive(small_root, workload, capsys, any_device,
                             seconds=1.0)
        pool = harness.load_cell(small_root, workload).traffic["pool"]
        assert rc == 0 and line["correct"] is True
        assert len(seen) > pool, workload        # the pool came round
        if workload.startswith("etcd"):          # keys shifted per check
            seen[:] = [tuple((f, t, v.split(",", 1)[1], p)
                             for f, t, v, p in h) for h in seen]
        assert len(set(seen)) == len(seen), workload
