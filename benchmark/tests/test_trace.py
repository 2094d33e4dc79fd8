"""The trace reduction: on hand-made planes whose answers are known, and on
a trace recorded on a TPU v5e by the harness's own loop (fixtures/: two
checks of a 10k-op hazelcast-cas-register history, PERF.md §3)."""

import gzip
from pathlib import Path

import pytest

from benchmark import trace

FIXTURE = Path(__file__).parent / "fixtures" / "plain-10k.xplane.pb.gz"


def planes(programs, spans=()):
    return {"spans": [("bench.window", 0, 100)] + list(spans),
            "devices": {"/device:TPU:0": list(programs)}}


def test_busy_is_the_union_and_gaps_take_the_host_span():
    progs = [("jit_check(1)", 10, 30), ("jit_digest(2)", 25, 35),
             ("jit_check(1)", 60, 70)]
    spans = [("bench.check", 0, 50), ("bench.between", 50, 55),
             ("bench.check", 55, 100)]
    r = trace.reduce(planes(progs, spans))
    assert r["busy_s"] == pytest.approx(35e-9)      # [10,35] + [60,70]
    assert r["program_s"] == pytest.approx(40e-9)   # the overlap counts twice
    assert r["window_s"] == pytest.approx(100e-9)
    assert dict(r["breakdown"]["device_ops"]) == pytest.approx(
        {"jit_check": 30e-9, "jit_digest": 10e-9})
    # gaps [0,10] and [35,60] (midpoint 47.5) in a check, [70,100] too
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.check"] * 3
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 25e-9, 10e-9])
    assert r["idle_by_label"] == pytest.approx({"bench.check": 65e-9})


def test_a_gap_between_checks_is_the_harness_s():
    r = trace.reduce(planes([("jit_check(1)", 0, 49), ("jit_check(1)", 56, 100)],
                            [("bench.check", 0, 50),
                             ("bench.between", 50, 55),
                             ("bench.check", 55, 100)]))
    assert r["breakdown"]["idle_gaps"] == [["bench.between",
                                            pytest.approx(7e-9)]]


def test_programs_outside_the_window_are_clipped():
    r = trace.reduce(planes([("jit_check(1)", -20, 10),
                             ("jit_check(1)", 95, 130)]))
    assert r["busy_s"] == pytest.approx(15e-9)


def test_nothing_to_read_gives_nothing():
    assert trace.reduce({"spans": [], "devices": {}}) is None
    assert trace.reduce(planes([])) is None


def test_program_names_drop_their_fingerprint():
    assert trace.program_name("jit_check(7001927594704723328)") == \
        "jit_check"
    assert trace.program_name("jit_check_chunk_batch(12)") == \
        "jit_check_chunk_batch"


def test_the_chip_trace(tmp_path):
    path = tmp_path / "plain-10k.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    p = trace.read_planes(path)
    assert list(p["devices"]) == ["/device:TPU:0"]
    assert len(p["devices"]["/device:TPU:0"]) == 78
    assert [s[0] for s in p["spans"]] == [
        "bench.window", "bench.between", "bench.check", "bench.between",
        "bench.check", "bench.between"]
    r = trace.reduce(p)
    assert r["window_s"] == pytest.approx(0.289545627)
    assert r["busy_s"] == pytest.approx(0.013614326)
    assert r["program_s"] == pytest.approx(0.013614326)   # no overlap
    ops = dict(r["breakdown"]["device_ops"])
    assert list(ops)[:2] == ["jit_check", "jit_check_chunk"]
    assert ops["jit_check"] == pytest.approx(0.007956064)
    idle = r["idle_by_label"]
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert idle["bench.between"] == pytest.approx(0.112109483)
