"""The YCSB-A deployment's pieces: its key chooser is YCSB's, its traffic
keeps the file's rates, the program's keyed checker agrees with the
reference on its histories, and the padding reader reads the program's
counter."""

import json
import math
import random
import struct
from collections import Counter

import pytest

from benchmark import harness, reference, relabel

from conftest import ROOT

CELL = "ycsb-a-keyed.zipf099"


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(ROOT, CELL)


def _fnv1a64(val):
    """FNV-1a 64 over the 8 little-endian bytes of a signed 64-bit value,
    as a signed 64-bit result."""
    h = 0xCBF29CE484222325
    for b in struct.pack("<q", val):
        h = ((h ^ b) * 1099511628211) % 2 ** 64
    return struct.unpack("<q", struct.pack("<Q", h))[0]


def test_fnvhash64_is_fnv1a_over_little_endian_bytes_then_java_abs(cell):
    ycsb = cell.generator
    negative = 0
    for val in [0, 1, 2, 255, 256, 999, 10 ** 10, 2 ** 40 + 7] + \
            random.Random(5).sample(range(10 ** 10 + 1), 200):
        h = _fnv1a64(val)
        negative += h < 0
        assert ycsb.fnvhash64(val) == abs(h), val
    assert negative > 50          # Math.abs does work here
    assert ycsb.fnvhash64(0) >= 0


def test_zipfian_draws_ycsbs_most_popular_items_first(cell):
    ycsb = cell.generator
    z = ycsb.Zipfian(0.99)
    assert z.next(0.0) == 0
    assert z.next(0.999 / ycsb.ZETAN) == 0              # u * zetan < 1
    assert z.next(1.5 / ycsb.ZETAN) == 1                # < zeta(2, 0.99)
    items = [z.next(u / 1000) for u in range(1000)]
    assert items == sorted(items) and items[-1] < ycsb.ITEMS
    with pytest.raises(ValueError):
        ycsb.Zipfian(0.9)
    with pytest.raises(ValueError):
        ycsb.history(dict(cell.config["shape"], distribution="uniform"),
                     cell.traffic, random.Random(1))


def _history(cell, seed, **params):
    rng = random.Random(seed)
    return cell.generator.history(cell.config["shape"],
                                  {**cell.traffic, **params}, rng)


def test_the_generator_is_deterministic_by_seed(cell):
    small = dict(cell.traffic, ops=2000)
    a = cell.generator.pool(cell.config["shape"], small, 2 ** 31 + 3)
    b = cell.generator.pool(cell.config["shape"], small, 2 ** 31 + 3)
    c = cell.generator.pool(cell.config["shape"], small, 2 ** 31 + 4)
    assert a == b
    assert a[0]["ops"] != c[0]["ops"]


def test_50k_ops_draw_every_key_and_a_hot_one(cell):
    ops, n = _history(cell, 2 ** 31 + 11)
    assert n == 50_000
    invokes = Counter(o["value"][0] for o in ops if o["type"] == "invoke")
    assert set(invokes) == set(range(1000))
    hottest = invokes.most_common(1)[0][1] / n
    assert 0.03 <= hottest <= 0.05
    assert sorted(invokes.values())[500] < 100       # the median key is short
    fs = Counter(o["f"] for o in ops if o["type"] == "invoke")
    assert abs(fs["read"] / n - 0.5) < 0.01            # read/update 0.5/0.5
    assert {o["value"][1] for o in ops if o["f"] == "write"} == set(range(5))


def test_crashes_and_corrupted_keys_follow_the_traffic_file(cell):
    [h] = cell.generator.pool(cell.config["shape"],
                              dict(cell.traffic, pool=1), 2 ** 31 + 17)
    ops = h["ops"]
    writes = [o for o in ops if o["type"] == "invoke" and o["f"] == "write"]
    info = [o for o in ops if o["type"] == "info"]
    assert {o["f"] for o in info} == {"write"}
    rate = cell.traffic["crash"]["rate"]     # a coin per write: binomial
    assert abs(len(info) / len(writes) - rate) < \
        4 * math.sqrt(rate * (1 - rate) / len(writes))
    # a crashed client comes back as a new process
    crashed = {o["process"] for o in info}
    after = [o for o in ops[max(o["index"] for o in info) + 1:]
             if o["process"] in crashed]
    assert after == []
    want = round(cell.traffic["corrupt_key_share"] * 1000)
    assert len(h["corrupted"]) == want
    bad = {ops[i]["value"][0] for i in h["corrupted"]}
    assert len(bad) == want
    for i in h["corrupted"]:
        assert ops[i]["type"] == "ok" and ops[i]["f"] == "read"
    verdicts = reference.linearizable_keyed(ops)
    assert {k for k, ok in verdicts.items() if not ok} == bad


@pytest.mark.parametrize("seed,share", [(2 ** 31 + 21, 0.0),
                                        (2 ** 31 + 22, 0.1),
                                        (2 ** 31 + 23, 0.1)])
def test_keyed_checker_answers_every_key_as_the_reference(cell, seed, share):
    """2k ops over 50 records, 5% of writes crashed, valid or with a
    tenth of the keys corrupted: the program's keyed check on the CPU,
    every key answered by the device batch engine, against the plain
    reference."""
    shape = dict(cell.config["shape"], records=50)
    traffic = dict(cell.traffic, ops=2000, pool=1, corrupt_key_share=share,
                   crash={"rate": 0.05, "effect": "either"})
    [h] = cell.generator.pool(shape, traffic, seed)
    want = cell.entry.reference(h["ops"])
    v = relabel.Relabels(seed, shape, traffic).variant(0)
    result = cell.entry.check(cell.entry.make(),
                              cell.entry.fresh(h["ops"], v))
    answers, analyzers, flags = cell.entry.summary(result, v)
    assert answers == want
    assert analyzers == {"tpu-wgl-batch"} and flags == []
    assert sum(not ok for ok in want.values()) == \
        (round(share * 50) + 1 if share else 0)


def test_relabelling_takes_fifty_clients(cell):
    r = relabel.Relabels(1, cell.config["shape"], cell.traffic)
    assert r.n_threads == 50 and r.keys == 1000


def test_benchmark_json_names_every_new_file(cell):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    short = cells["hazelcast-cas-register.short-2k"]
    assert short["traffic"] == "short-2k" and short["chips"] == 1
    t = json.loads((ROOT / "benchmark" / "traffic" /
                    "short-2k.json").read_text())
    assert t["generator"] == "register"
    assert (ROOT / "benchmark" / "metrics" / "batch_pad_share.py").exists()
    pad = {m["name"]: m for m in spec["per_layer"]}["batch_pad_share.keyed"]
    assert pad["workloads"] == ["etcd-register-keyed.50k", CELL]
    zipf = cells[CELL]
    assert zipf["config"] == "ycsb-a-keyed" and zipf["chips"] == 1
    conf = {c["name"]: c for c in spec["configs"]}["ycsb-a-keyed"]
    assert conf["file"] == "benchmark/configs/ycsb-a-keyed.json" and \
        conf["reduced"] == [] and cell.config["reduced"] == []
    assert cell.config["name"] == "ycsb-a-keyed"
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"].endswith(".keyed"):
            assert m["workloads"][-1] == CELL
    assert cell.traffic == {**cell.traffic, "generator": "ycsb", "ops": 50000,
                            "pool": 1, "crash": {"rate": 0.01,
                                                 "effect": "either"}}


def test_batch_pad_share_reads_the_programs_lane_steps(cell):
    from jepsen_tpu import telemetry

    read = harness.load_module(ROOT / "benchmark" / "metrics" /
                               "batch_pad_share.py").read
    telemetry.reset()
    assert read(None) is None
    shape = dict(cell.config["shape"], records=20)
    traffic = dict(cell.traffic, ops=600, pool=1, corrupt_key_share=0.0)
    [h] = cell.generator.pool(shape, traffic, 2 ** 31 + 31)
    v = relabel.Relabels(1, shape, traffic).variant(0)
    cell.entry.check(cell.entry.make(), cell.entry.fresh(h["ops"], v))
    snap = telemetry.snapshot()["jepsen_tpu_wgl_batch_lane_steps_total"]
    kinds = Counter()
    for labels, value in snap.items():
        kinds[labels.rsplit("kind=", 1)[1]] += value
    assert 0 < kinds["useful"] < kinds["dispatched"]
    assert read(None) == 1 - kinds["useful"] / kinds["dispatched"]
