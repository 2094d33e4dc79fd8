"""The yardstick's own pieces: the plain reference agrees with the
program's host search where that finishes, and the generator reproduces
its sources' op mix and sizes from a seed."""

import json
import random
from collections import Counter

import pytest

from benchmark import harness, reference, relabel

from conftest import ROOT


def _small(shape, seed, crash):
    gen = harness.load_module(ROOT / "benchmark" / "generators" /
                              "register.py")
    rng = random.Random(seed)
    ops, _ = gen.history(shape, {"ops": 60, "crash": crash}, rng)
    if rng.random() < 0.5:
        reads = [i for i, o in enumerate(ops)
                 if o["type"] == "ok" and o["f"] == "read"]
        i = rng.choice(reads)
        ops[i] = dict(ops[i], value=rng.randrange(shape["values"]))
    return ops


CRASHES = [{"count": 3, "placement": "random", "effect": "applied",
            "ops": "mutating"},
           {"count": 3, "placement": "even", "effect": "lost",
            "ops": "write"},
           {"rate": 0.1, "effect": "either"}]


@pytest.mark.parametrize("crash", CRASHES, ids=["applied", "lost", "either"])
def test_reference_agrees_with_the_programs_host_search(crash):
    """Against `linear.analysis_host` (Knossos-style, with no pruning) on
    small histories, a third of them rewritten at random."""
    from jepsen_tpu import models
    from jepsen_tpu.checker.linear import analysis_host
    from jepsen_tpu.history import History

    shape = {"threads_per_key": 4, "values": 3,
             "mix": {"read": 1, "write": 1, "cas": 1}}
    seen = Counter()
    for seed in range(150):
        ops = _small(shape, seed, crash)
        want = analysis_host(models.cas_register(), History(ops))["valid?"]
        assert reference.linearizable(ops) is want, seed
        seen[want] += 1
    assert seen[True] > 20 and seen[False] > 20


def test_the_control_breaks_only_crashed_ops():
    """With no crashed op the control is the reference."""
    shape = {"threads_per_key": 4, "values": 3,
             "mix": {"read": 1, "write": 1, "cas": 1}}
    for seed in range(50):
        ops = _small(shape, seed, {})
        assert reference.linearizable(ops, crashed="completed") is \
            reference.linearizable(ops)


def _traffic(workload):
    cell = harness.load_cell(ROOT, workload)
    return cell, cell.generator.pool(cell.config["shape"], cell.traffic,
                                     2 ** 31 + 12345)


def _mix(ops):
    return Counter(o["f"] for o in ops if o["type"] == "invoke")


def _live_peak(ops):
    """Most ops in flight at once, crashed ones not counted."""
    live = peak = 0
    for o in ops:
        if o["type"] == "invoke":
            live += 1
            peak = max(peak, live)
        else:
            live -= 1
    return peak


@pytest.mark.parametrize("workload,crashed,effect", [
    ("hazelcast-cas-register.crashed-10k", 9, "lost"),
    ("hazelcast-cas-register.plain-10k", 5, "applied")])
def test_single_register_traffic_keeps_its_source(workload, crashed, effect):
    cell, pool = _traffic(workload)
    assert len(pool) == cell.traffic["pool"] == 8
    for k, h in enumerate(pool):
        ops = h["ops"]
        assert h["n_ops"] == 10_000
        mix = _mix(ops)
        for f in ("read", "write", "cas"):          # r/w/cas each 1/3
            assert abs(mix[f] / 10_000 - 1 / 3) < 0.03, (f, mix)
        info = [o for o in ops if o["type"] == "info"]
        assert len(info) == crashed
        if effect == "lost":
            assert {o["f"] for o in info} == {"write"}
        assert _live_peak(ops) <= 5                  # 5 clients
        values = {o["value"] for o in ops if o["f"] == "write"}
        assert values <= set(range(5))
        assert len(h["corrupted"]) == (1 if k == 7 else 0)  # every 8th
    again = _traffic(workload)[1]
    assert again[3]["ops"] == pool[3]["ops"]         # the seed decides


def test_keyed_traffic_keeps_its_source():
    cell, pool = _traffic("etcd-register-keyed.50k")
    ops = pool[0]["ops"]
    assert abs(pool[0]["n_ops"] - 50_000) < 1_000
    per_key = reference.key_histories(ops)
    assert len(per_key) == 500
    for h in per_key.values():
        n = sum(o["type"] == "invoke" for o in h)
        assert 90 <= n <= 110                        # 100 +- 10%
        assert _live_peak(h) <= 10                   # 2n threads a key
    mix = _mix(ops)
    assert abs(mix["read"] / sum(mix.values()) - 0.5) < 0.03
    assert abs(mix["cas"] / mix["write"] - 2) < 0.15  # w/cas/cas
    # keys in flight at once, as concurrent-generator interleaves them
    open_keys, peak = set(), 0
    first = {k: h[0]["index"] for k, h in per_key.items()}
    last = {k: h[-1]["index"] for k, h in per_key.items()}
    for k in sorted(per_key, key=first.get):
        open_keys = {j for j in open_keys if last[j] > first[k]} | {k}
        peak = max(peak, len(open_keys))
    assert peak == 5
    assert len(pool[0]["corrupted"]) == 5            # one key in 100
    bad = {ops[i]["value"][0] for i in pool[0]["corrupted"]}
    verdicts = {k: reference.linearizable(per_key[k]) for k in bad}
    assert not any(verdicts.values())


def test_benchmark_json_names_every_file():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        assert (ROOT / "benchmark" / "traffic" /
                f"{w['traffic']}.json").exists()


@pytest.mark.parametrize("crash", CRASHES, ids=["applied", "lost", "either"])
def test_a_relabelled_history_keeps_its_verdict(crash):
    """The maps each check applies (benchmark/relabel.py) keep the
    reference's verdict, valid and rewritten histories alike."""
    shape = {"threads_per_key": 4, "values": 3,
             "mix": {"read": 1, "write": 1, "cas": 1}}
    relabels = relabel.Relabels(2 ** 31 + 7, shape, {})
    seen = Counter()
    for seed in range(150):
        ops = _small(shape, seed, crash)
        v = relabels.variant(seed)
        moved = [dict(o, value=v.value(o["value"]),
                      process=v.process(o["process"]),
                      time=o["time"] + v.time_shift) for o in ops]
        want = reference.linearizable(ops)
        assert reference.linearizable(moved) is want, seed
        seen[want] += 1
    assert seen[True] > 20 and seen[False] > 20


def test_keyed_relabelling_keeps_each_keys_verdict():
    cell, pool = _traffic("etcd-register-keyed.50k")
    ops = pool[0]["ops"]
    relabels = relabel.Relabels(2 ** 31 + 12345, cell.config["shape"],
                                cell.traffic)
    want = cell.entry.reference(ops)
    for n in (0, 1, 7):
        v = relabels.variant(n)
        hist = cell.entry.fresh(ops, v)
        got = reference.linearizable_keyed(list(hist))
        assert {v.base_key(k): a for k, a in got.items()} == \
            {k: a for k, a in want.items() if k != "all"}
        assert min(got) == n * 500


def test_variants_differ_within_a_run():
    shape = {"threads_per_key": 5, "values": 5}
    relabels = relabel.Relabels(2 ** 31 + 99, shape, {})
    maps = {(tuple(v.values), tuple(v.threads))
            for v in map(relabels.variant, range(2000))}
    assert len(maps) == 2000
    for n in range(2000):
        v = relabels.variant(n)
        assert sorted(v.values) == list(range(5))
        assert sorted(v.threads) == list(range(5))
