"""The multi-register configuration's own parts: its plain reference
against the program's host oracle, its generator, and the control of
`correct` at the cell's size."""

import json
import random

import pytest

from benchmark import control, reference_multi_register as reference
from benchmark.generators import multi_register

from conftest import ROOT

CELL = "yugabyte-multi-key-acid.mk-50keys"
SHAPE = json.loads((ROOT / "benchmark" / "configs" /
                    "yugabyte-multi-key-acid.json").read_text())["shape"]
TRAFFIC = json.loads((ROOT / "benchmark" / "traffic" /
                      "mk-50keys.json").read_text())


def _small(seed):
    """A small keyed history in the cell's shape: 2 subkeys, values 0-2,
    crash-heavy, one in three with a read's micro-op rewritten."""
    rng = random.Random(seed)
    shape = dict(SHAPE, threads_per_key=4, reserved_readers=2,
                 keys_in_flight=1, subkeys=2, values=3, process_limit=9)
    params = {"keys": 1, "crash": {"rate": 0.3, "effect": "either"}}
    ops, _ = multi_register.history(shape, params, rng)
    if seed % 3 == 0:
        reads = [i for i, o in enumerate(ops)
                 if o["type"] == "ok" and o["f"] == "read"]
        i = rng.choice(reads)
        k, txn = ops[i]["value"]
        txn = [list(m) for m in txn]
        m = rng.randrange(len(txn))
        txn[m][2] = rng.choice([v for v in range(3) if v != txn[m][2]])
        ops[i] = dict(ops[i], value=(k, txn))
    return [dict(o, value=o["value"][1]) for o in ops]


@pytest.mark.parametrize("block", range(8))
def test_reference_agrees_with_the_host_oracle(block):
    from jepsen_tpu import models
    from jepsen_tpu.checker.linear import analysis_host
    from jepsen_tpu.history import History

    answers = set()
    for seed in range(block * 25, block * 25 + 25):
        ops = _small(seed)
        want = analysis_host(models.multi_register(), History(ops))
        assert reference.linearizable(ops) == want["valid?"], seed
        answers.add(want["valid?"])
    assert answers == {True, False}


def test_the_generator_is_deterministic_and_keeps_the_shape():
    params = dict(TRAFFIC, keys=6, corrupt_key_share=0.0)
    a, n = multi_register.history(SHAPE, params, random.Random(5))
    b, _ = multi_register.history(SHAPE, params, random.Random(5))
    assert a == b and n == sum(o["type"] == "invoke" for o in a)
    per_key = reference.key_histories(a)
    assert len(per_key) == 6
    for ops in per_key.values():
        procs = {o["process"] for o in ops}
        assert len(procs) == SHAPE["process_limit"]
        crashed = [o for o in ops if o["type"] == "info"]
        assert crashed and all(o["f"] == "write" for o in crashed)
        # a key ends only where a new process would be one too many
        assert len(crashed) >= SHAPE["process_limit"] \
            - SHAPE["threads_per_key"]
        for o in ops:
            assert 1 <= len(o["value"]) <= SHAPE["subkeys"]
        assert reference.linearizable(ops)


def test_the_control_finds_wrong_answers_at_the_cells_size(capsys):
    """benchmark/control.py at the cell's size: the reference with the
    crashed-op guarantee broken reads `wrong` > 0 on every seed."""
    control.main(["--workload", CELL, "--seeds", "1,2,3"])
    for line in capsys.readouterr().out.strip().splitlines():
        assert json.loads(line)["compared"]["wrong"]["value"] > 0
