"""Key-sharded (independent) generator + checker tests, mirroring the
reference's `jepsen/test/jepsen/independent_test.clj`."""

import random

import pytest

import jepsen_tpu.generator as gen
from jepsen_tpu import independent
from jepsen_tpu.checker import linearizable, synth
from jepsen_tpu.generator.simulate import n_plus_nemesis_context, quick
from jepsen_tpu.history import History, history
from jepsen_tpu.independent import (
    KV, concurrent_generator, history_keys, ktuple, sequential_generator,
    subhistories, subhistory, tuple_key, tuple_value,
)
from jepsen_tpu.models import cas_register


def test_tuple():
    t = ktuple("k", 3)
    assert isinstance(t, KV)
    assert t.key == "k" and t.value == 3
    assert t == ("k", 3)  # still a tuple
    op = {"value": t}
    assert tuple_key(op) == "k"
    assert tuple_value(op) == 3
    assert tuple_key({"value": ("k", 3)}) is None  # plain pairs don't count


def test_sequential_generator():
    g = sequential_generator(
        [0, 1], lambda k: gen.limit(2, gen.repeat({"f": "read", "value": None})))
    ops = quick(n_plus_nemesis_context(2), gen.clients(g))
    assert [o["value"] for o in ops] == [
        KV(0, None), KV(0, None), KV(1, None), KV(1, None)]


def test_sequential_generator_exhausts():
    g = sequential_generator([], lambda k: {"f": "read"})
    assert quick(n_plus_nemesis_context(2), gen.clients(g)) == []


def test_concurrent_generator_partitions_threads():
    # 4 client threads, 2 per key: two keys run concurrently.
    g = concurrent_generator(
        2, iter(range(100)), lambda k: gen.limit(3, gen.repeat({"f": "w", "value": k})))
    ops = quick(n_plus_nemesis_context(4),
                gen.clients(gen.limit(12, g)))
    assert len(ops) == 12
    for o in ops:
        v = o["value"]
        assert isinstance(v, KV)
        assert v.value == v.key  # fgen closed over the right key
    # both groups made progress concurrently
    keys_by_group = {}
    for o in ops:
        keys_by_group.setdefault(o["process"] % 4 // 2,
                                 set()).add(o["value"].key)
    assert len(keys_by_group) == 2
    assert not (keys_by_group[0] & keys_by_group[1])


def test_concurrent_generator_rolls_to_next_key():
    # 2 threads, 1 group, keys exhaust one after another
    g = concurrent_generator(
        2, [10, 20], lambda k: gen.limit(2, gen.repeat({"f": "w", "value": k})))
    ops = quick(n_plus_nemesis_context(2), gen.clients(g))
    assert [o["value"] for o in ops] == [
        KV(10, 10), KV(10, 10), KV(20, 20), KV(20, 20)]


def test_concurrent_generator_divisibility():
    g = concurrent_generator(2, [1], lambda k: {"f": "r"})
    try:
        quick(n_plus_nemesis_context(3), gen.clients(g))
        raise AssertionError("expected ValueError")
    except ValueError as e:
        assert "divisible" in str(e)


def _kv_history():
    """Two keys; key 'a' linearizable, key 'b' not (read sees a value
    never written)."""
    ops = []
    t = [0]

    def add(process, typ, f, k, v):
        t[0] += 1
        ops.append({"type": typ, "f": f, "value": KV(k, v),
                    "process": process, "time": t[0]})

    add(0, "invoke", "write", "a", 1)
    add(0, "ok", "write", "a", 1)
    add(0, "invoke", "read", "a", None)
    add(0, "ok", "read", "a", 1)
    add(1, "invoke", "write", "b", 1)
    add(1, "ok", "write", "b", 1)
    add(1, "invoke", "read", "b", None)
    add(1, "ok", "read", "b", 2)  # never written!
    return history(ops)


def test_history_keys_and_subhistory():
    h = _kv_history()
    assert history_keys(h) == ["a", "b"]
    sub = subhistory("a", h)
    assert len(sub) == 4
    assert all(not isinstance(o["value"], KV) for o in sub)
    assert sub[3]["value"] == 1


def test_subhistory_keeps_nemesis_ops():
    h = history([
        {"type": "invoke", "f": "w", "value": KV("a", 1), "process": 0},
        {"type": "info", "f": "start", "value": None, "process": "nemesis"},
        {"type": "ok", "f": "w", "value": KV("a", 1), "process": 0},
    ])
    sub = subhistory("a", h)
    assert len(sub) == 3
    assert sub[1]["process"] == "nemesis"


def _op(process, typ, value, f="w"):
    return {"type": typ, "f": f, "value": value, "process": process}


def _nemesis(f):
    return {"type": "info", "f": f, "value": None, "process": "nemesis"}


def _seeded_keyed_ops(n_keys, n_ops, seed):
    """Random keyed ops over n_keys keys, with nemesis ops, non-KV client
    ops and KV ops from non-int processes mixed in."""
    rng = random.Random(seed)
    ops = []
    for i in range(n_ops):
        r = rng.random()
        if r < 0.03:
            ops.append(_nemesis(rng.choice(["start", "stop"])))
        elif r < 0.05:
            ops.append(_op(rng.randrange(10), "invoke", rng.randrange(5)))
        elif r < 0.06:
            ops.append(_op("nemesis", "info", KV(rng.randrange(n_keys), i)))
        else:
            ops.append(_op(rng.randrange(10), rng.choice(["invoke", "ok"]),
                           KV(rng.randrange(n_keys), rng.randrange(5))))
    return ops


SPLIT_CASES = {
    "nemesis-before-between-after": [
        _nemesis("start"),
        _op(0, "invoke", KV("a", 1)),
        _nemesis("stop"),
        _op(0, "ok", KV("a", 1)),
        _op(1, "invoke", KV("b", 2)),
        _nemesis("start"),          # after key a's last op
        _op(1, "ok", KV("b", 2)),
        _nemesis("stop"),
    ],
    "key-first-seen-after-nemesis": [
        _op(0, "invoke", KV("a", 1)),
        _op(0, "ok", KV("a", 1)),
        _nemesis("start"),
        _nemesis("stop"),
        _op(1, "invoke", KV("c", 3)),
        _op(1, "ok", KV("c", 3)),
    ],
    "kv-op-from-non-int-process": [
        _op(0, "invoke", KV("a", 1)),
        _op("nemesis", "info", KV("a", 9)),
        _op("nemesis", "info", KV("z", 9)),
        _op(0, "ok", KV("a", 1)),
    ],
    "non-kv-client-op-dropped": [
        _op(0, "invoke", KV("a", 1)),
        _op(2, "invoke", 5),
        _op(2, "ok", ("a", 5)),     # a plain pair is no KV
        _op(0, "ok", KV("a", 1)),
    ],
    "one-and-true-are-one-key": [
        _op(0, "invoke", KV(1, 1)),
        _nemesis("start"),
        _op(1, "invoke", KV(True, 2)),
        _op(0, "ok", KV(1, 1)),
        _op(1, "ok", KV(True, 2)),
    ],
    "empty": [],
    "seeded-200-keys": _seeded_keyed_ops(200, 5000, seed=2024),
}


@pytest.mark.parametrize("name", sorted(SPLIT_CASES))
def test_subhistories_match_per_key_subhistory(name):
    ops = SPLIT_CASES[name]
    before = [dict(o) for o in ops]
    h = history(ops)
    want = {k: subhistory(k, h) for k in history_keys(h)}
    got = subhistories(h)
    ids = {id(o) for o in ops}
    assert list(got) == list(want)                     # key order
    for k in want:
        assert isinstance(got[k], History)
        assert got[k].ops == want[k].ops               # op for op
        for a, b in zip(got[k].ops, want[k].ops):
            assert (a is b) == (id(b) in ids)          # shared ops not copied
    assert ops == before                               # inputs untouched
    assert all(o["value"] is p["value"] for o, p in zip(ops, before))


def _keyed_nemesis_history():
    """Four keys of synthetic register histories, interleaved round-robin
    with nemesis ops between them; key 2 is corrupted."""
    per_key = []
    for k in range(4):
        h = synth.register_history(40, concurrency=3, values=5,
                                   crash_rate=0.05, seed=300 + k)
        per_key.append(synth.corrupt(h, seed=k) if k == 2 else h)
    streams = [[{**o, "value": KV(k, o["value"]),
                 "process": o["process"] + 1000 * k} for o in h.ops]
               for k, h in enumerate(per_key)]
    ops = [_nemesis("start")]
    i = 0
    while any(streams):
        for s in streams:
            if s:
                ops.append(s.pop(0))
        i += 1
        if i % 17 == 0:
            ops.append(_nemesis("stop" if i % 34 == 0 else "start"))
    return history(ops)


@pytest.mark.parametrize("algorithm", ["auto", "host"])
def test_independent_check_unchanged_by_one_pass_split(algorithm,
                                                       monkeypatch):
    """The checker's verdict, failures and per-key results (blame's
    op-index included) are those the per-key split gives."""
    h = _keyed_nemesis_history()
    c = independent.checker(linearizable(cas_register(), algorithm),
                            strict_device=True)
    got = c.check({}, h, {})
    with monkeypatch.context() as m:
        m.setattr(independent, "subhistories", lambda hist: {
            k: subhistory(k, hist) for k in history_keys(hist)})
        want = c.check({}, h, {})
    assert got["valid?"] is False and got["failures"] == [2]
    assert got["valid?"] == want["valid?"]
    assert got["failures"] == want["failures"]
    assert list(got["results"]) == list(want["results"]) == [0, 1, 2, 3]
    for k in want["results"]:
        g, w = got["results"][k], want["results"][k]
        assert g["valid?"] == w["valid?"]
        assert g.get("analyzer") == w.get("analyzer")
        assert g.get("op-index") == w.get("op-index")
        assert g.get("op") == w.get("op")
    if algorithm == "auto":
        assert "tpu" in got["results"][0]["analyzer"]
        assert got["results"][2]["op-index"] is not None


def test_independent_checker_host():
    c = independent.checker(linearizable(cas_register(), "host"))
    res = c.check({}, _kv_history(), {})
    assert res["valid?"] is False
    assert res["failures"] == ["b"]
    assert res["results"]["a"]["valid?"] is True
    assert res["results"]["b"]["valid?"] is False


def test_independent_checker_tpu_batched():
    c = independent.checker(linearizable(cas_register(), "auto"))
    res = c.check({}, _kv_history(), {})
    assert res["valid?"] is False
    assert res["failures"] == ["b"]
    assert res["results"]["a"]["valid?"] is True
    assert res["results"]["b"]["valid?"] is False
    # the batched path actually ran on device
    assert "tpu" in res["results"]["a"]["analyzer"]


def test_independent_strict_device_raises_and_default_falls_back(
        caplog, monkeypatch):
    import logging
    import pytest
    from jepsen_tpu.checker import wgl

    def boom(*a, **k):
        raise RuntimeError("simulated kernel breakage")

    monkeypatch.setattr(wgl, "analysis_tpu_batch", boom)
    c = linearizable(cas_register(), "auto")
    with pytest.raises(RuntimeError, match="simulated"):
        independent.checker(c, strict_device=True).check(
            {}, _kv_history(), {})
    # default: loud warning, correct per-key fallback verdict
    with caplog.at_level(logging.WARNING, "jepsen_tpu.independent"):
        res = independent.checker(c).check({}, _kv_history(), {})
    assert res["valid?"] is False and res["failures"] == ["b"]
    assert any("falling back" in r.message for r in caplog.records)
    assert "simulated" in res["device-fallback"]["error"]


def test_concurrent_generator_skips_empty_key_generators():
    # keys 0-1 yield empty generators; productive keys must still run
    def fgen(k):
        if k < 2:
            return None
        return gen.limit(2, gen.repeat({"f": "w", "value": k}))

    g = concurrent_generator(2, iter(range(4)), fgen)
    ops = quick(n_plus_nemesis_context(2), gen.clients(g))
    assert [o["value"] for o in ops] == [
        KV(2, 2), KV(2, 2), KV(3, 3), KV(3, 3)]
