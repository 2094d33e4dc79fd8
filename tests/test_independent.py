"""Key-sharded (independent) generator + checker tests, mirroring the
reference's `jepsen/test/jepsen/independent_test.clj`."""

import jepsen_tpu.generator as gen
from jepsen_tpu import independent
from jepsen_tpu.checker import linearizable
from jepsen_tpu.generator.simulate import n_plus_nemesis_context, quick
from jepsen_tpu.history import history
from jepsen_tpu.independent import (
    KV, concurrent_generator, history_keys, ktuple, sequential_generator,
    subhistory, tuple_key, tuple_value,
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
