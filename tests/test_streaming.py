"""Streaming (online) verification: equivalence with the offline
checkers, chunked carry-resume identity, journal tail-follow, early
abort, and the end-to-end --online path.

The contract under test (checker/streaming.py): the online pipeline's
verdict on a history equals the offline verdict on the same history —
for both kernel families — because the incremental encoder emits a
byte-identical step stream and the chunked carry walk decides exactly
what the one-shot walk decides.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from jepsen_tpu import models, store
from jepsen_tpu.checker import streaming, synth, wgl
from jepsen_tpu.history import history


MODEL = models.cas_register()
DM = wgl.DEVICE_MODELS[MODEL.device_model]

# One sort shape (F=256, P=8, E=128) and one dense shape shared across
# the pipeline tests below, so tier-1 pays each kernel compile once.
CHUNK = 128
SLOTS = 8


def _valid_hist(n=400, conc=4, seed=7, crash_rate=0.0):
    return synth.register_history(n, concurrency=conc, values=5,
                                  crash_rate=crash_rate, seed=seed)


def _feed_all(s, hist):
    for op in hist.ops:
        s.feed(op)
    return s


# -- encoder identity -------------------------------------------------------

def test_encoder_stream_is_byte_identical_to_build_steps():
    h = synth.register_history(800, concurrency=5, values=5,
                               crash_rate=0.02, seed=7)
    ops = wgl.encode_ops_for_model(MODEL, h)
    p = wgl._bucket(wgl.required_slots(ops), lo=8)
    off = wgl.build_steps(ops, p)

    enc = streaming.StreamEncoder(DM.codec, DM.droppable, p)
    for op in h.ops:
        if isinstance(op.get("process"), int):
            enc.feed(op)
    enc.finish()
    rows = enc.take(10 ** 9)
    x = np.asarray(rows, np.int32)
    assert x.shape == off.x.shape
    assert (x == off.x).all()
    assert enc.steps_emitted == off.n


def test_encoder_resolves_crash_tail_like_encode_ops():
    # chop the final completions: the open tail must encode as
    # pending-forever :info rows, exactly as encode_ops does
    h = _valid_hist(300, seed=11)
    cut = [o for o in h.ops][:-7]
    h2 = history(cut)
    ops = wgl.encode_ops_for_model(MODEL, h2)
    p = wgl._bucket(wgl.required_slots(ops), lo=8)
    off = wgl.build_steps(ops, p)
    enc = streaming.StreamEncoder(DM.codec, DM.droppable, p)
    for op in h2.ops:
        if isinstance(op.get("process"), int):
            enc.feed(op)
    enc.finish()
    rows = enc.take(10 ** 9)
    assert (np.asarray(rows, np.int32) == off.x).all()


# -- chunked carry-resume: byte-identical verdict/config-counts -------------

def _one_crashed_write_hist():
    """Tiny history with a crashed (pending-forever) write so a chunk
    split can land strictly inside its pending window."""
    ops = []
    t = [0]

    def emit(o):
        o["time"] = t[0]
        t[0] += 1
        ops.append(o)

    emit({"type": "invoke", "f": "write", "value": 1, "process": 0})
    emit({"type": "ok", "f": "write", "value": 1, "process": 0})
    # the crashed write: invoked here, never completes
    emit({"type": "invoke", "f": "write", "value": 3, "process": 1})
    emit({"type": "info", "f": "write", "value": 3, "process": 1})
    for i in range(12):
        p = 2 + (i % 2)
        emit({"type": "invoke", "f": "read", "value": None, "process": p})
        # the crashed write of 3 legally linearizes between reads 5/6
        emit({"type": "ok", "f": "read", "value": 1 if i < 6 else 3,
              "process": p})
    return history(ops)


def _summaries_equal(a, b):
    for x, y in zip(a, b):
        assert np.asarray(x).tolist() == np.asarray(y).tolist()


@pytest.mark.parametrize("family", ["sort", "dense"])
def test_chunk_resume_byte_identical(family):
    import jax.numpy as jnp

    h = _one_crashed_write_hist()
    ops = wgl.encode_ops_for_model(MODEL, h)
    p = 4
    steps = wgl.build_steps(ops, p)
    E = 64
    padded = steps.pad_to(E)
    if family == "dense":
        k = wgl._dense_kernel("cas-register", -1, 8, p, E)
    else:
        k = wgl._kernel("cas-register", 64, p, E, None)
    x = jnp.asarray(padded.x)
    s0 = jnp.int32(MODEL.device_state())
    import jax
    one_shot = jax.device_get(k.check(x, jnp.int32(steps.n), s0))

    def pad_chunk(rows):
        buf = np.zeros((E, padded.x.shape[1]), np.int32)
        buf[:, steps.w] = -1
        buf[:, steps.w + 2:] = -1
        buf[:len(rows)] = rows
        return jnp.asarray(buf)

    # every split point — including splits that land while the crashed
    # write is pending (it pends from step 1 to the very end)
    for split in range(steps.n + 1):
        carry = k.init_carry(s0)
        carry = k.check_stream_chunk(pad_chunk(padded.x[:split]),
                                     jnp.int32(split), carry)
        carry = k.check_stream_chunk(
            pad_chunk(padded.x[split:steps.n]),
            jnp.int32(steps.n - split), carry)
        _summaries_equal(jax.device_get(k.summarize(carry)), one_shot)


# -- online pipeline == offline verdicts ------------------------------------

def test_stream_valid_matches_offline_sort():
    h = _valid_hist()
    r = streaming.stream_check(MODEL, h, chunk_entries=CHUNK,
                               slots=SLOTS)
    a = wgl.analysis_tpu(MODEL, h)
    assert r["valid?"] is True and a["valid?"] is True
    assert r["analyzer"] == "tpu-wgl-streaming"
    assert r["chunks"] >= 2
    assert r["op-count"] == a["op-count"]


def test_stream_invalid_matches_offline_sort_and_names_culprit():
    h = synth.corrupt(_valid_hist(), seed=3)
    r = streaming.stream_check(MODEL, h, chunk_entries=CHUNK,
                               slots=SLOTS)
    a = wgl.analysis_tpu(MODEL, h)
    assert r["valid?"] is False and a["valid?"] is False
    assert r.get("op-index") == a.get("op-index")
    assert r["op"]["value"] == 10 ** 6


def test_stream_valid_matches_offline_dense():
    h = _valid_hist(seed=13)
    r = streaming.stream_check(MODEL, h, chunk_entries=CHUNK,
                               slots=SLOTS, engine="dense",
                               state_range=(-1, 4))
    a = wgl.analysis_tpu(MODEL, h)
    assert r["valid?"] is True and a["valid?"] is True
    assert r["analyzer"] == "tpu-wgl-dense-streaming"


def test_stream_dense_invalid_in_range_matches_offline():
    # an in-range stale read: the dense table must catch it without
    # any range escape
    h = _valid_hist(seed=17)
    bad = None
    for i, o in enumerate(h.ops):
        if o["type"] == "ok" and o["f"] == "read" \
                and o.get("value") is not None and i > 50:
            ops2 = [dict(x) for x in h.ops]
            ops2[i]["value"] = (ops2[i]["value"] + 2) % 5
            cand = history(ops2)
            if wgl.analysis_tpu(MODEL, cand)["valid?"] is False:
                bad = cand
                break
    assert bad is not None, "could not build an in-range violation"
    r = streaming.stream_check(MODEL, bad, chunk_entries=CHUNK,
                               slots=SLOTS, engine="dense",
                               state_range=(-1, 4))
    assert r["valid?"] is False
    assert r["analyzer"] == "tpu-wgl-dense-streaming"


def test_stream_dense_range_escape_falls_back_to_sort():
    # corrupt() writes a read of 10**6 — far outside the declared
    # range; the stream must rebuild onto the sort kernel, not return
    # an unsound dense verdict
    h = synth.corrupt(_valid_hist(seed=19), seed=5)
    r = streaming.stream_check(MODEL, h, chunk_entries=CHUNK,
                               slots=SLOTS, engine="dense",
                               state_range=(-1, 4))
    a = wgl.analysis_tpu(MODEL, h)
    assert r["valid?"] is False and a["valid?"] is False
    assert r["analyzer"] == "tpu-wgl-streaming"   # downgraded


def test_stream_crash_tail_matches_offline():
    h = history([o for o in _valid_hist(seed=23).ops][:-9])
    r = streaming.stream_check(MODEL, h, chunk_entries=CHUNK,
                               slots=SLOTS)
    a = wgl.analysis_tpu(MODEL, h)
    assert r["valid?"] == a["valid?"] is True


def test_stream_slot_overflow_rebuilds_and_agrees():
    h = _valid_hist(n=300, conc=12, seed=29)
    s = streaming.WglStream(MODEL, chunk_entries=CHUNK, slots=8)
    _feed_all(s, h)
    assert s.p > 8          # the rebuild happened
    r = s.finish()
    a = wgl.analysis_tpu(MODEL, h)
    assert r["valid?"] == a["valid?"] is True


def test_stream_early_abort_detects_mid_feed():
    h = _valid_hist(n=1200, conc=4, seed=31)
    # plant the violation at ~25% so chunks keep flowing afterwards
    ops = [dict(o) for o in h.ops]
    for i, o in enumerate(ops):
        if i > len(ops) // 4 and o["type"] == "ok" \
                and o["f"] == "read":
            o["value"] = 10 ** 6
            break
    bad = history(ops)
    s = streaming.WglStream(MODEL, chunk_entries=CHUNK, slots=SLOTS)
    fed = 0
    for op in bad.ops:
        s.feed(op)
        fed += 1
        if s.violation:
            break
    assert s.violation and fed < len(bad.ops)
    r = s.finish()
    assert r["valid?"] is False
    assert r["violation-at-op"] == s.violation_at_op <= fed


# -- streaming elle (wr) ----------------------------------------------------

def _wr_ok(process, txn, t):
    return [{"type": "invoke", "f": "txn", "value": txn,
             "process": process, "time": t},
            {"type": "ok", "f": "txn", "value": txn,
             "process": process, "time": t + 1}]


def _wr_fail(process, txn, t):
    return [{"type": "invoke", "f": "txn", "value": txn,
             "process": process, "time": t},
            {"type": "fail", "f": "txn", "value": txn,
             "process": process, "time": t + 1}]


def _wr_parity(h):
    from jepsen_tpu.checker.elle import wr
    s = streaming.WrStream()
    for op in h.ops:
        s.feed(op)
    r = s.finish()
    a = wr.check(h)
    assert r["valid?"] == a["valid?"]
    assert r["anomaly-types"] == a["anomaly-types"]
    assert r["txn-count"] == a["txn-count"]
    return r


def test_wr_stream_parity_on_workload_history():
    _wr_parity(synth.wr_history(600, seed=45100))


def test_wr_stream_parity_fixtures():
    # G1c cycle
    _wr_parity(history(
        _wr_ok(0, [["w", "x", 1], ["r", "y", 1]], 0)
        + _wr_ok(1, [["w", "y", 1], ["r", "x", 1]], 2)))
    # G-single via a nil read
    _wr_parity(history(
        _wr_ok(0, [["w", "x", 1], ["w", "y", 1]], 0)
        + _wr_ok(1, [["r", "y", 1], ["r", "x", None]], 2)))
    # internal + G1b
    _wr_parity(history(
        _wr_ok(0, [["w", "x", 1], ["w", "x", 2]], 0)
        + _wr_ok(1, [["r", "x", 1]], 2)))


def test_wr_stream_late_arrivals_resolve():
    # the read lands BEFORE its writer completes, and a failed write is
    # read before the :fail arrives — both must resolve through the
    # pending indexes
    g1a_late = history(
        _wr_ok(1, [["r", "x", 9]], 0)
        + _wr_fail(0, [["w", "x", 9]], 2))
    r = _wr_parity(g1a_late)
    assert "G1a" in r["anomaly-types"]

    wr_late = history(
        _wr_ok(1, [["r", "x", 1], ["w", "y", 1]], 0)
        + _wr_ok(0, [["w", "x", 1], ["r", "y", 1]], 2))
    r2 = _wr_parity(wr_late)
    assert r2["valid?"] is False


# -- streamed-result reuse guards -------------------------------------------

def test_streamed_reuse_guards():
    from jepsen_tpu.checker.elle import RWRegisterChecker
    from jepsen_tpu.checker.linear import Linearizable

    h = history(_wr_ok(0, [["w", "x", 1]], 0)
                + _wr_ok(1, [["r", "x", 1]], 2))
    s = streaming.WrStream()
    for op in h.ops:
        s.feed(op)
    r = s.finish()
    test = {"streamed-results": {"elle-wr": r}}
    # same question: reused verbatim
    plain = RWRegisterChecker()
    assert plain.check(test, h, {}) == dict(r)
    # a sibling with additional graphs must NOT adopt the plain result
    rt = RWRegisterChecker(additional_graphs=("realtime",))
    assert "streamed" not in rt.check(test, h, {})
    # ... nor one asking about different anomalies
    narrow = RWRegisterChecker(anomalies=("G1a",))
    assert "streamed" not in narrow.check(test, h, {})

    # Linearizable: a different model never adopts another's verdict
    hr = _valid_hist(n=40, conc=2, seed=37)
    lr = {"valid?": True, "streamed": True, "model": repr(MODEL),
          "history-len": len(hr.client_ops())}
    ltest = {"streamed-results": {"linear": lr}}
    same = Linearizable(MODEL, "host")
    other = Linearizable(models.cas_register(0), "host")
    assert same.check(ltest, hr, {}).get("streamed") is True
    assert other.check(ltest, hr, {}).get("streamed") is None


def test_dense_caps_raise_at_construction():
    with pytest.raises(ValueError):
        streaming.WglStream(MODEL, engine="dense",
                            state_range=(-1, 4), slots=32)
    # 'auto' downgrades to the sort engine instead of declining the
    # whole online pipeline (a state-range hint at high concurrency
    # must not cost the user streaming altogether)
    s = streaming.WglStream(MODEL, engine="auto",
                            state_range=(-1, 4), slots=32)
    assert s.engine == "sort"


# -- journal subscribe / tail-follow ----------------------------------------

def test_journal_subscribe_feeds_ops_and_drops_broken(tmp_path):
    j = store.Journal(str(tmp_path / "journal.jsonl"))
    seen = []
    unsub = j.subscribe(seen.append)

    def broken(op):
        raise RuntimeError("boom")
    j.subscribe(broken)
    j.append({"type": "invoke", "f": "w", "process": 0})
    j.append({"type": "ok", "f": "w", "process": 0})
    j.close()
    assert len(seen) == 2
    unsub()
    assert j._subs == []    # the broken one was dropped too


def test_journal_tail_buffers_torn_line(tmp_path):
    p = str(tmp_path / "journal.jsonl")
    tail = store.JournalTail(p)
    assert tail.poll() == []          # not created yet
    with open(p, "w") as fh:
        fh.write(json.dumps({"i": 1}) + "\n")
        fh.write('{"i": 2, "val')     # torn mid-write
        fh.flush()
        assert tail.poll() == [{"i": 1}]
        assert tail.poll() == []      # torn tail stays buffered
        fh.write('ue": "x"}\n')       # the rest lands
        fh.flush()
        assert tail.poll() == [{"i": 2, "value": "x"}]
    with open(p, "a") as fh:
        fh.write("{corrupt}\n")
    with pytest.raises(ValueError):
        tail.poll()


# -- end-to-end: core.run --online ------------------------------------------

def _atom_test(tmp_path, n=400, **kw):
    import random

    from jepsen_tpu import generator as gen, testkit
    from jepsen_tpu.checker import linearizable

    state = testkit.AtomState()
    rng = random.Random(45100)
    t = testkit.noop_test()
    t["ssh"] = {"dummy": True}
    t["store-dir"] = str(tmp_path / "store")
    t.update({
        "name": "online smoke",
        "db": testkit.atom_db(state),
        "client": testkit.atom_client(state, latency_s=0.0),
        "concurrency": 5,
        # AtomDB.setup zeroes the cell, so the model starts at 0
        "checker": linearizable(models.cas_register(0)),
        "online": True,
        "online-chunk-entries": CHUNK,
        "generator": gen.clients(gen.limit(n, gen.mix([
            lambda: {"f": "read"},
            lambda: {"f": "write", "value": rng.randint(0, 4)},
            lambda: {"f": "cas", "value": [rng.randint(0, 4),
                                           rng.randint(0, 4)]},
        ]))),
    })
    t.update(kw)
    return t


def test_core_run_online_streams_and_reuses_result(tmp_path):
    from jepsen_tpu import core

    t = core.run(_atom_test(tmp_path))
    sr = t["streamed-results"]["linear"]
    assert sr["valid?"] is True
    assert sr["streamed"] is True
    # analyze() reused the streamed verdict instead of re-checking
    assert t["results"]["valid?"] is True
    assert t["results"].get("streamed") is True
    assert t["results"]["analyzer"].startswith("tpu-wgl")
    # ... and the journal fed the stream (a journal existed: named test)
    assert (tmp_path / "store").exists()


from jepsen_tpu import client as jclient  # noqa: E402


class _LyingClient(jclient.Client):
    """Returns impossible reads after a warm-up — the violation the
    online checker must catch mid-run."""

    def __init__(self, state, after):
        from jepsen_tpu import testkit
        self.inner = testkit.atom_client(state, latency_s=0.0005)
        self.after = after
        self.count = [0]

    def open(self, test, node):
        c = _LyingClient.__new__(_LyingClient)
        c.inner = self.inner.open(test, node)
        c.after = self.after
        c.count = self.count
        return c

    def setup(self, test):
        self.inner.setup(test)

    def invoke(self, test, op):
        out = self.inner.invoke(test, op)
        self.count[0] += 1
        if self.count[0] > self.after and op["f"] == "read" \
                and out["type"] == "ok":
            out = dict(out)
            out["value"] = 10 ** 6
        return out

    def teardown(self, test):
        self.inner.teardown(test)

    def close(self, test):
        self.inner.close(test)


def test_core_run_abort_on_violation(tmp_path):
    from jepsen_tpu import core, testkit

    # pre-warm the exact kernel shape the online checker will use, so
    # the abort races the (fast) run with a hot compile cache
    streaming.stream_check(MODEL, _valid_hist(n=60, conc=4, seed=3),
                           chunk_entries=CHUNK, slots=16)
    state = testkit.AtomState()
    n = 20000
    t = _atom_test(tmp_path, n=n, name="abort on violation",
                   client=_LyingClient(state, after=150),
                   db=testkit.atom_db(state))
    t["abort-on-violation"] = True
    done = core.run(t)
    assert done.get("aborted-on-violation") is True
    assert len(done["history"]) < 2 * n   # the run stopped early
    assert done["results"]["valid?"] is False


# -- CLI: --online / --abort-on-violation / compile cache -------------------

def test_cli_online_end_to_end(tmp_path, monkeypatch):
    from jepsen_tpu import cli

    cache_dir = str(tmp_path / "jax-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)

    def test_fn(options):
        t = _atom_test(tmp_path, n=120)
        t["name"] = "cli online"
        t["store-dir"] = options["store-dir"]
        # the CLI flags must have reached the test map
        assert options["online"] is True
        assert options["abort-on-violation"] is True
        t["online"] = options["online"]
        t["abort-on-violation"] = options["abort-on-violation"]
        return t

    cmds = cli.single_test_cmd({"test_fn": test_fn})
    with pytest.raises(SystemExit) as e:
        cli.run(cmds, ["test", "--no-ssh", "--online",
                       "--abort-on-violation",
                       "--store-dir", str(tmp_path / "store")])
    assert e.value.code == 0
    # the CLI applied the persistent compilation cache, in the
    # environment's directory and no other
    import jax
    assert jax.config.jax_compilation_cache_dir == cache_dir
    stored = store.load_test(str(tmp_path / "store" / "latest"))
    assert stored["results"]["valid?"] is True
    assert stored["results"].get("streamed") is True
