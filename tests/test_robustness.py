"""Run survivability (ISSUE 2): op deadlines + wedged-worker
containment, the write-ahead op journal, crash salvage, and bounded
teardown.

Jepsen's value is the history: faults are injected on purpose, so the
harness must survive hung clients and crashed runs without losing the
data it was built to collect. These tests wedge and kill runs on
purpose and assert the history survives.
"""

import json
import os
import random
import threading
import time

import pytest

from jepsen_tpu import checker as jchecker
from jepsen_tpu import client as jclient
from jepsen_tpu import core
from jepsen_tpu import generator as gen
from jepsen_tpu import models, store, testkit
from jepsen_tpu.checker.linear import analysis_host
from jepsen_tpu.generator import interpreter
from jepsen_tpu.history import History
from jepsen_tpu.util import relative_time


class HangingClient(jclient.Client):
    """Wedges forever (well: 30 s, so a broken containment path fails
    the test instead of hanging the suite) on its first invoke; later
    invokes answer ok. Late answers carry 'late' so leakage into the
    history is detectable."""

    def __init__(self, hang_first_n: int = 1, latency_s: float = 0.0):
        self.release = threading.Event()
        self.hang_first_n = hang_first_n
        self.latency_s = latency_s
        self.n = 0
        self.lock = threading.Lock()

    def open(self, test, node):
        return self

    def invoke(self, test, op):
        with self.lock:
            self.n += 1
            hang = self.n <= self.hang_first_n
        if hang:
            self.release.wait(30)
            return {**op, "type": "ok", "late": True}
        if self.latency_s:
            time.sleep(self.latency_s)
        return {**op, "type": "ok"}


def hang_test(tmp_path, client, **kw):
    t = testkit.noop_test()
    t.update({
        "store-dir": str(tmp_path / "store"),
        "start-time": store.start_time(),
        "client": client,
    })
    t.update(kw)
    return t


# -- op deadlines + wedged-worker containment -------------------------------

def test_hung_invoke_times_out_journals_info_and_retires_process(tmp_path):
    """Acceptance: a run whose client hangs forever terminates within
    op-timeout + grace, with the hung op journaled as :info and the
    wedged process retired and replaced."""
    client = HangingClient()
    t = hang_test(
        tmp_path, client,
        concurrency=1,
        generator=gen.clients(gen.limit(6, gen.repeat({"f": "read"}))),
    )
    t["op-timeout"] = 0.2
    t0 = time.monotonic()
    with relative_time():
        hist = interpreter.run(t)
    elapsed = time.monotonic() - t0
    try:
        # terminated within op-timeout + grace, nowhere near the 30 s
        # the client would have held its worker
        assert elapsed < 5
        infos = [o for o in hist if o["type"] == "info"]
        assert len(infos) == 1
        assert infos[0]["error"] == ["op-timeout", 0.2]
        # the wedged process was retired: later ops run as process 1
        procs = {o["process"] for o in hist}
        assert procs == {0, 1}
        # the run still consumed every generated op on the replacement
        assert len([o for o in hist if o["type"] == "invoke"]) == 6
        assert len(hist) == 12
        # the synthetic :info is in the journal (flushed immediately)
        j = store.load_journal(t)
        assert [o["error"] for o in j if o["type"] == "info"] == \
            [["op-timeout", 0.2]]
        assert len(j) == len(hist)
    finally:
        client.release.set()


def test_late_completion_from_abandoned_worker_is_discarded(tmp_path):
    """The abandoned worker eventually answers; its late result must be
    discarded, not double-completed into the history."""
    client = HangingClient(latency_s=0.1)
    t = hang_test(
        tmp_path, client,
        concurrency=1,
        generator=gen.clients(gen.limit(4, gen.repeat({"f": "read"}))),
    )
    t["op-timeout"] = 0.15
    with relative_time():
        # release the wedged worker mid-run (the replacement is still
        # working through ops 2-4), so its late 'ok' races the rest of
        # the run through the completions queue
        threading.Timer(0.25, client.release.set).start()
        hist = interpreter.run(t)
    assert not any(o.get("late") for o in hist), \
        "late completion from a retired worker leaked into the history"
    h = History(hist)
    # well-formed: the timed-out invoke pairs with its synthetic :info
    assert len(h.pending()) == 0
    assert len([o for o in hist if o["type"] == "info"]) == 1


def test_per_op_deadline_overrides_test_level_timeout(tmp_path):
    client = HangingClient()
    t = hang_test(
        tmp_path, client,
        concurrency=1,
        generator=gen.clients(gen.limit(
            2, gen.repeat({"f": "read", "deadline": 0.15}))),
    )
    # test-level bound is enormous; the per-op deadline must win
    t["op-timeout"] = 3600
    t0 = time.monotonic()
    with relative_time():
        hist = interpreter.run(t)
    elapsed = time.monotonic() - t0
    client.release.set()
    assert elapsed < 5
    infos = [o for o in hist if o["type"] == "info"]
    assert len(infos) == 1
    assert infos[0]["error"] == ["op-timeout", 0.15]


def test_hung_nemesis_is_retired_without_concurrent_invoke(tmp_path):
    """A wedged nemesis invoke times out like a client's, but the single
    shared nemesis object must never be invoked concurrently: later
    nemesis ops complete as :info without touching it."""
    from jepsen_tpu import nemesis as jnemesis

    invokes = []
    release = threading.Event()

    class WedgingNemesis(jnemesis.Nemesis):
        def setup(self, test):
            return self

        def invoke(self, test, op):
            invokes.append(op["f"])
            if op["f"] == "start":
                release.wait(30)
            return dict(op)

    t = hang_test(
        tmp_path, testkit.atom_client(testkit.AtomState(0), latency_s=0),
        concurrency=2,
        nemesis=WedgingNemesis(),
        generator=gen.phases(
            gen.nemesis(gen.once({"type": "info", "f": "start"})),
            gen.clients(gen.limit(4, gen.repeat({"f": "read"}))),
            gen.nemesis(gen.once({"type": "info", "f": "stop"})),
        ),
    )
    t["op-timeout"] = 0.2
    t0 = time.monotonic()
    with relative_time():
        hist = interpreter.run(t)
    elapsed = time.monotonic() - t0
    release.set()
    assert elapsed < 5
    # the real nemesis saw only the wedged op — never a concurrent one
    assert invokes == ["start"]
    nem = [o for o in hist if o["process"] == "nemesis"
           and o["type"] == "info" and o.get("error")]
    errors = [o["error"] for o in nem]
    assert ["op-timeout", 0.2] in errors
    assert any(isinstance(e, str) and e.startswith("nemesis-retired")
               for e in errors)
    # client ops were unaffected
    assert len([o for o in hist if o["f"] == "read"
                and o["type"] == "ok"]) == 4


def test_run_without_op_timeout_is_unchanged(tmp_path):
    """No op-timeout configured: ordinary runs behave exactly as
    before (no deadlines, no journal-induced history changes)."""
    state = testkit.AtomState(0)
    t = hang_test(
        tmp_path, testkit.atom_client(state, latency_s=0.0),
        concurrency=3,
        generator=gen.clients(gen.limit(30, gen.repeat({"f": "read"}))),
    )
    with relative_time():
        hist = interpreter.run(t)
    assert len(hist) == 60
    assert all(o["type"] in ("invoke", "ok") for o in hist)


# -- write-ahead journal + crash salvage ------------------------------------

def cas_mix(r):
    def g():
        w = r.random()
        if w < 0.5:
            return {"f": "read"}
        if w < 0.8:
            return {"f": "write", "value": r.randrange(5)}
        return {"f": "cas", "value": [r.randrange(5), r.randrange(5)]}
    return g


def test_crash_salvage_round_trip(tmp_path):
    """Acceptance: a run killed mid-history leaves a journal.jsonl from
    which the partial history is recovered and checked — here via a
    generator that explodes when the nemesis phase starts."""
    base = str(tmp_path / "store")
    state = testkit.AtomState(0)
    r = random.Random(45100)

    def boom():
        raise RuntimeError("nemesis exploded")

    t = testkit.noop_test()
    t.update({
        "name": "salvage",
        "store-dir": base,
        "ssh": {"dummy": True},
        "concurrency": 3,
        "db": testkit.atom_db(state),
        "client": testkit.atom_client(state, latency_s=0.0),
        "generator": gen.phases(
            gen.clients(gen.limit(40, cas_mix(r))),
            gen.nemesis(boom)),
    })
    with pytest.raises(gen.GenException):
        core.run(t)

    d = store.latest(base)
    assert d is not None, "a crashed run must still be `latest`"

    # the WAL survived the crash and replays
    j = store.read_journal(os.path.join(d, "journal.jsonl"))
    assert len(j) == 80  # 40 invokes + 40 completions

    # load_journal over the same run via its test identity
    t2 = {"name": "salvage", "store-dir": base,
          "start-time": os.path.basename(d)}
    j2 = store.load_journal(t2)
    assert list(j2) == list(j)

    # core.run's abort path salvaged history.jsonl.gz from the journal
    loaded = store.load_test(d)
    assert [o["f"] for o in loaded["history"]] == [o["f"] for o in j]

    # ...and the partial history is checkable
    a = analysis_host(models.cas_register(0), loaded["history"])
    assert a["valid?"] is True
    res = jchecker.check_safe(jchecker.stats(), loaded, loaded["history"])
    assert res.get("valid?") is not None


def test_torn_final_journal_line_is_tolerated(tmp_path):
    p = str(tmp_path / "journal.jsonl")
    ops = [{"type": "invoke", "f": "read", "value": None,
            "process": 0, "time": 1},
           {"type": "ok", "f": "read", "value": 3,
            "process": 0, "time": 2}]
    with open(p, "w") as fh:
        for o in ops:
            fh.write(json.dumps(o) + "\n")
        fh.write('{"type": "invoke", "f": "wri')  # SIGKILL mid-write
    j = store.read_journal(p)
    assert len(j) == 2
    assert [o["f"] for o in j] == ["read", "read"]
    # an interrupted *final newline* is also fine
    with open(p, "w") as fh:
        fh.write(json.dumps(ops[0]) + "\n" + json.dumps(ops[1]))
    assert len(store.read_journal(p)) == 2


def test_mid_file_journal_corruption_raises(tmp_path):
    """Only a torn *final* line is a crash artifact; garbage earlier in
    the journal is real damage and must not be silently dropped."""
    p = str(tmp_path / "journal.jsonl")
    with open(p, "w") as fh:
        fh.write('{"type": "invoke", "f": "read"}\n')
        fh.write("garbage{{{\n")
        fh.write('{"type": "ok", "f": "read"}\n')
    with pytest.raises(ValueError, match="not the final line"):
        store.read_journal(p)


def test_load_test_salvages_from_journal_without_test_json(tmp_path):
    """A SIGKILL'd run can die before save_1 ever writes test.json; the
    analyze path reconstructs identity from the dir layout and replays
    the journal."""
    d = tmp_path / "store" / "mytest" / "20260803T000000.000000"
    os.makedirs(d)
    ops = [{"type": "invoke", "f": "read", "value": None,
            "process": 0, "time": 1},
           {"type": "ok", "f": "read", "value": 0,
            "process": 0, "time": 2},
           {"type": "invoke", "f": "write", "value": 1,
            "process": 1, "time": 3}]
    with open(d / "journal.jsonl", "w") as fh:
        for o in ops:
            fh.write(json.dumps(o) + "\n")
    loaded = store.load_test(str(d))
    assert loaded["name"] == "mytest"
    assert loaded["start-time"] == "20260803T000000.000000"
    assert loaded["salvaged-from-journal"] is True
    h = loaded["history"]
    assert [o["f"] for o in h] == ["read", "read", "write"]
    assert [o["index"] for o in h] == [0, 1, 2]  # indexed for checkers
    assert [o["f"] for o in h.pending()] == ["write"]


@pytest.mark.parametrize("n", [0, 1, 2 * 4096 + 3])
def test_history_file_is_one_json_line_per_op(tmp_path, n):
    """history.jsonl.gz holds exactly the lines json.dumps gives each op
    (values JSON lacks converted as everywhere in the store), and reads
    back as the same ops."""
    import gzip
    ops = [{"index": i, "type": "ok" if i % 2 else "invoke",
            "process": i % 7, "f": "cas", "value": (i % 5, i % 3),
            "error": {"set": {i}} if i % 11 == 0 else None}
           for i in range(n)]
    test = {"name": "h", "start-time": "t", "store-dir": str(tmp_path)}
    p = store.write_history(test, ops)
    with gzip.open(p, "rt") as fh:
        assert fh.read() == "".join(
            json.dumps(op, default=store._json_default) + "\n"
            for op in ops)
    back = store.read_history(p)
    assert [o["value"] for o in back] == [list(o["value"]) for o in ops]


def test_journal_flushes_info_ops_immediately(tmp_path):
    p = str(tmp_path / "j.jsonl")
    j = store.Journal(p, flush_interval_s=3600)
    j.append({"type": "invoke", "f": "read", "process": 0})
    # plain ops are buffered (flush interval far away)...
    with open(p) as fh:
        assert fh.read() == ""
    # ...but an :info op forces the buffer out: it's exactly the op a
    # post-mortem needs
    j.append({"type": "info", "f": "read", "process": 0,
              "error": "indeterminate"})
    with open(p) as fh:
        assert fh.read().count("\n") == 2
    j.close()


def test_journal_flushes_nemesis_ops_immediately(tmp_path):
    p = str(tmp_path / "j.jsonl")
    j = store.Journal(p, flush_interval_s=3600)
    j.append({"type": "invoke", "f": "start", "process": "nemesis"})
    with open(p) as fh:
        assert fh.read().count("\n") == 1
    j.close()
    # close() is idempotent and appends after close are ignored
    j.close()
    j.append({"type": "ok", "f": "start", "process": "nemesis"})
    with open(p) as fh:
        assert fh.read().count("\n") == 1


def test_interpreter_only_runs_do_not_journal(tmp_path, monkeypatch):
    """Without a prepared store identity (name + start-time) the
    interpreter must not litter ./store with journal files."""
    monkeypatch.chdir(tmp_path)
    t = testkit.noop_test()  # has a name but no start-time
    t.update({
        "concurrency": 2,
        "client": testkit.atom_client(testkit.AtomState(0)),
        "generator": gen.clients(gen.limit(4, gen.repeat({"f": "read"}))),
    })
    with relative_time():
        hist = interpreter.run(t)
    assert len(hist) == 8
    assert not os.path.exists(tmp_path / "store")


# -- bounded teardown -------------------------------------------------------

class HangingTeardownClient(jclient.Client):
    """invoke works; teardown wedges (a dead node's socket)."""

    def __init__(self, log):
        self.log = log

    def open(self, test, node):
        return self

    def invoke(self, test, op):
        return {**op, "type": "ok"}

    def teardown(self, test):
        self.log.append("teardown-start")
        threading.Event().wait(30)

    def close(self, test):
        self.log.append("close")


def test_hung_client_teardown_does_not_hang_the_run(tmp_path):
    log = []
    t = testkit.noop_test()
    t.update({
        "name": "hung teardown",
        "store-dir": str(tmp_path / "store"),
        "ssh": {"dummy": True},
        "concurrency": 2,
        "teardown-timeout": 0.3,
        "client": HangingTeardownClient(log),
        "generator": gen.clients(gen.limit(4, gen.repeat({"f": "read"}))),
    })
    t0 = time.monotonic()
    done = core.run(t)
    elapsed = time.monotonic() - t0
    assert elapsed < 15, "hung teardown must be abandoned, not awaited"
    assert done["results"]["valid?"] is True
    # teardown was attempted on every node, then abandoned; close still
    # ran — once per node-client plus once per interpreter worker client
    nn = len(t["nodes"])
    assert log.count("teardown-start") == nn
    assert log.count("close") == nn + t["concurrency"]
