"""Elle-class cycle checker tests: kernels, list-append, rw-register —
golden histories in, verdicts out (the reference's checker test style)."""

import numpy as np

import jepsen_tpu.generator as gen
from jepsen_tpu.checker import elle
from jepsen_tpu.checker.elle import kernels, list_append, wr
from jepsen_tpu.generator import simulate as sim
from jepsen_tpu.history import history


# -- kernels ----------------------------------------------------------------

def test_transitive_closure():
    a = np.zeros((3, 3), bool)
    a[0, 1] = a[1, 2] = True
    c = kernels.transitive_closure(a)
    assert c[0, 2] and c[0, 1] and c[1, 2]
    assert not c[2, 0] and not c.diagonal().any()


def test_transitive_closure_sharded():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]), ("rows",))
    a = np.zeros((10, 10), bool)
    for i in range(9):
        a[i, i + 1] = True
    c = kernels.transitive_closure(a, mesh=mesh)
    assert c[0, 9]
    assert not c.diagonal().any()


def test_analyze_graph_g0():
    n = 2
    ww = np.zeros((n, n), bool)
    ww[0, 1] = ww[1, 0] = True
    r = kernels.analyze_graph(ww, np.zeros_like(ww), np.zeros_like(ww))
    assert r["G0"] and r["G1c"]
    assert not r["G2-item"]


def test_analyze_graph_g_single():
    n = 2
    ww = np.zeros((n, n), bool)
    wr_m = np.zeros((n, n), bool)
    rw = np.zeros((n, n), bool)
    wr_m[0, 1] = True
    rw[1, 0] = True
    r = kernels.analyze_graph(ww, wr_m, rw)
    assert not r["G0"] and not r["G1c"]
    assert r["G-single"] and not r["G2-item"]


def test_analyze_graph_g2():
    # two rw edges forming the only cycle
    n = 2
    rw = np.zeros((n, n), bool)
    rw[0, 1] = rw[1, 0] = True
    r = kernels.analyze_graph(np.zeros_like(rw), np.zeros_like(rw), rw)
    assert not r["G1c"] and not r["G-single"]
    assert r["G2-item"]


def test_analyze_graph_acyclic():
    n = 3
    ww = np.zeros((n, n), bool)
    ww[0, 1] = ww[1, 2] = True
    r = kernels.analyze_graph(ww, np.zeros_like(ww), np.zeros_like(ww))
    assert not any(r[t] for t in ("G0", "G1c", "G-single", "G2-item"))


# -- list append ------------------------------------------------------------

def _ok(process, txn, t):
    return [{"type": "invoke", "f": "txn", "value": txn, "process": process,
             "time": t},
            {"type": "ok", "f": "txn", "value": txn, "process": process,
             "time": t + 1}]


def _fail(process, txn, t):
    return [{"type": "invoke", "f": "txn", "value": txn, "process": process,
             "time": t},
            {"type": "fail", "f": "txn", "value": txn, "process": process,
             "time": t + 1}]


def test_append_valid_history():
    h = history(
        _ok(0, [["append", "x", 1]], 0)
        + _ok(1, [["r", "x", [1]], ["append", "x", 2]], 2)
        + _ok(0, [["r", "x", [1, 2]]], 4))
    res = list_append.check(h)
    assert res["valid?"] is True


def test_append_g1c_write_read_cycle():
    h = history(
        _ok(0, [["append", "x", 1], ["r", "y", [1]]], 0)
        + _ok(1, [["append", "y", 1], ["r", "x", [1]]], 2))
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "G1c" in res["anomaly-types"]
    cyc = res["anomalies"]["G1c"][0]["cycle"]
    assert cyc is not None and len(cyc) == 3  # T -> T' -> T


def test_append_g_single():
    h = history(
        _ok(0, [["append", "x", 1], ["append", "y", 1]], 0)
        + _ok(1, [["r", "x", [1]], ["r", "y", []]], 2))
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "G-single" in res["anomaly-types"]


def test_append_g0():
    h = history(
        _ok(0, [["append", "x", 1], ["append", "y", 2]], 0)
        + _ok(1, [["append", "x", 2], ["append", "y", 1]], 2)
        + _ok(2, [["r", "x", [1, 2]]], 4)
        + _ok(3, [["r", "y", [1, 2]]], 6))
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "G0" in res["anomaly-types"]


def test_append_g1a_aborted_read():
    h = history(
        _fail(0, [["append", "x", 1]], 0)
        + _ok(1, [["r", "x", [1]]], 2))
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "G1a" in res["anomaly-types"]


def test_append_g1b_intermediate_read():
    h = history(
        _ok(0, [["append", "x", 1], ["append", "x", 2]], 0)
        + _ok(1, [["r", "x", [1]]], 2))
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "G1b" in res["anomaly-types"]


def test_append_duplicates():
    h = history(
        _ok(0, [["append", "x", 1]], 0)
        + _ok(1, [["append", "x", 1]], 2))
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "duplicate-elements" in res["anomaly-types"]


def test_append_incompatible_order():
    h = history(
        _ok(0, [["r", "x", [1, 2]]], 0)
        + _ok(1, [["r", "x", [1, 3]]], 2))
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "incompatible-order" in res["anomaly-types"]


def test_append_internal():
    h = history(_ok(0, [["append", "x", 5], ["r", "x", []]], 0))
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "internal" in res["anomaly-types"]
    # and the consistent version is fine
    h2 = history(_ok(0, [["append", "x", 5], ["r", "x", [5]]], 0))
    assert list_append.check(h2)["valid?"] is True


def test_append_anomaly_selection():
    # a G-single history passes when only G1 is checked
    h = history(
        _ok(0, [["append", "x", 1], ["append", "y", 1]], 0)
        + _ok(1, [["r", "x", [1]], ["r", "y", []]], 2))
    res = list_append.check(h, anomalies=("G1a", "G1b", "G1c"))
    assert res["valid?"] is True


# -- rw register ------------------------------------------------------------

def test_wr_valid_history():
    h = history(
        _ok(0, [["w", "x", 1]], 0)
        + _ok(1, [["r", "x", 1]], 2)
        + _ok(0, [["w", "x", 2]], 4)
        + _ok(1, [["r", "x", 2]], 6))
    res = wr.check(h)
    assert res["valid?"] is True


def test_wr_g1c():
    h = history(
        _ok(0, [["w", "x", 1], ["r", "y", 1]], 0)
        + _ok(1, [["w", "y", 1], ["r", "x", 1]], 2))
    res = wr.check(h)
    assert res["valid?"] is False
    assert "G1c" in res["anomaly-types"]


def test_wr_g_single():
    h = history(
        _ok(0, [["w", "x", 1], ["w", "y", 1]], 0)
        + _ok(1, [["r", "y", 1], ["r", "x", None]], 2))
    res = wr.check(h)
    assert res["valid?"] is False
    assert "G-single" in res["anomaly-types"]


def test_wr_g1a_and_g1b():
    h = history(
        _fail(0, [["w", "x", 9]], 0)
        + _ok(1, [["r", "x", 9]], 2))
    res = wr.check(h)
    assert "G1a" in res["anomaly-types"]

    h2 = history(
        _ok(0, [["w", "x", 1], ["w", "x", 2]], 0)
        + _ok(1, [["r", "x", 1]], 2))
    res2 = wr.check(h2)
    assert "G1b" in res2["anomaly-types"]


def test_wr_internal():
    h = history(_ok(0, [["w", "x", 1], ["r", "x", 2]], 0))
    res = wr.check(h)
    assert "internal" in res["anomaly-types"]


def test_wr_ww_from_intra_txn_order():
    # T1 w x 1; T2 r x 1, w x 2 => ww T1->T2; T1 also reads T2's write:
    # cycle (G1c via ww+wr)
    h = history(
        _ok(0, [["w", "x", 1], ["r", "y", 2]], 0)
        + _ok(1, [["r", "x", 1], ["w", "x", 9], ["w", "y", 2]], 2))
    res = wr.check(h)
    assert res["valid?"] is False
    assert "G1c" in res["anomaly-types"]


# -- generators + workload bundles ------------------------------------------

def test_append_gen_traceable():
    with gen.fixed_rng(2):
        ops = sim.quick(sim.n_plus_nemesis_context(3),
                        gen.clients(gen.limit(40, elle.append_gen())))
    assert len(ops) == 40
    seen = set()
    for o in ops:
        assert o["f"] == "txn"
        for m in o["value"]:
            assert m[0] in ("append", "r")
            if m[0] == "append":
                assert (m[1], m[2]) not in seen  # unique per key
                seen.add((m[1], m[2]))


def _serial_store_executor(mode):
    """A simulate-completion fn applying txns serially to an in-memory
    store (invocation order = serialization order, so the history must
    verify)."""
    store = {}

    def complete(ctx, invoke):
        out = dict(invoke)
        txn = []
        for m in invoke["value"]:
            f, k, v = m
            if f == "append":
                store.setdefault(k, []).append(v)
                txn.append([f, k, v])
            elif f == "w":
                store[k] = v
                txn.append([f, k, v])
            else:  # read
                got = store.get(k, [] if mode == "append" else None)
                txn.append(["r", k, list(got) if mode == "append"
                            else got])
        out["type"] = "ok"
        out["value"] = txn
        out["time"] = invoke["time"] + 1
        return out

    return complete


def test_wr_workload_end_to_end():
    from jepsen_tpu.workloads import wr as ww
    bundle = ww.workload()
    with gen.fixed_rng(6):
        h = sim.simulate(sim.n_plus_nemesis_context(3),
                         gen.clients(gen.limit(30, bundle["generator"])),
                         _serial_store_executor("wr"))
    res = bundle["checker"].check({}, history(h), {})
    assert res["valid?"] is True
    assert res["txn-count"] == 30


def test_append_workload_end_to_end():
    from jepsen_tpu.workloads import append as aw
    bundle = aw.workload({"key-count": 3})
    with gen.fixed_rng(8):
        h = sim.simulate(sim.n_plus_nemesis_context(3),
                         gen.clients(gen.limit(30, bundle["generator"])),
                         _serial_store_executor("append"))
    res = bundle["checker"].check({}, history(h), {})
    assert res["valid?"] is True
    assert res["txn-count"] == 30


def test_append_unfilled_reads_carry_no_information():
    # echo-style histories (reads stay None) must not produce anomalies
    h = history(
        _ok(0, [["append", "x", 1], ["r", "y", None]], 0)
        + _ok(1, [["append", "y", 1], ["r", "x", None]], 2))
    assert list_append.check(h)["valid?"] is True


def test_expand_anomalies():
    assert elle.expand_anomalies(("G1",)) == ("G1a", "G1b", "G1c")
    assert elle.expand_anomalies(("G0", "G2")) == ("G0", "G-single",
                                                   "G2-item")


def test_g2_not_masked_by_unrelated_weaker_cycle():
    # a G1c cycle on a/b AND an independent pure write-skew (2 rw) on x/y;
    # a serializability-only config must still flag the G2 cycle
    h = history(
        _ok(0, [["w", "a", 1], ["r", "b", 1]], 0)
        + _ok(1, [["w", "b", 1], ["r", "a", 1]], 2)
        + _ok(2, [["w", "x", 1], ["r", "y", None]], 4)
        + _ok(3, [["w", "y", 1], ["r", "x", None]], 6))
    res = wr.check(h, anomalies=("G-single", "G2-item"))
    assert res["valid?"] is False
    assert "G2-item" in res["anomaly-types"]
    cert = res["anomalies"]["G2-item"][0]["cycle"]
    assert cert is not None


def test_elle_ignores_nemesis_ops():
    h = history(
        _ok(0, [["append", "x", 1]], 0)
        + [{"type": "info", "f": "start-partition", "value": ["n1", "n2"],
            "process": "nemesis", "time": 1}]
        + _ok(1, [["r", "x", [1]]], 2))
    res = list_append.check(h)
    assert res["valid?"] is True
    assert res["txn-count"] == 2  # nemesis op is not a transaction
    res2 = wr.check(history(
        _ok(0, [["w", "x", 1]], 0)
        + [{"type": "info", "f": "start", "value": [{"a": 1}],
            "process": "nemesis", "time": 1}]
        + _ok(1, [["r", "x", 1]], 2)))
    assert res2["valid?"] is True


# -- sparse SCC pipeline at scale --------------------------------------------

def test_scc_labels_matches_tarjan_fallback():
    rng = np.random.default_rng(3)
    n = 200
    m = 600
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    a = kernels.scc_labels(n, src, dst)
    b = kernels._tarjan_labels(n, src, dst)
    # identical partitions (label ids may differ): bijective label map
    fwd, bwd = {}, {}
    for x, y in zip(a.tolist(), b.tolist()):
        assert fwd.setdefault(x, y) == y
        assert bwd.setdefault(y, x) == x


def test_analyze_edges_valid_at_scale():
    from jepsen_tpu.checker import synth
    h = synth.append_history(5000)
    res = list_append.check(h)
    assert res["valid?"] is True
    assert res["txn-count"] == 5000


def test_analyze_edges_many_injected_sccs():
    from jepsen_tpu.checker import synth
    h = synth.inject_append_cycles(synth.append_history(500), 20, "G1c")
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "G1c" in res["anomaly-types"]
    cert = res["anomalies"]["G1c"][0]["cycle"]
    assert cert is not None and cert[0]["index"] == cert[-1]["index"]


def test_analyze_edges_g_single_injected():
    from jepsen_tpu.checker import synth
    h = synth.inject_append_cycles(synth.append_history(300), 5,
                                   "G-single")
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "G-single" in res["anomaly-types"]


def test_analyze_edges_sharded_mesh():
    import jax
    from jax.sharding import Mesh
    from jepsen_tpu.checker import synth

    mesh = Mesh(np.array(jax.devices()[:8]), ("keys",))
    h = synth.inject_append_cycles(synth.append_history(300), 11, "G1c")
    res = list_append.check(h, mesh=mesh)
    assert res["valid?"] is False
    assert "G1c" in res["anomaly-types"]
    # classified on the device, its staged stacks spread over the mesh
    assert res["classifier"] == "device"
    assert res["classifier-devices"] == 8


def test_classify_batches_host_parity(monkeypatch):
    # the JEPSEN_TPU_ELLE_HOST=1 host mirror (the reference
    # chip_smoke.py holds the device classifier to, and the final rung
    # after a second device fault) must agree flag-for-flag with the
    # device kernel on random SCC blocks — so make sure the "device"
    # side really takes the device path
    monkeypatch.delenv("JEPSEN_TPU_ELLE_HOST", raising=False)
    rng = np.random.default_rng(11)
    buckets = {}
    for e in (8, 16):
        b = 6
        mats = []
        for _ in range(3):
            m = (rng.random((b, e, e)) < 0.15).astype(np.float32)
            for s in range(b):
                np.fill_diagonal(m[s], 0.0)
            mats.append(m)
        buckets[e] = tuple(mats)
    dev = kernels._classify_batches(buckets)
    host = kernels._classify_batches_host(buckets)
    for e in buckets:
        for d, h in zip(dev[e], host[e]):
            assert (np.asarray(d) == np.asarray(h)).all()


def test_check_host_classify_env(monkeypatch):
    from jepsen_tpu.checker import synth
    monkeypatch.setenv("JEPSEN_TPU_ELLE_HOST", "1")
    h = synth.inject_append_cycles(synth.append_history(300), 7, "G1c")
    res = list_append.check(h)
    assert res["valid?"] is False and "G1c" in res["anomaly-types"]
    assert res["classifier"] == "host-mirror"


def test_analyze_edges_oversized_scc_host_path():
    # force the oversized path with a tiny max_dense: a 4-node G1c ring
    # plus a disjoint 2-node G0 ring
    edges = {(0, 1): {"ww"}, (1, 2): {"wr"}, (2, 3): {"ww"},
             (3, 0): {"wr"}, (4, 5): {"ww"}, (5, 4): {"ww"}}
    res = kernels.analyze_edges(6, edges, max_dense=3)
    assert res["oversized-sccs"] == 1  # the 4-ring
    assert res["G0"] and res["G1c"]
    assert not res["G-single"] and not res["G2-item"]


def test_analyze_edges_oversized_scc_with_outgoing_edges():
    # an oversized SCC with edges leaving the SCC must still classify
    # (regression: dst-outside-SCC edges crashed the host classifier)
    edges = {(0, 1): {"ww"}, (1, 2): {"ww"}, (2, 0): {"ww"},
             (2, 3): {"ww"}, (3, 4): {"wr"}}
    res = kernels.analyze_edges(5, edges, max_dense=2)
    assert res["G0"] and res["G1c"]


def test_analyze_edges_oversized_g2_not_masked_by_g1c():
    # one SCC containing BOTH a wr-cycle (G1c) and a 2-rw cycle (G2);
    # the oversized path must report both, independently
    edges = {(0, 1): {"wr"}, (1, 0): {"wr"},          # G1c ring
             (1, 2): {"rw"}, (2, 1): {"rw"}}          # 2-rw ring
    res = kernels.analyze_edges(3, edges, max_dense=2)
    assert res["G1c"] and res["G2-item"]
    dense = kernels.analyze_edges(3, edges, max_dense=4096)
    assert dense["G1c"] and dense["G2-item"]


def test_two_g_single_cycles_sharing_a_node_are_not_g2():
    # cycle A: 0-rw->1-ww->0; cycle B: 0-ww->2-rw->3-ww->0. Every simple
    # cycle has exactly one anti-dependency; stitching them through the
    # shared node 0 is not a simple cycle, so G2-item must stay False
    # (regression: the distinct-rw-sources test alone reports G2)
    edges = {(0, 1): {"rw"}, (1, 0): {"ww"}, (0, 2): {"ww"},
             (2, 3): {"rw"}, (3, 0): {"ww"}}
    for max_dense in (2, 4096):
        res = kernels.analyze_edges(4, edges, max_dense=max_dense)
        assert res["G-single"], max_dense
        assert not res["G2-item"], max_dense


def test_analyze_edges_self_loops():
    r = kernels.analyze_edges(2, {(0, 0): {"ww"}})
    assert r["G0"] and r["G1c"] and 0 in r["cycle-nodes"]
    r2 = kernels.analyze_edges(2, {(1, 1): {"rw"}})
    assert r2["G-single"] and not r2["G0"]
    # dense adapter with a true diagonal
    ww = np.zeros((2, 2), bool)
    ww[1, 1] = True
    assert kernels.analyze_graph(ww, np.zeros_like(ww),
                                 np.zeros_like(ww))["G0"]


def test_analyze_edges_oversized_g_single_and_g2():
    # oversized classification distinguishes one-rw from >=2-rw cycles
    e1 = {(0, 1): {"rw"}, (1, 2): {"ww"}, (2, 0): {"wr"}}
    r1 = kernels.analyze_edges(3, e1, max_dense=2)
    assert r1["G-single"] and not r1["G1c"] and not r1["G2-item"]
    e2 = {(0, 1): {"rw"}, (1, 2): {"ww"}, (2, 3): {"rw"}, (3, 0): {"ww"}}
    r2 = kernels.analyze_edges(4, e2, max_dense=2)
    assert r2["G2-item"] and not r2["G-single"]


def test_append_phantom_value_does_not_hide_anti_dependency():
    # a corrupt store fabricates value 9 in x's chain [1, 9, 2]; the
    # reader of [1] must still anti-depend on the (real) writer of 2,
    # closing a G-single cycle through T2 -wr-> R on k2
    h = history(
        _ok(0, [["append", "x", 1]], 0)
        + _ok(1, [["append", "x", 2], ["append", "k2", 5]], 2)
        + _ok(2, [["r", "x", [1]], ["r", "k2", [5]]], 4)
        + _ok(3, [["r", "x", [1, 9, 2]]], 6))
    res = list_append.check(h)
    assert res["valid?"] is False
    assert "G-single" in res["anomaly-types"]


def test_wr_history_synth_valid():
    from jepsen_tpu.checker import synth
    h = synth.wr_history(3000)
    res = wr.check(h)
    assert res["valid?"] is True
    assert res["txn-count"] == 3000


def test_g_single_certificate_has_exactly_one_rw():
    h = history(
        _ok(0, [["append", "x", 1], ["append", "y", 1]], 0)
        + _ok(1, [["r", "x", [1]], ["r", "y", []]], 2))
    res = list_append.check(h)
    cert = res["anomalies"]["G-single"][0]["cycle"]
    assert cert is not None
    assert cert[0]["index"] == cert[-1]["index"]  # closed cycle
    assert len(cert) == 3  # reader -rw-> writer -wr-> reader
