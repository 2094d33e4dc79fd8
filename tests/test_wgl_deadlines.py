"""The batch path's deadlines for crashed ops (wgl.release_crashed) and its
split of a slot bucket by key length (wgl._length_parts): every verdict
stays the host oracle's, a key is checked again only when a deadline may
have cost it its verdict, and only skewed lengths split a group."""

import numpy as np
import pytest

from jepsen_tpu import models as m
from jepsen_tpu import telemetry
from jepsen_tpu.checker import synth, wgl
from jepsen_tpu.checker.linear import analysis_host
from jepsen_tpu.history import KIND_INFO, History, OpArray


def op(type, f, value, process=0):
    return {"type": type, "f": f, "value": value, "process": process}


def crashed(f, value, process):
    return [op("invoke", f, value, process), op("info", f, value, process)]


def ok(f, value, process, read=None):
    return [op("invoke", f, read if f == "read" else value, process),
            op("ok", f, value, process)]


def _rechecked():
    snap = telemetry.snapshot(prefix="jepsen_tpu_wgl_batch_rechecked")
    return sum(snap.get("jepsen_tpu_wgl_batch_rechecked_keys_total",
                        {}).values())


def test_a_crash_is_open_until_the_next_of_its_kind():
    h = History(ok("write", 0, 0) + crashed("write", 1, 1)
                + crashed("cas", [1, 2], 2) + crashed("write", 1, 3)
                + crashed("write", 2, 4) + crashed("write", 1, 5)
                + crashed("cas", [1, 2], 6))
    ops = wgl.encode_ops_for_model(m.cas_register(), h)
    one, released = wgl.release_crashed(ops, 1)
    assert released
    kinds = one.kind.tolist()
    assert kinds == [0, wgl.KIND_RELEASED, wgl.KIND_RELEASED,
                     wgl.KIND_RELEASED, KIND_INFO, KIND_INFO, KIND_INFO]
    # each deadline is the invocation of the next crash of the same kind
    assert one.ret[1:4].tolist() == [ops.inv[3], ops.inv[6], ops.inv[5]]
    # at most one crash of each of the three kinds holds a slot at once
    assert wgl.required_slots(one) == 3 < wgl.required_slots(ops) == 6
    steps = wgl.build_steps(one, wgl.required_slots(one))
    releases = steps.x[:steps.n, steps.w + 1] == wgl.F_RELEASE
    assert releases.sum() == 3
    assert (steps.ret_row[:steps.n][releases] == -1).all()
    assert wgl.event_count(one) == len(ops) + 1 + 3
    two, released = wgl.release_crashed(ops, 2)
    assert released and two.kind.tolist().count(wgl.KIND_RELEASED) == 1
    every, released = wgl.release_crashed(ops, 3)
    assert not released and every is ops


def _two_after_a_write(n_reads=2):
    """Two crashed writes of 1, then a completed write of 0 and reads of
    1 around writes of 2: with the first crash closed at the second's
    invocation, one crash of 1 is left for the reads, which need
    `n_reads` of them."""
    h = crashed("write", 1, 1) + crashed("write", 1, 2) + ok("write", 0, 0)
    for k in range(n_reads):
        if k:
            h += ok("write", 2, 0)
        h += ok("read", 1, 3)
    return History(h)


def _read_past_a_deadline():
    """A read of a value never written, after the first deadline."""
    return History(crashed("write", 1, 1) + crashed("write", 1, 2)
                   + ok("read", 3, 3))


@pytest.mark.parametrize("key,valid,again", [
    # dies past the deadline; the prefix lives, so the deadlines go
    (lambda: _two_after_a_write(2), True, 2),
    (lambda: _two_after_a_write(3), False, 2),
    # dies past the deadline, and the prefix convicts it
    (_read_past_a_deadline, False, 1)])
def test_a_key_failing_past_a_deadline_is_checked_again(key, valid, again):
    plain = synth.register_history(30, concurrency=3, values=3, seed=1)
    hists = [key(), plain]
    assert analysis_host(m.cas_register(), hists[0])["valid?"] is valid
    before = _rechecked()
    rs = wgl.analysis_tpu_batch(m.cas_register(), hists)
    assert [r["valid?"] for r in rs] == [valid, True]
    assert {r["analyzer"] for r in rs} == {"tpu-wgl-batch"}
    assert _rechecked() - before == again
    sort = wgl.analysis_tpu_batch(m.cas_register(), hists, engine="sort")
    assert [r["valid?"] for r in sort] == [valid, True]


def test_a_key_that_dies_before_its_first_deadline_is_not_checked_again():
    bad = History(ok("write", 0, 0) + ok("read", 3, 1)
                  + crashed("write", 1, 2) + crashed("write", 1, 3)
                  + ok("read", 1, 1))
    ops = wgl.encode_ops_for_model(m.cas_register(), bad)
    assert wgl.release_crashed(ops, 1)[1]
    before = _rechecked()
    rs = wgl.analysis_tpu_batch(m.cas_register(), [bad, _two_after_a_write()])
    assert [r["valid?"] for r in rs] == [False, True]
    assert rs[0]["op-index"] == 2          # the read of 3
    assert _rechecked() - before == 2      # only the other key


@pytest.mark.parametrize("seed", range(6))
def test_crash_heavy_batches_agree_with_the_host(seed):
    """Many crashed writes over two values, so most kinds repeat: valid
    and corrupted keys side by side, each as the host oracle decides."""
    hists = []
    for s in range(4):
        h = synth.register_history(36, concurrency=4, values=2,
                                   crash_rate=0.3, seed=100 * seed + s)
        hists.append(synth.corrupt(h, seed + s) if s % 2 else h)
    rs = wgl.analysis_tpu_batch(m.cas_register(), hists)
    want = [analysis_host(m.cas_register(), h)["valid?"] for h in hists]
    assert [r["valid?"] for r in rs] == want
    assert False in want


def _keys(*events):
    """Stand-in encoded keys, all ops completed, with these event counts."""
    out = []
    for e in events:
        n = e // 2
        z = np.zeros(n, np.int32)
        out.append(OpArray(f=z, a=z, b=z, kind=z, inv=np.arange(n),
                           ret=np.arange(n), process=z, index=np.arange(n)))
    return out


def test_length_parts_split_only_skewed_lengths():
    # keyed-50k-like: 72-87 ops a key, one part however they straddle 128
    even = _keys(*([140] * 40 + [170] * 300))
    assert wgl._length_parts(list(range(len(even))), even) == \
        [list(range(len(even)))]
    # Zipf-like: many short keys and a few long ones part ways
    skew = _keys(*([100] * 700 + [240] * 200 + [4000] * 3))
    parts = wgl._length_parts(list(range(len(skew))), skew)
    assert parts == [list(range(700)), list(range(700, 900)),
                     list(range(900, 903))]
    # a handful of middling keys rides with the longer ones
    few = _keys(*([400] * 3 + [4000] * 2))
    assert wgl._length_parts([0, 1, 2, 3, 4], few) == [[3, 4, 0, 1, 2]]
