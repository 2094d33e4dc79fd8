"""TPU linearizability kernel: a JIT-linearization frontier search in XLA.

This replaces the reference's CPU-bound Knossos search (consumed via
`jepsen/src/jepsen/checker.clj:185-216`; `knossos.linear` / `knossos.wgl`),
which needs a 32 GB heap and "can take hours" on 10k-op histories. The
algorithm here is the same just-in-time linearization search, re-shaped for
a systolic/vector machine:

**Configurations are fixed-width.** A configuration is (model state: int32,
linearized-pending-ops bitmask: uint32[W]). Each in-flight operation holds a
*slot* in [0, P); slots are assigned host-side by scanning the history
(freed at completion, held forever by crashed :info ops), so the bitmask
width is bounded by real concurrency, not history length.

**The search is a frontier, not a stack.** The frontier is a dense array of
F configurations. We process history entries in order inside one
`lax.while_loop`:

  * *invoke*: the op occupies its slot. The frontier is closed under
    linearization (invariant), so only sequences beginning with the new op
    can add configurations: stage A linearizes just the new op against all
    F configs (one small sort to dedup); stage B repeatedly expands from
    freshly-added configs against all P pending slots (F*P candidates)
    until closure — in typical histories stage B's legality mask is empty
    and its sort never runs.
  * *complete*: every configuration must have linearized the op (its
    linearization point precedes its completion); survivors clear the bit
    and the slot is recycled.

Dedup is a multi-word lexicographic `lax.sort` + neighbor-equality mask;
stable sort with old-configs-first makes "new config" detection exact.
The history is linearizable iff any configuration survives every entry.
The event stream ships to the device as packed *steps* (see Steps):
runs of consecutive completions merge into the next invoke's step,
nearly halving the sequential depth of the device loop, and the whole
stream is one int32 matrix — one host->device transfer per check.

Soundness under resource caps: frontier overflow (> F live configs) only
*drops* candidate linearizations, so a 'valid' verdict is always sound; an
'invalid' verdict under overflow is reported as 'unknown' and escalated.
Slot overflow (> P concurrent+crashed pending ops) is detected host-side
before launch.

Batching: `vmap` over independent per-key histories. `_plan_groups`
plans a batch's dispatch groups once (keys bucketed by slot need, then
by length; dense groups cheapest-first, the sort group last), and both
batch entries dispatch that plan: `analysis_tpu_batch` checks each group in
one `_check_group` call under the recovery ladder, and
`check_batch_sharded` shards each group's key axis over a
`jax.sharding.Mesh` and reduces verdicts with a psum-OR over ICI.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import heapq
import logging
import os
import time as _time
from typing import Callable, NamedTuple

import numpy as np

from .. import calibrate as _calibrate, telemetry as _telemetry
from .._platform import (FAULT_COMPILE, FAULT_DEVICE_LOST,
                         FAULT_OOM, attest_enabled, backend_reinit,
                         classify_backend_error, guarded_device_get,
                         maybe_corrupt, maybe_inject_fault)
from ..history import (DeviceEncodingError, F_CAS, F_READ, F_WRITE,
                       KIND_INFO, KIND_OK, NIL, PENDING_RET, OpArray,
                       default_register_codec, encode_ops,
                       history as as_history)

log = logging.getLogger(__name__)

# -- telemetry (doc/observability.md catalogs these) -------------------------
# Per-chunk latency by dispatch site; the streaming layer observes into
# the same family (site='stream') so one histogram covers every device
# chunk the pipeline runs.
_M_CHUNK = _telemetry.histogram(
    "jepsen_tpu_wgl_chunk_seconds",
    "Device chunk dispatch + lagged-sync latency",
    ("site", "family"))
_M_COMPILE = _telemetry.histogram(
    "jepsen_tpu_wgl_compile_seconds",
    "Kernel build (trace/cache miss) and warm-up compile latency",
    ("family", "stage"))
_M_ENGINE = _telemetry.counter(
    "jepsen_tpu_wgl_engine_decisions_total",
    "select_engine outcomes by family, dedup engine, and coarse reason",
    ("family", "dedup", "reason"))
_M_ELEMENTOPS = _telemetry.counter(
    "jepsen_tpu_wgl_modeled_elementops_total",
    "Modeled element-ops of the engines select_engine chose",
    ("family",))
_M_RUNGS = _telemetry.counter(
    "jepsen_tpu_wgl_recovery_rungs_total",
    "Recovery-ladder rung climbs by classified fault kind and site",
    ("kind", "site"))
_M_OPS = _telemetry.counter(
    "jepsen_tpu_wgl_checked_ops_total",
    "History ops decided by device-checking entries",
    ("site",))
# What a batch's vmapped dispatch ran against what its keys needed: every
# lane (padding included) is staged with E rows and runs every step the
# chunk loop advances, a real key needs only its own steps. 1 - useful /
# dispatched is the stepping's padding, 1 - useful / staged the staging's.
_M_LANE_STEPS = _telemetry.counter(
    "jepsen_tpu_wgl_batch_lane_steps_total",
    "Lane-steps of batch dispatches: staged (lanes x E rows), dispatched "
    "(lanes x steps advanced) and useful (each real key's steps, up to "
    "those advanced)",
    ("family", "kind"))
# The dense table's cells those dispatched lane-steps carried: a dense
# group's lanes x S x 2^P at every step its chunk loop advances.
_M_TABLE_CELLS = _telemetry.counter(
    "jepsen_tpu_wgl_batch_table_cells_total",
    "Dense-table cells stepped by batch dispatches: lanes x S x 2^P x "
    "steps advanced, at each chunk dispatch",
    ("family",))
_M_GROUPS = _telemetry.counter(
    "jepsen_tpu_wgl_batch_groups_total",
    "Batch dispatch groups run (one vmapped kernel shape each)",
    ("family",))
_M_RECHECKED = _telemetry.counter(
    "jepsen_tpu_wgl_batch_rechecked_keys_total",
    "Keys a batch checked again, by prefix or with later deadlines, "
    "after a crash deadline may have cost their verdict (see "
    "release_crashed)")
_M_DEADLINE_CHECKS = _telemetry.counter(
    "jepsen_tpu_wgl_deadline_checks_total",
    "Checks in _decide_in_rounds by site (offline: analysis_tpu, batch: "
    "analysis_tpu_batch) and outcome: none (no op had a crash deadline), "
    "stood (the verdict under deadlines was final), again (the history "
    "went another round)",
    ("site", "outcome"))

# A crashed op given a deadline by release_crashed: it may take effect
# only before its ret, where its slot is released (OpArray.kind)
KIND_RELEASED = 2
# f of a step that releases the slots in its mask instead of completing
# them: configurations that took those ops and those that did not both
# live on (dense kernel only)
F_RELEASE = -2


class SlotOverflow(Exception):
    """More concurrent+crashed pending ops than the kernel's P slots."""


# ---------------------------------------------------------------------------
# Device models: vectorized step semantics (mirrors models.device_step_*)
# ---------------------------------------------------------------------------

def _register_step(cas_enabled: bool):
    def step(state, f, a, b):
        import jax.numpy as jnp
        legal = (f == F_READ) & ((a == NIL) | (state == a))
        legal = legal | (f == F_WRITE)
        if cas_enabled:
            cas_ok = (f == F_CAS) & (state == a)
            legal = legal | cas_ok
            new = jnp.where(f == F_WRITE, a, jnp.where(cas_ok, b, state))
        else:
            new = jnp.where(f == F_WRITE, a, state)
        return legal, new
    return step


def _mutex_step(state, f, a, b):
    # f: 0 = acquire, 1 = release. Outputs broadcast over state x f.
    import jax.numpy as jnp
    state, f = jnp.broadcast_arrays(state, f)
    legal = ((f == 0) & (state == 0)) | ((f == 1) & (state == 1))
    new = jnp.where(f == 0, jnp.ones_like(state), jnp.zeros_like(state))
    return legal, new


def mutex_codec(o: dict) -> tuple[int, int, int]:
    f = o["f"]
    if f == "acquire":
        return 0, NIL, NIL
    if f == "release":
        return 1, NIL, NIL
    raise DeviceEncodingError(f"unknown mutex op f={f!r}")


# -- counter: f 0 = read(observed; b=1 iff constrained), 1 = add(delta) ------
# Counters reach negative values routinely, so an observed read of -1
# must NOT collide with the NIL sentinel: b carries an explicit
# "constrained" flag instead.

def _counter_step(state, f, a, b):
    import jax.numpy as jnp
    state, f, a, b = jnp.broadcast_arrays(state, f, a, b)
    legal = ((f == 0) & ((b == 0) | (state == a))) | (f == 1)
    new = jnp.where(f == 1, state + a, state)
    return legal, new


def counter_codec(o: dict) -> tuple[int, int, int]:
    f, v = o["f"], o["value"]
    if f == "read":
        if v is None:
            return 0, 0, 0
        return 0, int(v), 1
    if f == "add":
        return 1, int(v), NIL
    raise DeviceEncodingError(f"unknown counter op f={f!r}")


def _counter_range(init, f, a, b):
    f, a, b = np.asarray(f), np.asarray(a), np.asarray(b)
    deltas = a[f == 1]
    lo = init + int(deltas[deltas < 0].sum()) if deltas.size else init
    hi = init + int(deltas[deltas > 0].sum()) if deltas.size else init
    # completed reads also name reachable values (paranoia: they must
    # equal a state anyway); include them so invalid histories still
    # encode
    reads = a[(f == 0) & (b == 1)]
    if reads.size:
        lo = min(lo, int(reads.min()))
        hi = max(hi, int(reads.max()))
    return lo, hi


# -- grow-only set: f 0 = read(bitmask), 1 = add(element id) -----------------

GSET_MAX_ELEMENTS = 31   # state is an int32 membership bitmask


def _gset_step(state, f, a, b):
    import jax.numpy as jnp
    state, f, a = jnp.broadcast_arrays(state, f, a)
    legal = ((f == 0) & ((a == NIL) | (state == a))) | (f == 1)
    shift = jnp.clip(a, 0, GSET_MAX_ELEMENTS - 1)
    new = jnp.where(f == 1, state | (1 << shift), state)
    return legal, new


def gset_codec(o: dict) -> tuple[int, int, int]:
    f, v = o["f"], o["value"]
    if f == "add":
        v = int(v)
        if not 0 <= v < GSET_MAX_ELEMENTS:
            raise DeviceEncodingError(
                f"g-set element {v} outside [0, {GSET_MAX_ELEMENTS})"
                " — use the host model")
        return 1, v, NIL
    if f == "read":
        if v is None:
            return 0, NIL, NIL
        mask = 0
        for x in v:
            x = int(x)
            if not 0 <= x < GSET_MAX_ELEMENTS:
                raise DeviceEncodingError(
                    f"g-set element {x} outside "
                    f"[0, {GSET_MAX_ELEMENTS}) — use the host model")
            mask |= 1 << x
        return 0, mask, NIL
    raise DeviceEncodingError(f"unknown g-set op f={f!r}")


def _gset_range(init, f, a, b):
    f, a = np.asarray(f), np.asarray(a)
    full = int(init)
    for x in a[f == 1]:
        full |= 1 << int(x)
    for m in a[(f == 0) & (a != NIL)]:
        full |= int(m)
    return 0, full


# -- unordered queue: f 0 = dequeue(v), 1 = enqueue(v) -----------------------
# state: 4-bit per-value multiplicities, values in [0, 7)

from ..history import UQ_COUNT_MAX, UQ_VALUES  # noqa: E402 (shared
# with models.UnorderedQueue.device_state — one copy of the layout)


def _uqueue_step(state, f, a, b):
    import jax.numpy as jnp
    state, f, a = jnp.broadcast_arrays(state, f, a)
    shift = 4 * jnp.clip(a, 0, UQ_VALUES - 1)
    cnt = (state >> shift) & UQ_COUNT_MAX
    ok_a = (a >= 0) & (a < UQ_VALUES)
    legal = jnp.where(f == 1, ok_a & (cnt < UQ_COUNT_MAX),
                      ok_a & (cnt > 0))
    new = jnp.where(legal & (f == 1), state + (1 << shift),
                    jnp.where(legal & (f == 0),
                              state - (1 << shift), state))
    return legal, new


def _uqueue_validate(ops: OpArray, model) -> None:
    """A sound upper bound on any reachable per-value multiplicity:
    initial copies plus enqueues invoked so far, minus ok dequeues
    returned so far, maxed over the event stream. If it can exceed
    the 4-bit digit cap the device multiset would silently saturate
    (carrying into the next value's digit) — raise so the checker
    falls back to the host model."""
    events: list[tuple[int, int, int]] = []
    for r in range(len(ops)):
        v = int(ops.a[r])
        if ops.f[r] == 1:                       # enqueue (incl. crashed)
            events.append((int(ops.inv[r]), 0, v))
        elif ops.kind[r] == KIND_OK:            # ok dequeue
            events.append((int(ops.ret[r]), 1, v))
    events.sort()
    outstanding = [0] * UQ_VALUES
    for (v, _i) in getattr(model, "pending", ()):
        v = int(v)
        if not 0 <= v < UQ_VALUES:
            raise DeviceEncodingError(
                f"initial queue value {v} outside [0, {UQ_VALUES}) — "
                "use the host model")
        outstanding[v] += 1
        if outstanding[v] > UQ_COUNT_MAX:
            raise DeviceEncodingError(
                f"initial queue state has more than {UQ_COUNT_MAX} "
                f"copies of {v} — use the host model")
    for _, kind, v in events:
        if kind == 0:
            outstanding[v] += 1
            if outstanding[v] > UQ_COUNT_MAX:
                raise DeviceEncodingError(
                    f"queue value {v} may have more than "
                    f"{UQ_COUNT_MAX} outstanding copies — the device "
                    "multiset digit would saturate; use the host model")
        else:
            outstanding[v] -= 1


def uqueue_codec(o: dict) -> tuple[int, int, int]:
    f, v = o["f"], o["value"]
    if v is None:
        raise DeviceEncodingError(
            "queue op with unknown value (crashed dequeue?) — the "
            "device multiset can't branch over it; use the host model")
    v = int(v)
    if not 0 <= v < UQ_VALUES:
        raise DeviceEncodingError(
            f"queue value {v} outside [0, {UQ_VALUES}) — use the "
            "host model")
    if f == "enqueue":
        return 1, v, NIL
    if f == "dequeue":
        return 0, v, NIL
    raise DeviceEncodingError(f"unknown queue op f={f!r}")


# -- multi-register: f 0 = reads only, 1 = writes ---------------------------
# A transaction lowers to what it asks of the state it starts from and
# what it leaves: for subkey k, bit 9 + k of `a` constrains its digit to
# bits [3k, 3k + 3) of `a` (code 7, never a digit, for a transaction that
# reads back other than it wrote), and bit 9 + k of `b` sets it to bits
# [3k, 3k + 3) of `b`. The state is models.MultiRegister's mixed-radix
# packing: 216 states, where 3-bit fields would take 366 (and a table
# bucket of 512, twice the rows to step and one slot fewer under
# DENSE_TABLE_CAP).

from ..models import (MR_RADIX, MR_SUBKEYS, mr_digit,  # noqa: E402
                      mr_subkey)

MR_READ, MR_WRITE = 0, 1
_MR_NEVER = 7


def _mr_digits(state):
    """The subkeys' digits of packed states, least significant first."""
    return [state // MR_RADIX ** k % MR_RADIX for k in range(MR_SUBKEYS)]


def _mr_step(state, f, a, b):
    import jax.numpy as jnp
    state, a, b = jnp.broadcast_arrays(state, a, b)
    legal = jnp.ones(state.shape, jnp.bool_)
    new = jnp.zeros_like(state)
    for k, d in enumerate(_mr_digits(state)):
        legal = legal & ((((a >> (9 + k)) & 1) == 0)
                         | (d == ((a >> (3 * k)) & 7)))
        new = new + jnp.where(((b >> (9 + k)) & 1) == 1,
                              (b >> (3 * k)) & 7, d) * MR_RADIX ** k
    return legal, new


def multi_register_codec(o: dict) -> tuple[int, int, int]:
    """A transaction [[f, k, v], ...] as (f, a, b): the digits it needs
    of the state it starts from, in micro-op order (a read after the
    transaction's own write of that subkey is decided here), and the
    digits it writes. A nil read constrains nothing."""
    need: dict[int, int] = {}
    wrote: dict[int, int] = {}
    for mop in o["value"] or ():
        f, k, v = mop
        k = mr_subkey(k)
        if f in ("w", "write"):
            wrote[k] = mr_digit(v)
        elif f in ("r", "read"):
            if v is None:
                continue
            d = mr_digit(v)
            if k in wrote:
                if wrote[k] != d:
                    need[k] = _MR_NEVER
            elif need.setdefault(k, d) != d:
                need[k] = _MR_NEVER
        else:
            raise DeviceEncodingError(
                f"unknown multi-register micro-op f={f!r}")
    a = sum((d << (3 * k)) | (1 << (9 + k)) for k, d in need.items())
    b = sum((d << (3 * k)) | (1 << (9 + k)) for k, d in wrote.items())
    return (MR_WRITE if wrote else MR_READ), a, b


def _mr_range(init, f, a, b):
    """States over the subkeys the history touches: all digits of every
    subkey up to the highest touched, so a relabelled history keeps its
    shape."""
    a, b = np.asarray(a), np.asarray(b)
    touched = int(np.bitwise_or.reduce(np.concatenate(
        [a[a != NIL] >> 9, b[b != NIL] >> 9, [0]]).astype(np.int64)))
    top = max(touched.bit_length(),
              next((k + 1 for k in reversed(range(MR_SUBKEYS))
                    if init // MR_RADIX ** k % MR_RADIX), 1))
    return 0, MR_RADIX ** top - 1


def _mr_reset(init):
    """A write of `init`'s digits to every subkey: legal from every
    state, it moves each to `init`."""
    return MR_WRITE, 0, sum((d << (3 * k)) | (1 << (9 + k))
                            for k, d in enumerate(_mr_digits(int(init))))


def _mr_image(x, legal, f, a, b):
    """The rows of word table `x` (S states x W words of configurations)
    moved by one transaction: the rows it is legal from, OR-reduced over
    each written subkey's digit and placed at the written digit. O(S x
    W), where the transition product is O(S^2 x W). The table's first
    6^t rows are every state of the t subkeys _mr_range spans, t the
    most that S holds."""
    import jax.numpy as jnp
    from jax import lax
    S, rest = x.shape[0], x.shape[1:]
    t = max(t for t in range(MR_SUBKEYS + 1) if MR_RADIX ** t <= S)
    n = MR_RADIX ** t
    x = jnp.where(legal.reshape((S,) + (1,) * len(rest)), x,
                  jnp.zeros_like(x))
    x = x[:n].reshape((MR_RADIX,) * t + rest)
    for k in range(t):
        axis = t - 1 - k
        shape = [1] * x.ndim
        shape[axis] = MR_RADIX
        hit = jnp.arange(MR_RADIX).reshape(shape) == ((b >> (3 * k)) & 7)
        moved = jnp.expand_dims(lax.reduce(x, x.dtype.type(0),
                                           lax.bitwise_or, (axis,)), axis)
        placed = jnp.where(hit, moved, jnp.zeros_like(x))
        x = jnp.where(((b >> (9 + k)) & 1) == 1, placed, x)
    return jnp.concatenate([x.reshape((n,) + rest),
                            jnp.zeros((S - n,) + rest, x.dtype)])


@dataclasses.dataclass(frozen=True)
class DeviceModel:
    """A model with enumerable int32 state, steppable on device.

    step        (state, f, a, b) -> (legal, new_state), broadcasting
    codec       op dict -> (f, a, b) int encoding
    droppable   f-codes whose pending (crashed) ops constrain nothing
    state_range (init_state, f, a, b arrays) -> inclusive (lo, hi)
                bounds on every reachable state — lets the kernel pack
                a whole config into one u32 sort key when it fits
    """
    step: Callable
    codec: Callable
    droppable: frozenset
    state_range: Callable
    validate: Callable | None = None  # (OpArray, model) -> None | raise
    # (f, a, b) -> the kind of a crashed op: release_crashed gives it a
    # deadline at the next crash of its kind
    crash_kind: Callable = lambda f, a, b: (f, a, b)
    # (words, legal, f, a, b) -> the rows one op moves a word table's
    # rows to, for a model whose states are too many for the transition
    # product (see _dense_kernel_cached)
    image: Callable | None = None
    # init state -> (f, a, b) of an op legal from every state that moves
    # each to init: a dense lane then checks keys one after another
    # (_pack_lanes)
    reset: Callable | None = None

    def __iter__(self):  # legacy tuple shape: (step, codec, droppable)
        return iter((self.step, self.codec, self.droppable))


def _register_range(init, f, a, b):
    a, b = np.asarray(a), np.asarray(b)
    hi, lo = init, min(NIL, init)
    for v in (a[a != NIL], b[b != NIL]):
        if v.size:
            hi = max(hi, int(v.max()))
            lo = min(lo, int(v.min()))
    return lo, hi


DEVICE_MODELS: dict[str, DeviceModel] = {
    "cas-register": DeviceModel(_register_step(True),
                                default_register_codec,
                                frozenset({F_READ}), _register_range),
    "register": DeviceModel(_register_step(False), default_register_codec,
                            frozenset({F_READ}), _register_range),
    "mutex": DeviceModel(_mutex_step, mutex_codec, frozenset(),
                         lambda init, f, a, b: (0, 1)),
    # crashed (pending) reads constrain nothing for counter/g-set and
    # are droppable; queue dequeues are never droppable
    "counter": DeviceModel(_counter_step, counter_codec,
                           frozenset({0}), _counter_range),
    "g-set": DeviceModel(_gset_step, gset_codec,
                         frozenset({0}), _gset_range),
    "unordered-queue": DeviceModel(
        _uqueue_step, uqueue_codec, frozenset(),
        lambda init, f, a, b: (0, (1 << (4 * UQ_VALUES)) - 1),
        validate=_uqueue_validate),
    # write transactions come in hundreds of (f, a, b) kinds, so a crash
    # would almost never meet a later one of its own: any crashed write
    # is of one kind
    "multi-register": DeviceModel(
        _mr_step, multi_register_codec, frozenset({MR_READ}), _mr_range,
        crash_kind=lambda f, a, b: f, image=_mr_image, reset=_mr_reset),
}


# ---------------------------------------------------------------------------
# Host preprocessing: ops -> packed event steps with slot assignment
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Steps:
    """The kernels' input: the history as packed event steps.

    One int32 row of ``x`` per step: ``[ret_mask words (W) | inv_slot |
    f | a | b]``. A step first *completes* every slot in ret_mask, then
    — when inv_slot >= 0 — *invokes* (inv_slot, f, a, b). Merged
    streams (build_steps merge=True) fold each run of consecutive :ok
    completions into the following invoke's step: completions commute
    (clearing distinct bits is injective and preserves frontier
    closure) and configurations cannot change between adjacent events,
    so the merged stream decides exactly the same verdict while nearly
    halving the sequential depth of the device loop. Unmerged streams
    carry one event per step, so the step where the frontier died
    names a single culprit op (used to re-derive blame for invalid
    verdicts). The whole stream is one matrix so a checker call costs
    one host->device transfer, not five.

    ret_row  int32[T] — op row of the step's sole completion (-1 if
             none, or ambiguous because several were merged)
    inv_row  int32[T] — op row of the step's invoke (-1 if none)

    A batch's stack (build_steps_batch) has a leading key axis on x,
    ret_row and inv_row, and n is int32[B].
    """
    x: np.ndarray        # (T, W+4) int32
    ret_row: np.ndarray
    inv_row: np.ndarray
    w: int
    n: int               # live steps (<= T)

    def pad_to(self, t: int) -> "Steps":
        if len(self.x) == t:
            return self
        assert len(self.x) <= t, "cannot shrink steps"
        m = t - len(self.x)
        pad = np.zeros((m, self.w + 4), np.int32)
        pad[:, self.w] = -1      # no invoke
        pad[:, self.w + 2:] = NIL
        neg = np.full(m, -1, np.int32)
        return Steps(np.concatenate([self.x, pad]),
                     np.concatenate([self.ret_row, neg]),
                     np.concatenate([self.inv_row, neg]), self.w, self.n)


def event_count(ops: OpArray) -> int:
    """Length of the unmerged event stream (invokes + ok returns +
    releases) — the T capacity that lets merged and unmerged streams
    share one compiled kernel."""
    return len(ops) + int((np.asarray(ops.kind) != KIND_INFO).sum())


def _sorted_events(ops_list: list) -> tuple:
    """Every key's events in the order the kernels step them: by key,
    then position, a release before an invoke before a return at one
    position, then op row. Returns each event's key, order (-1 release,
    0 invoke, 1 return) and row, rows counted over the keys' ops laid
    end to end, with the first such row of each key (and the total)."""
    lens = np.fromiter(map(len, ops_list), np.int64, len(ops_list))
    row0 = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=row0[1:])
    kind, inv, ret = (_concat(ops_list, c) for c in ("kind", "inv", "ret"))
    ok = kind == KIND_OK
    done = np.flatnonzero(ok | (kind == KIND_RELEASED))
    # invokes in row order, then completions in row order: a stable sort
    # keeps tied (key, position, order) events in row order
    row = np.concatenate([np.arange(len(kind)), done])
    pos = np.concatenate([inv, ret[done]]).astype(np.int64)
    order = np.concatenate([np.zeros(len(kind), np.int64),
                            np.where(ok[done], 1, -1)])
    key = np.repeat(np.arange(len(lens)), lens)[row]
    if len(pos):
        pos -= pos.min()
        span = int(pos.max()) + 1
        idx = np.argsort((key * span + pos) * 3 + order + 1, kind="stable")
        key, order, row = key[idx], order[idx], row[idx]
    return key, order, row, row0


def _concat(ops_list: list, field: str) -> np.ndarray:
    return np.concatenate([np.asarray(getattr(o, field)) for o in ops_list]
                          or [np.zeros(0, np.int32)])


def required_slots_batch(ops_list: list) -> list[int]:
    """required_slots of each OpArray in `ops_list`, in one pass: the
    running count of pending ops over the sorted events, its peak taken
    key by key."""
    key, order, _, _ = _sorted_events(ops_list)
    out = np.ones(len(ops_list), np.int64)
    if len(key):
        level = np.cumsum(np.where(order == 0, 1, -1))
        first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
        base = np.where(first > 0, level[first - 1], 0)
        out[key[first]] = np.maximum(
            np.maximum.reduceat(level, first) - base, 1)
    return out.tolist()


def required_slots(ops: OpArray) -> int:
    """The peak number of simultaneously-pending ops (crashed ops pend
    forever, or to their release_crashed deadline) — the minimum slot
    count the kernel needs. Computing it up front avoids SlotOverflow
    escalation recompiles."""
    return required_slots_batch([ops])[0]


def release_crashed(ops: OpArray, m: int,
                    kind: Callable = DeviceModel.crash_kind
                    ) -> tuple[OpArray, bool]:
    """Give each crashed op a deadline: the invocation of the m-th later
    crashed op of its kind (`kind(f, a, b)`, by default the same f, a,
    b; DeviceModel.crash_kind), after which it may no longer take effect
    and its slot is released. Returns the ops and whether any got a
    deadline.

    A deadline only narrows when an op may take effect, so a history
    linearizable with deadlines is linearizable, and with none left it
    is the history itself. A crashed op otherwise holds its slot
    forever, so a key with many crashes would step a 2^P table too wide
    to run; with deadlines at most m of a kind hold slots at once. With
    m = 1 a crash that took effect before the next of its kind was
    invoked, as most do, is still covered."""
    info = np.flatnonzero(np.asarray(ops.kind) == KIND_INFO)
    if len(info) <= m:
        return ops, False
    by_kind: dict = {}
    for r in info:
        by_kind.setdefault(kind(int(ops.f[r]), int(ops.a[r]),
                                int(ops.b[r])), []).append(int(r))
    ret, kind = np.array(ops.ret), np.array(ops.kind)
    for rows in by_kind.values():
        for j, r in enumerate(rows[:-m]):
            ret[r], kind[r] = ops.inv[rows[j + m]], KIND_RELEASED
    if (kind == np.asarray(ops.kind)).all():
        return ops, False
    return dataclasses.replace(ops, ret=ret, kind=kind), True


def _pending_forever(ops: OpArray) -> OpArray:
    """The ops without release_crashed's deadlines (the sort kernel has
    no release step)."""
    released = np.asarray(ops.kind) == KIND_RELEASED
    if not released.any():
        return ops
    return dataclasses.replace(
        ops, kind=np.where(released, KIND_INFO, ops.kind),
        ret=np.where(released, PENDING_RET, ops.ret))


def _death_position(ops: OpArray, result: dict) -> int | None:
    """Where the definite invalid `result` of `ops` died: the position
    of its culprit's completion (None if it names no completed op)."""
    rows = np.flatnonzero(np.asarray(ops.index) == result.get("op-index"))
    if not len(rows) or ops.kind[rows[0]] != KIND_OK:
        return None
    return int(ops.ret[rows[0]])


def _prefix(ops: OpArray, position: int, droppable) -> OpArray:
    """The ops invoked by `position`, those that complete after it
    pending forever (or dropped where pending they constrain nothing).
    Any linearization of the whole history, cut at `position`, is one
    of this prefix, so a prefix that is not linearizable convicts the
    history."""
    late = (np.asarray(ops.kind) == KIND_OK) & \
        (np.asarray(ops.ret) > position)
    keep = (np.asarray(ops.inv) <= position) & \
        ~(late & np.isin(ops.f, list(droppable)))
    cut = dataclasses.replace(
        ops, kind=np.where(late, KIND_INFO, ops.kind),
        ret=np.where(late, PENDING_RET, ops.ret))
    return OpArray(**{f.name: getattr(cut, f.name)[keep]
                      for f in dataclasses.fields(OpArray)})


def _decide_in_rounds(pre: list, run, dm: DeviceModel,
                      deadlines: bool = True, site: str = "batch"):
    """Decide encoded histories through rounds of `run(indices, ops)`.

    A key is first checked with release_crashed's deadlines at m = 1.
    An invalid verdict stands if no deadline was given or the key died
    before the first, where its stream is the history's own. Otherwise
    the history's prefix up to that death is checked with no deadlines:
    an invalid prefix convicts the key, a valid one means the deadlines
    cost the verdict, and the key goes round again at 2m. With no
    deadline left a round checks the history itself, so every verdict
    is exact. `site` labels the checks in jepsen_tpu_wgl_deadline_checks
    (offline: analysis_tpu, whose `run` is one _search a round; batch:
    analysis_tpu_batch, whose `run` plans the round's dispatch groups
    and checks each once). `dm` is the histories' device model: its
    droppable ops and crash kinds."""
    results: list[dict | None] = [None] * len(pre)
    plan = {i: (1, None) for i in range(len(pre))}   # key -> (m, cut)
    while plan:
        keys = list(plan)
        with _telemetry.section("wgl.encode"):
            sent = []
            for i in keys:
                m, cut = plan[i]
                if cut is not None:
                    sent.append((_prefix(pre[i], cut, dm.droppable),
                                 False))
                elif deadlines:
                    sent.append(release_crashed(pre[i], m, dm.crash_kind))
                else:
                    sent.append((pre[i], False))
        nxt = {}
        stood = 0
        for i, (ops, released), r in zip(keys, sent,
                                          run(keys, [o for o, _ in sent])):
            m, cut = plan[i]
            if cut is not None and r["valid?"] is True:
                nxt[i] = (2 * m, None)
                continue
            if released and r["valid?"] is False:
                died = _death_position(ops, r)
                first = int(np.asarray(ops.ret)[
                    np.asarray(ops.kind) == KIND_RELEASED].min())
                if died is None or died > first:
                    nxt[i] = (2 * m, None) if died is None else (m, died)
                    continue
            r["op-count"] = len(pre[i])
            results[i] = r
            stood += released
        for outcome, n in (("none", len(keys) - stood - len(nxt)),
                           ("stood", stood), ("again", len(nxt))):
            _M_DEADLINE_CHECKS.labels(site=site, outcome=outcome).inc(n)
        if site == "batch":
            _M_RECHECKED.inc(len(nxt))
        plan = nxt
    return results


def build_steps(ops: OpArray, p: int, merge: bool = True) -> Steps:
    """Lower an OpArray to packed event steps, assigning each op a slot
    in [0, p). Raises SlotOverflow if concurrency + crashed ops exceed
    p."""
    st = build_steps_batch([ops], p, merge)
    n = int(st.n[0])
    return Steps(st.x[0, :n], st.ret_row[0, :n], st.inv_row[0, :n], st.w, n)


def build_steps_batch(ops_list: list, p: int, merge: bool = True,
                      t: int | None = None, lanes: int | None = None
                      ) -> Steps:
    """Lower every OpArray of `ops_list` to packed event steps at once,
    straight into one (lanes, t, W+4) stack: key i's steps fill lane i
    from row 0, and the rest of every lane, and every lane past the
    keys, is padding (no completion, no invoke; n 0). `t`, the rows a
    lane, defaults to the _bucket of the longest key's step count;
    `lanes` defaults to one a key.

    Each op takes the lowest free slot at its invoke and frees it at its
    return or release (a crashed op never does); SlotOverflow names the
    first invoke that finds none of p free. Steps end at every invoke
    and release; merged (merge=True), a run of returns rides in the
    step its next invoke ends, and gets a completion-only step of its
    own before a release or at the key's end."""
    key, order, row, row0 = _sorted_events(ops_list)
    slot = _assign_slots(key, order, row, row0, p)
    w = max(1, (p + 31) // 32)
    is_inv, is_ret = order == 0, order == 1
    ends = np.ones(len(key), bool)
    if merge and len(key):
        ends[:-1] = ~is_ret[:-1] | (order[1:] == -1) | (key[1:] != key[:-1])
    step = np.cumsum(ends) - ends
    b = len(ops_list) if lanes is None else lanes
    ns = np.bincount(key[ends], minlength=b)
    n_max = int(ns.max(initial=0))
    if t is None:
        t = _bucket(max(n_max, 1))
    assert n_max <= t, "cannot shrink steps"
    at = key * t + step - (np.cumsum(ns) - ns)[key]   # flat row of each event
    slot = slot[row]
    done = ~is_inv
    masks = np.bincount(at[done] * w + (slot[done] >> 5),
                        weights=np.left_shift(1, slot[done] & 31),
                        minlength=b * t * w)   # a step's bits are distinct
    x = np.empty((b * t, w + 4), np.int32)
    x[:, :w] = masks.astype(np.uint32).view(np.int32).reshape(b * t, w)
    x[:, w:] = (-1, 0, NIL, NIL)   # no invoke
    local = row - row0[key]
    ri, ai = row[is_inv], at[is_inv]
    x[ai, w] = slot[is_inv]
    for c, field in enumerate(("f", "a", "b"), w + 1):
        x[ai, c] = _concat(ops_list, field)[ri]
    x[at[order == -1], w + 1] = F_RELEASE
    inv_row = np.full(b * t, -1, np.int32)
    inv_row[ai] = local[is_inv]
    ret_row = np.full(b * t, -1, np.int32)
    ret_row[at[is_ret]] = local[is_ret]
    ret_row[np.bincount(at[is_ret], minlength=b * t) > 1] = -1
    return Steps(x.reshape(b, t, w + 4), ret_row.reshape(b, t),
                 inv_row.reshape(b, t), w, ns.astype(np.int32))


def _assign_slots(key, order, row, row0, p: int) -> np.ndarray:
    """Each row's slot: the lowest free one at its invoke, freed at its
    return or release, over each key's sorted events in turn."""
    slot = [0] * int(row0[-1])
    bounds = np.searchsorted(key, np.arange(len(row0))).tolist()
    invokes, rows = (order == 0).tolist(), row.tolist()
    pop, push = heapq.heappop, heapq.heappush
    for k in range(len(row0) - 1):
        lo, hi = bounds[k], bounds[k + 1]
        free = list(range(p))   # a sorted list is a heap
        for is_inv, r in zip(invokes[lo:hi], rows[lo:hi]):
            if not is_inv:
                push(free, slot[r])
            elif free:
                slot[r] = pop(free)
            else:
                raise SlotOverflow(
                    f"more than {p} pending ops at op row "
                    f"{r - int(row0[k])} (crashed ops hold slots forever); "
                    f"raise p or check on the host")
    return np.asarray(slot, np.int64)


def _bucket(n: int, lo: int = 64) -> int:
    """Round up to a power of two to bound jit recompiles."""
    e = lo
    while e < n:
        e *= 2
    return e


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------

Kernel = collections.namedtuple(
    "Kernel", ["check", "check_batch", "check_chunk", "check_chunk_batch",
               "check_stream_chunk", "init_carry", "summarize", "digest"])


def _mk_digest():
    """Build the jitted carry digest: xor-fold of (component wrap-sum *
    prime_i) over the carry elements in order — the host mirror is
    abft.carry_digest_host, which must stay in lockstep. Verified at
    the chunk boundaries where the carry is fetched anyway (stream
    checkpoints, offline summarize): a mismatch means the carry
    changed between the device's reduction and the fetch."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from . import abft

    i32 = jnp.int32

    @jax.jit
    def digest(carry):
        h = i32(0)
        for i, c in enumerate(carry):
            c = jnp.asarray(c)
            if c.dtype == jnp.uint32:
                ci = lax.bitcast_convert_type(c, i32)
            else:
                ci = c.astype(i32)
            h = h ^ (jnp.sum(ci, dtype=i32) * i32(abft.prime_i32(i)))
        return h

    return digest


def _pack_params(state_range: tuple[int, int] | None,
                 P: int) -> tuple[int, int] | None:
    """Normalize a state range to the (s_lo, sb_bits) the kernel is
    actually specialized on — or None when packing is impossible — so
    histories differing only in irrelevant value ranges share one
    compiled kernel."""
    if state_range is None or P > 32:
        return None
    s_lo = state_range[0]
    sb_bits = (state_range[1] - state_range[0] + 1).bit_length()
    if P + sb_bits + 1 > 32:
        return None
    return s_lo, sb_bits


# Pallas gates and their default on a TPU backend. The closure round
# compiles on v5e and is on there by default; the hash dedup is refused
# by Mosaic ("Cannot store scalars to VMEM", tests/test_chip_compile.py)
# so the sort dedup is the default and =1 is a loud opt-in: the
# compiler's error surfaces instead of a quiet switch of paths.
PALLAS_CLOSURE_ENV = "JEPSEN_TPU_PALLAS_CLOSURE"
PALLAS_DEDUP_ENV = "JEPSEN_TPU_PALLAS_DEDUP"
_PALLAS_TPU_DEFAULT = {PALLAS_CLOSURE_ENV: True, PALLAS_DEDUP_ENV: False}


def _pallas_enabled(env_var: str, override=None) -> tuple[bool, bool]:
    """Resolve a pallas opt-in/out to (use_pallas, on_tpu): an explicit
    checker option beats the env gate beats the backend default
    (_PALLAS_TPU_DEFAULT on a real TPU; interpret-mode opt-in
    elsewhere). Resolved OUTSIDE the kernel caches so flipping the env
    (or passing pallas=) mid-process takes effect on the next call."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if override is not None:
        return bool(override), on_tpu
    flag = os.environ.get(env_var)
    return (flag == "1" or (flag != "0" and on_tpu
                            and _PALLAS_TPU_DEFAULT[env_var])), on_tpu


# dedup-engine names (reported in analyses and bench artifacts)
DEDUP_PALLAS = "pallas-hash"
DEDUP_SORT = "xla-sort"
DEDUP_NONE = "dense-table"   # the dense family has no dedup at all
# closure-round names (dense family only)
CLOSURE_PALLAS = "pallas-closure"
CLOSURE_XLA = "xla-closure"
CLOSURE_IMAGE = "image-closure"


def _hash_gate(F: int, P: int, pack: tuple[int, int] | None) -> bool:
    """The ONE gate for the Pallas hash dedup: single-u32 packed
    config (pack resolved, one mask word) and the hash working set in
    VMEM. Shared by the kernel build (_kernel_cached) and every
    reporting site (dedup_engine), so the 'dedup' stamped in analyses
    can never drift from the engine the kernel actually ran."""
    if pack is None or (P + 31) // 32 > 1:
        return False
    from . import wgl_dedup
    return wgl_dedup.eligible(F, P)


def dedup_engine(F: int, P: int, pack: tuple[int, int] | None,
                 pallas=None) -> str:
    """Which dedup the sort-family kernel would run at this shape —
    shapes failing _hash_gate keep the lexicographic sort."""
    use, _on_tpu = _pallas_enabled(PALLAS_DEDUP_ENV, pallas)
    return DEDUP_PALLAS if use and _hash_gate(F, P, pack) else DEDUP_SORT


def closure_engine(model: str, S: int, P: int, pallas=None) -> str:
    """Which closure round the dense kernel runs for the device model
    `model` at this shape — the same gates _dense_kernel_cached
    applies."""
    if DEVICE_MODELS[model].image is not None:
        return CLOSURE_IMAGE
    use, _on_tpu = _pallas_enabled(PALLAS_CLOSURE_ENV, pallas)
    from . import wgl_pallas
    return CLOSURE_PALLAS if use and wgl_pallas.eligible(S, P) \
        else CLOSURE_XLA


def _kernel(model_name: str, F: int, P: int, E: int,
            pack: tuple[int, int] | None = None, pallas=None):
    """Build (or fetch) the jitted sort-family checker. The
    Pallas-vs-XLA dedup choice is resolved HERE, outside the cache, so
    flipping JEPSEN_TPU_PALLAS_DEDUP (or a checker's pallas= option)
    mid-process takes effect on the next call instead of being baked
    into a cached kernel — the same contract as _dense_kernel."""
    use_dedup, on_tpu = _pallas_enabled(PALLAS_DEDUP_ENV, pallas)
    return _kernel_cached(model_name, F, P, E, pack, use_dedup, on_tpu,
                          attest_enabled())


def _clear_sort_caches():
    """Reset every cache that baked in a sort-kernel build decision
    (tests reach through the _kernel wrapper for this)."""
    _kernel_cached.cache_clear()
    _sharded_runner_cached.cache_clear()


_kernel.cache_clear = _clear_sort_caches


@functools.lru_cache(maxsize=32)
def _kernel_cached(model_name: str, F: int, P: int, E: int,
                   pack: tuple[int, int] | None,
                   use_dedup: bool, on_tpu: bool,
                   use_attest: bool = True):
    """Build the jitted checker for a (model, frontier-size, slots,
    entry-capacity) shape. Returns fn(entry arrays..., n_entries) ->
    (ok, death_entry, overflow, max_frontier).

    use_attest: accumulate ABFT self-check residues in the carry's
    ``att`` element (see the attestation comment on init_carry) —
    resolved from JEPSEN_TPU_ATTEST outside the cache like the pallas
    gates. The att element is ALWAYS present (uniform carry shape for
    checkpoints either way); only the accumulation is gated.

    pack: (s_lo, sb_bits) from _pack_params. When the whole config
    (invalid flag, biased state, P-bit pending mask) fits one uint32,
    dedup packs it into a single sort key; the multi-word
    lexicographic sort is the kernel's dominant cost, so this is the
    difference between sorting one u32 lane and W+2 lanes per entry.

    use_dedup: with a packed config, route the dedup through the
    Pallas open-addressing hash kernel (checker/wgl_dedup.py) instead
    of the sort — same frontier *set* in first-seen order instead of
    key order, so verdicts/summaries/blame are identical (the
    downstream phases are order-invariant). Shapes the hash gate
    rejects keep the sort."""
    # build-latency telemetry lives INSIDE the cached body: lru_cache
    # only runs it on a miss, so every observed sample is a real build
    # (a cache_info().misses delta around the call races under the
    # service's concurrent streams and would record warm hits)
    t_build = _time.monotonic()
    import jax
    import jax.numpy as jnp
    from jax import lax

    step = DEVICE_MODELS[model_name].step
    W = max(1, (P + 31) // 32)
    u32 = jnp.uint32
    i32 = jnp.int32
    if pack is not None:
        s_lo, sb_bits = pack
    else:
        s_lo, sb_bits = 0, 64
    packed = pack is not None and W == 1

    # Pallas hash dedup (the sort-free frontier): _hash_gate is sized
    # for the kernel's LARGEST dedup call (stage B's F*(1+P)
    # candidates) so one kernel never mixes dedup engines.
    hash_dedup = None
    if use_dedup and _hash_gate(F, P, pack):
        from . import wgl_dedup
        hash_dedup = functools.partial(
            wgl_dedup.dedup_fn, F=F, interpret=not on_tpu)

    # per-slot bit-vector table, shared by the completion phase and the
    # expansion stage
    _bits = np.zeros((P, W), np.uint32)
    for _p in range(P):
        _bits[_p, _p // 32] = np.uint32(1) << (_p % 32)
    BITMAT = jnp.asarray(_bits)

    def has_bit(masks, bv):
        return (masks & bv[None, :]).astype(jnp.bool_).any(axis=1)

    def _neq_prev(x):
        return jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), x[1:] != x[:-1]])

    def dedup_packed(masks, states, valid, origin):
        """Single-key dedup: key = invalid<<31 | (state-lo)<<P | mask."""
        key = jnp.where(valid, u32(0), u32(1) << 31) \
            | ((states - s_lo).astype(u32) << P) | masks[:, 0]
        key_s, org_s = lax.sort([key, origin.astype(i32)], num_keys=1,
                                is_stable=True)
        valid_s = (key_s >> 31 == 0) & _neq_prev(key_s)
        overflow = valid_s[F:].any() if len(key) > F else jnp.bool_(False)
        masks_f = (key_s[:F] & u32((1 << P) - 1))[:, None]
        states_f = ((key_s[:F] >> P) & u32((1 << sb_bits) - 1)) \
            .astype(i32) + s_lo
        valid_f = valid_s[:F]
        new_f = valid_f & (org_s[:F] == 1)
        return masks_f, states_f, valid_f, new_f, valid_f.sum(), \
            overflow, i32(0)

    def dedup_hash(masks, states, valid):
        """Sort-free dedup: the packed 31-bit config key goes through
        the Pallas open-addressing hash kernel (wgl_dedup), which
        returns the distinct valid keys compacted in first-seen order
        plus per-slot new flags. Old configs occupy input rows [0, F)
        at both call sites, so first-seen-wins is exactly the stable
        sort's old-configs-first rule and `new` needs no origin lane.
        The frontier is set-equal to the sort path's — downstream is
        order-invariant, so verdicts/summaries/blame are identical.

        ABFT: the pallas kernel also emits its table-occupancy XOR
        digest (xor of claimed keys ^ count mix). When the distinct
        count fits the frontier the same value is recomputed here from
        the compacted OUTPUT (a different store path), and any
        disagreement — a flipped VMEM word, a dropped or
        double-claimed key — is returned as `mism` for the caller's
        att accumulator."""
        key = jnp.where(
            valid,
            ((states - s_lo) << P) | masks[:, 0].astype(i32),
            i32(-1))
        out_keys, new_f, distinct, kdig = hash_dedup(len(key))(key)
        valid_f = out_keys >= 0
        safe = jnp.where(valid_f, out_keys, 0)
        masks_f = (safe & ((1 << P) - 1)).astype(u32)[:, None]
        states_f = (safe >> P) + s_lo
        if use_attest:
            from .wgl_dedup import DIGEST_COUNT_MIX
            exp = lax.reduce(jnp.where(valid_f, safe, 0), i32(0),
                             lax.bitwise_xor, (0,))
            exp = exp ^ (distinct * i32(DIGEST_COUNT_MIX))
            mism = ((exp != kdig) & (distinct <= F)).astype(i32)
        else:
            mism = i32(0)
        return masks_f, states_f, valid_f, new_f & valid_f, \
            valid_f.sum(), distinct > F, mism

    def dedup(masks, states, valid, origin):
        """Sort (N,)-rows lexicographically by (invalid, mask words, state);
        mark duplicate keys invalid (stable sort + old-configs-first makes
        the original config win); truncate to F.

        Returns (masks[F,W], states[F], valid[F], new[F], count,
        overflow, mism) — mism is the hash path's digest-mismatch flag
        (always 0 for the sort variants, whose output IS the sorted
        input: there is no second store path to cross-check).
        """
        if hash_dedup is not None:
            return dedup_hash(masks, states, valid)
        if packed:
            return dedup_packed(masks, states, valid, origin)
        invalid_key = (~valid).astype(u32)
        operands = [invalid_key] + [masks[:, w] for w in range(W)] \
            + [states, origin.astype(i32)]
        out = lax.sort(operands, num_keys=W + 2, is_stable=True)
        inv_s, ms, st_s, org_s = out[0], out[1:1 + W], out[1 + W], out[2 + W]

        first = _neq_prev(inv_s) | _neq_prev(st_s)
        for mw in ms:
            first = first | _neq_prev(mw)
        valid_s = (inv_s == 0) & first
        overflow = valid_s[F:].any() if len(inv_s) > F else jnp.bool_(False)
        masks_f = jnp.stack([mw[:F] for mw in ms], axis=1)
        states_f = st_s[:F]
        valid_f = valid_s[:F]
        new_f = valid_f & (org_s[:F] == 1)
        return masks_f, states_f, valid_f, new_f, valid_f.sum(), \
            overflow, i32(0)

    def expand_full(masks, states, valid, new, slot_f, slot_a, slot_b,
                    slot_occ, overflow, att):
        """Stage B: close the frontier under linearization, expanding only
        from freshly-added configs each round."""

        def cond(c):
            return c[3].any() & ~c[6]  # any new configs & not converged

        def body(c):
            masks, states, valid, new, overflow, att, _ = c
            # candidates: new configs x all pending slots
            legal, cstate = step(states[:, None], slot_f[None, :],
                                 slot_a[None, :], slot_b[None, :])
            already = (masks[:, None, :] & BITMAT[None, :, :]) \
                .astype(jnp.bool_).any(-1)                         # (F,P)
            legal = legal & valid[:, None] & new[:, None] \
                & slot_occ[None, :] & ~already
            any_legal = legal.any()

            def do_sort(_):
                cmasks = (masks[:, None, :] | BITMAT[None, :, :]) \
                    .reshape(F * P, W)
                cstates = cstate.reshape(F * P)
                cvalid = legal.reshape(F * P)
                all_masks = jnp.concatenate([masks, cmasks])
                all_states = jnp.concatenate([states, cstates])
                all_valid = jnp.concatenate([valid, cvalid])
                origin = jnp.concatenate(
                    [jnp.zeros(F, jnp.bool_), jnp.ones(F * P, jnp.bool_)])
                m2, s2, v2, n2, cnt2, ovf2, mism = dedup(
                    all_masks, all_states, all_valid, origin)
                grew = n2.any()
                return m2, s2, v2, n2, overflow | ovf2, att + mism, \
                    ~grew

            def no_sort(_):
                # Derive constants from varying operands so both cond
                # branches carry the same manual-axes tags under shard_map.
                return masks, states, valid, \
                    valid & False, overflow, att, any_legal | True

            return lax.cond(any_legal, do_sort, no_sort, None)

        masks, states, valid, new, overflow, att, _ = lax.while_loop(
            cond, body, (masks, states, valid, new, overflow, att,
                         jnp.bool_(False)))
        return masks, states, valid, overflow, att

    def init_carry(init_state):
        # carry layout: (e, masks, states, valid, slot_f, slot_a,
        # slot_b, slot_occ, overflow, att, count, max_count). att is
        # the ABFT attestation accumulator — in-loop invariant
        # residues (valid configs holding bits of unoccupied slots,
        # hash-dedup digest mismatches) sum into it and it must read 0
        # on host at every chunk boundary (abft.verify_carry); the
        # element is present even with attestation off so carry
        # checkpoints keep one shape.
        masks0 = jnp.zeros((F, W), u32)
        states0 = jnp.full((F,), init_state, i32)
        valid0 = jnp.zeros((F,), jnp.bool_).at[0].set(True)
        return (i32(0), masks0, states0, valid0,
                jnp.zeros((P,), i32), jnp.full((P,), NIL, i32),
                jnp.full((P,), NIL, i32), jnp.zeros((P,), jnp.bool_),
                jnp.bool_(False), i32(0), i32(1), i32(1))

    def summarize(carry):
        # att rides along as the 5th output so EVERY verdict fetch —
        # fused single-call, batch, sharded, stream liveness/finish —
        # sees the in-kernel attestation accumulator, not only the
        # boundaries that fetch the whole carry (_check_att raises
        # on a nonzero value at each consumer)
        (e, _m, _s, _valid, *_slots, overflow, att, count,
         max_count) = carry
        ok = count > 0
        death = jnp.where(ok, i32(-1), e - 1)
        return ok, death, overflow, max_count, att

    def run_range(x, stop, carry):
        """Advance the search from carry's position up to step `stop`
        (or until the frontier dies). Bounded-duration device work: long
        histories run as a sequence of these calls with the frontier
        carried between them — which is also the checkpoint for
        long searches (the carry round-trips through host memory)."""
        def invoke_phase(s, f, a, b, args):
            masks, states, valid, slot_f, slot_a, slot_b, slot_occ, \
                overflow, att = args
            slot_f = slot_f.at[s].set(f)
            slot_a = slot_a.at[s].set(a)
            slot_b = slot_b.at[s].set(b)
            slot_occ = slot_occ.at[s].set(True)
            # stage A: linearize just the new op
            legal, nstate = step(states, f, a, b)
            bv = BITMAT[s]
            cvalid = valid & legal & ~has_bit(masks, bv)
            all_masks = jnp.concatenate([masks, masks | bv[None, :]])
            all_states = jnp.concatenate([states, nstate])
            all_valid = jnp.concatenate([valid, cvalid])
            origin = jnp.concatenate(
                [jnp.zeros(F, jnp.bool_), jnp.ones(F, jnp.bool_)])
            masks, states, valid, new, _, ovf, mism = dedup(
                all_masks, all_states, all_valid, origin)
            overflow = overflow | ovf
            att = att + mism
            # stage B: chase enabled chains
            masks, states, valid, overflow, att = expand_full(
                masks, states, valid, new, slot_f, slot_a, slot_b,
                slot_occ, overflow, att)
            return masks, states, valid, slot_f, slot_a, slot_b, \
                slot_occ, overflow, att

        def cond(c):
            return (c[0] < stop) & (c[10] > 0)

        def body(c):
            (e, masks, states, valid, slot_f, slot_a, slot_b, slot_occ,
             overflow, att, count, max_count) = c
            row = x[e]
            rm = lax.bitcast_convert_type(row[:W], u32)        # (W,)
            s, f, a, b = row[W], row[W + 1], row[W + 2], row[W + 3]
            # completion phase: survivors linearized every returned op.
            # No dedup needed: clearing set bits is injective on masks,
            # so distinct surviving configs stay distinct; closure is
            # preserved, so no re-expansion either. rm == 0 is a no-op.
            have = ((masks & rm[None, :]) == rm[None, :]).all(axis=1)
            valid = valid & have
            masks = masks & ~rm[None, :]
            slot_occ = slot_occ & ~(BITMAT & rm[None, :]) \
                .astype(jnp.bool_).any(axis=1)
            (masks, states, valid, slot_f, slot_a, slot_b, slot_occ,
             overflow, att) = lax.cond(
                s >= 0,
                lambda args: invoke_phase(s, f, a, b, args),
                lambda args: args,
                (masks, states, valid, slot_f, slot_a, slot_b, slot_occ,
                 overflow, att))
            if use_attest:
                # ABFT frontier invariant: a valid configuration may
                # only hold pending bits of OCCUPIED slots (completion
                # clears freed slots from every mask; invoke occupies
                # before setting). A bit-flip in masks/valid/slot_occ
                # violates this with high probability; residues sum
                # into att and are checked host-side at chunk
                # boundaries. Cost: one (F, W) mask op per step.
                occw = jnp.sum(
                    jnp.where(slot_occ[:, None], BITMAT,
                              jnp.zeros_like(BITMAT)), axis=0)   # (W,)
                bad = valid & ((masks & ~occw[None, :]) != 0).any(axis=1)
                att = att + bad.sum().astype(i32)
            count = valid.sum().astype(i32)
            return (e + 1, masks, states, valid, slot_f, slot_a, slot_b,
                    slot_occ, overflow, att, count,
                    jnp.maximum(max_count, count))

        return lax.while_loop(cond, body, carry)

    def make_check(x, n_steps, init_state):
        return summarize(run_range(x, n_steps, init_carry(init_state)))

    @jax.jit
    def check(x, n_steps, init_state):
        return make_check(x, n_steps, init_state)

    @jax.jit
    def check_batch(x, n_steps, init_state):
        return jax.vmap(make_check)(x, n_steps, init_state)

    @jax.jit
    def check_chunk(x, stop, carry):
        return run_range(x, stop, carry)

    @jax.jit
    def check_chunk_batch(x, stops, carry):
        return jax.vmap(run_range)(x, stops, carry)

    @jax.jit
    def check_stream_chunk(x, n, carry):
        # Streaming entry: x holds only THIS chunk's steps, so the
        # carry's absolute event count is rebased to 0 for the range
        # walk and restored afterwards — a growing history streams as
        # fixed-shape chunks through ONE compiled kernel, shipping each
        # step exactly once (the whole-x chunk API re-ships the prefix).
        local = (i32(0),) + tuple(carry[1:])
        out = run_range(x, n, local)
        return (out[0] + carry[0],) + tuple(out[1:])

    k = Kernel(check, check_batch, check_chunk, check_chunk_batch,
               check_stream_chunk, init_carry, summarize,
               _mk_digest())
    _M_COMPILE.labels(family="sort", stage="build").observe(
        _time.monotonic() - t_build)
    return k


# ---------------------------------------------------------------------------
# Dense reachable-set kernel (symbolic model checking on device)
# ---------------------------------------------------------------------------
#
# When the model's state count S and the slot count P are small enough
# that S * 2^P fits in device memory, the *entire* configuration space
# fits a dense boolean table T[state, pending-mask]. Every history entry
# is then a vectorized transform of the whole table:
#
#   * linearizing pending op p from (s, m) reaches (step(s), m | bit_p):
#     a tiny SxS boolean "transition matmul" over the state axis composed
#     with a bit-set gather along the mask axis — for ALL P pending slots
#     at once, as one batched (P, S, C) op, iterated to fixpoint;
#   * an :ok return keeps configs holding the op's bit and clears it —
#     a pure gather;
#   * the history is linearizable iff the table is ever nonempty after
#     the last entry.
#
# No sort, no frontier cap, no overflow, no escalation: verdicts are
# EXACT. The sort-frontier kernel above remains the fallback for
# histories whose peak pending-op count P makes 2^P infeasible. This is
# the idiomatic TPU shape for WGL search: the pending-subset powerset
# that explodes knossos (`checker.clj:213-216`) becomes the lane axis.

DENSE_TABLE_CAP = 1 << 22   # max S * 2^P bools held as the dense table


def _dense_kernel(model_name: str, s_lo: int, S: int, P: int, E: int,
                  pallas=None):
    """Build the jitted dense-table checker for S states x P slots x
    E entry capacity. Same call shapes as the sort kernel.

    The Pallas-vs-XLA closure choice is resolved HERE, outside the
    cache, so flipping JEPSEN_TPU_PALLAS_CLOSURE (or a checker's
    pallas= option) mid-process takes effect on the next call instead
    of being baked into a cached kernel."""
    use_pallas, on_tpu = _pallas_enabled(PALLAS_CLOSURE_ENV, pallas)
    return _dense_kernel_cached(model_name, s_lo, S, P, E,
                                use_pallas, on_tpu, attest_enabled())


def _clear_dense_caches():
    """Reset every cache that baked in a dense-kernel build decision
    (tests reach through the _dense_kernel wrapper for this)."""
    _dense_kernel_cached.cache_clear()
    _sharded_runner_cached.cache_clear()


_dense_kernel.cache_clear = _clear_dense_caches


@functools.lru_cache(maxsize=32)
def _dense_kernel_cached(model_name: str, s_lo: int, S: int, P: int,
                         E: int, use_pallas: bool, on_tpu: bool,
                         use_attest: bool = True):
    # miss-only build timing — see the sort kernel's twin comment
    t_build = _time.monotonic()
    import jax
    import jax.numpy as jnp
    from jax import lax

    step = DEVICE_MODELS[model_name].step
    C = 1 << P
    i32 = jnp.int32
    f32 = jnp.float32
    s_vals = s_lo + np.arange(S, dtype=np.int32)           # (S,)
    cols = np.arange(C, dtype=np.int32)                    # (C,)

    S_VALS = jnp.asarray(s_vals)
    COLS = jnp.asarray(cols)
    ARANGE_P = jnp.arange(P)

    # Pallas fused closure round: ON by default on real TPU hardware
    # (2x on the easy 10k headline, 6x on the adversarial P=14 shape —
    # the (P, S, C) intermediates never leave VMEM), opt-in elsewhere
    # (interpret mode keeps it testable on CPU), opt-out via
    # JEPSEN_TPU_PALLAS_CLOSURE=0 (resolved by the _dense_kernel
    # wrapper). Shapes past the VMEM gate fall back to the XLA
    # formulation below.
    image = DEVICE_MODELS[model_name].image
    pallas_round = None
    if use_pallas and image is None:
        from . import wgl_pallas
        if wgl_pallas.eligible(S, P):
            pallas_round = wgl_pallas.closure_round_fn(
                S, P, interpret=not on_tpu)
    # With an image the table is held as S x W uint32 words, W = C / L:
    # configuration c = j * W + w is bit j of word w, so the low log2(W)
    # slot bits index words and the rest bits within a word, and each
    # pass over the table moves 32 configurations a lane.
    W = max(1, C // 32)
    L = C // W
    LOGW = W.bit_length() - 1
    u32 = jnp.uint32
    JS = jnp.arange(L, dtype=u32)
    KEEP = [u32(sum(1 << j for j in range(L) if not j >> q & 1))
            for q in range(L.bit_length() - 1)]   # bits with bit q clear

    def pack(cols):
        """A (C,) bool column mask as its (W,) words."""
        return jnp.sum(cols.reshape(L, W).astype(u32) << JS[:, None],
                       axis=0, dtype=u32)

    def across(x, p, up):
        """Configurations moved across slot bit p of the word table:
        up, from c (bit clear) to c | 2^p; down, from c | 2^p to c. The
        side they leave reads empty."""
        if p < LOGW:
            b = 1 << p
            v = x.reshape(S, W // (2 * b), 2, b)
            z = jnp.zeros_like(v[:, :, :1, :])
            halves = [z, v[:, :, :1, :]] if up else [v[:, :, 1:, :], z]
            return jnp.concatenate(halves, axis=2).reshape(S, W)
        q = p - LOGW
        if up:
            return (x & KEEP[q]) << u32(1 << q)
        return (x >> u32(1 << q)) & KEEP[q]

    def clear(x, p):
        """The word table without the configurations holding bit p."""
        if p < LOGW:
            b = 1 << p
            v = x.reshape(S, W // (2 * b), 2, b)
            return jnp.concatenate([v[:, :, :1, :], jnp.zeros_like(
                v[:, :, :1, :])], axis=2).reshape(S, W)
        return x & KEEP[p - LOGW]

    def popcount(x):
        return lax.population_count(x).sum(dtype=i32)

    def closure_image(words, legal, slot_f, slot_a, slot_b):
        """The closure by each slot's image (DeviceModel.image) over the
        word table: each slot's moved rows OR-ed into the columns with
        its bit set, slot by slot, until the popcount stops growing.
        O(P * S * C / 32) a round."""

        def wbody(c):
            tb, cnt, _ = c
            for p in range(P):
                tb = tb | across(image(tb, legal[p], slot_f[p], slot_a[p],
                                       slot_b[p]), p, up=True)
            return tb, popcount(tb), cnt

        words, _, _ = lax.while_loop(
            lambda c: c[1] != c[2], wbody,
            (words, popcount(words), i32(-1)))
        return words

    def closure(table, slot_f, slot_a, slot_b, slot_occ):
        """Close the table under linearization of every occupied slot."""
        legal, new = step(S_VALS[None, :], slot_f[:, None],
                          slot_a[:, None], slot_b[:, None])     # (P, S)
        legal = legal & slot_occ[:, None]
        if image is not None:
            return closure_image(table, legal, slot_f, slot_a, slot_b)
        # M[p, s, s2]: linearizing slot p moves state s to s2
        M = (legal[:, :, None]
             & (new[:, :, None] == S_VALS[None, None, :]))      # (P,S,S2)
        Mf = M.astype(f32)

        if pallas_round is not None:
            # fused VMEM round (default on TPU): transition product +
            # butterfly + OR-accumulate in one kernel, no HBM
            # intermediates
            MfT = jnp.swapaxes(Mf, 1, 2)

            def pcond(c):
                _tb, cnt, prev = c
                return cnt != prev

            def pbody(c):
                tb, cnt, _ = c
                tb = pallas_round(tb, MfT)
                return tb, tb.sum().astype(i32), cnt

            tbf, _, _ = lax.while_loop(
                pcond, pbody,
                (table.astype(f32), table.sum().astype(i32), i32(-1)))
            return tbf > 0

        # fixpoint: iterate while the popcount grows. M (the P x S x S
        # transition tensor) is computed once per invoke above, outside
        # the loop — XLA hoists it as a loop constant; only the table
        # changes per round.
        def wcond(c):
            tb, cnt, prev = c
            return cnt != prev

        def wbody(c):
            tb, cnt, _ = c
            moved = jnp.einsum("psq,sc->pqc", Mf,
                               tb.astype(f32)) > 0               # (P,S2,C)
            # destination (s2, c | bit_p) comes from source col c (bit_p
            # clear): a butterfly along the mask axis — per-p static
            # reshape + concat, which XLA lowers as layout moves instead
            # of the lane gather take_along_axis would emit
            for p in range(P):
                b = 1 << p
                m = moved[p].reshape(S, C // (2 * b), 2, b)
                cand = jnp.concatenate(
                    [jnp.zeros_like(m[:, :, :1, :]), m[:, :, :1, :]],
                    axis=2)
                tb = tb | cand.reshape(S, C)
            return tb, tb.sum().astype(i32), cnt

        table, _, _ = lax.while_loop(
            wcond, wbody,
            (table, table.sum().astype(i32), i32(-1)))
        return table

    def init_carry(init_state):
        # carry layout: (e, table, slot_f, slot_a, slot_b, slot_occ,
        # att, count, max_count) — att is the ABFT attestation
        # accumulator (see the sort kernel's twin): table-occupancy
        # invariant residues sum into it and it must read 0 on host
        # at every chunk boundary (abft.verify_carry). The table is
        # (S, C) bool, or (S, W) words with an image.
        if image is not None:
            table = jnp.zeros((S, W), u32).at[init_state - s_lo, 0].set(1)
        else:
            table = jnp.zeros((S, C), jnp.bool_)
            table = table.at[init_state - s_lo, 0].set(True)
        return (i32(0), table,
                jnp.zeros((P,), i32), jnp.full((P,), NIL, i32),
                jnp.full((P,), NIL, i32), jnp.zeros((P,), jnp.bool_),
                i32(0), i32(1), i32(1))

    def summarize(carry):
        # att as the 5th output — see the sort kernel's twin
        (e, table, _sf, _sa, _sb, _occ, att, count,
         max_count) = carry
        ok = count > 0
        death = jnp.where(ok, i32(-1), e - 1)
        # the dense table never drops configurations: overflow is
        # impossible and every verdict is exact
        return ok, death, jnp.bool_(False), max_count, att

    def run_range(x, stop, carry):
        def invoke_phase(s, f, a, b, args):
            table, slot_f, slot_a, slot_b, slot_occ = args
            slot_f = slot_f.at[s].set(f)
            slot_a = slot_a.at[s].set(a)
            slot_b = slot_b.at[s].set(b)
            slot_occ = slot_occ.at[s].set(True)
            table = closure(table, slot_f, slot_a, slot_b, slot_occ)
            return table, slot_f, slot_a, slot_b, slot_occ

        def cond(c):
            return (c[0] < stop) & (c[7] > 0)

        def body(c):
            (e, table, slot_f, slot_a, slot_b, slot_occ, att, count,
             maxc) = c
            row = x[e]
            # the dense table caps P well below 31, so the completion
            # mask fits a non-negative int32 — no bitcast needed
            rm = row[0]
            s, f, a, b = row[1], row[2], row[3], row[4]
            # completion phase: survivors hold every returned bit; the
            # new config is the same mask with them cleared (injective:
            # no dedup, and closure is preserved, so no re-expansion).
            # table'[c] = table[c | rm] iff c ∩ rm = ∅; rm = 0 is the
            # identity gather. A release step (F_RELEASE) keeps the
            # configurations that did not take the released ops as well:
            # table'[c] = table[c] | table[c | rm], still closed.
            release = (s < 0) & (f == F_RELEASE)
            if image is not None:
                # the same bit by bit: each returned bit moves its
                # configurations down and empties the columns it was in
                done, kept = table, table
                for p in range(P):
                    hit = ((rm >> p) & 1) == 1
                    done = jnp.where(hit, across(done, p, up=False), done)
                    kept = jnp.where(hit, clear(kept, p), kept)
                table = jnp.where(release, kept | done, done)
            else:
                done = jnp.take(table, COLS | rm, axis=1)
                table = jnp.where(release, table | done,
                                  done) & ((COLS & rm) == 0)[None, :]
            slot_occ = slot_occ & \
                ~((rm >> ARANGE_P) & 1).astype(jnp.bool_)
            table, slot_f, slot_a, slot_b, slot_occ = lax.cond(
                s >= 0,
                lambda args: invoke_phase(s, f, a, b, args),
                lambda args: args,
                (table, slot_f, slot_a, slot_b, slot_occ))
            if use_attest:
                # ABFT table invariant: a configuration column whose
                # mask holds a bit of an UNOCCUPIED slot is
                # unreachable (completions gather those columns away;
                # the closure only sets occupied bits) — any true cell
                # there is a bit-flip. Cost: one (S, C) mask-and-sum
                # per step, the same shape as the count reduction.
                occ_bits = jnp.sum(
                    jnp.where(slot_occ, 1 << ARANGE_P,
                              jnp.zeros_like(ARANGE_P)),
                    dtype=i32)
                badc = (COLS & ~occ_bits) != 0                  # (C,)
                if image is not None:
                    att = att + popcount(table & pack(badc)[None, :])
                else:
                    att = att + jnp.sum(table & badc[None, :], dtype=i32)
            count = popcount(table) if image is not None \
                else table.sum().astype(i32)
            return (e + 1, table, slot_f, slot_a, slot_b, slot_occ,
                    att, count, jnp.maximum(maxc, count))

        return lax.while_loop(cond, body, carry)

    def make_check(x, n_steps, init_state):
        return summarize(run_range(x, n_steps, init_carry(init_state)))

    @jax.jit
    def check(x, n_steps, init_state):
        return make_check(x, n_steps, init_state)

    @jax.jit
    def check_batch(x, n_steps, init_state):
        return jax.vmap(make_check)(x, n_steps, init_state)

    @jax.jit
    def check_chunk(x, stop, carry):
        return run_range(x, stop, carry)

    @jax.jit
    def check_chunk_batch(x, stops, carry):
        return jax.vmap(run_range)(x, stops, carry)

    @jax.jit
    def check_stream_chunk(x, n, carry):
        # streaming rebase — see the sort kernel's twin for the contract
        local = (i32(0),) + tuple(carry[1:])
        out = run_range(x, n, local)
        return (out[0] + carry[0],) + tuple(out[1:])

    k = Kernel(check, check_batch, check_chunk, check_chunk_batch,
               check_stream_chunk, init_carry, summarize,
               _mk_digest())
    _M_COMPILE.labels(family="dense", stage="build").observe(
        _time.monotonic() - t_build)
    return k


DENSE_STATE_CAP = 512  # closure() is O(P * S^2 * C): bound S too


def _dense_shape(srange: tuple[int, int],
                 p_exact: int) -> tuple[int, int, int] | None:
    """(s_lo, S_bucketed, P_exact) if the dense table fits the caps,
    else None. S is bucketed to a power of two so histories differing
    only in value range share a compiled kernel — the padding rows are
    unreachable states and never become true."""
    lo, hi = srange
    S = hi - lo + 1
    if S > DENSE_STATE_CAP:
        return None
    S = _bucket(S, lo=4)
    if S * (1 << p_exact) <= DENSE_TABLE_CAP:
        return lo, S, p_exact
    return None


# ---------------------------------------------------------------------------
# Engine cost model: sort vs dense vs pallas variants
# ---------------------------------------------------------------------------
#
# The two kernel families are now both tunable (dense: XLA butterfly vs
# Pallas closure round; sort: XLA lex-sort vs Pallas hash dedup), so
# 'auto' picks by a small per-event work model instead of
# "dense-whenever-it-fits". Units are abstract element-ops with a
# single cross-family constant (MXU_ADVANTAGE) for work the MXU eats;
# the constants are calibrated against the r05 hardware numbers
# (dense 2-6x over sort on the small-S register shapes) and exposed
# here so a future hardware round can re-fit them in one place.

MXU_ADVANTAGE = 256     # batched-matmul element-ops per VPU-op
CLOSURE_ROUNDS = 2      # typical stage-B fixpoint depth per invoke
HASH_PROBE_COST = 6     # serial probe+claim cost per candidate key
DENSE_EXACT_BIAS = 8.0  # dense verdicts are exact (no frontier, no
#                         escalation re-runs): prefer dense until its
#                         modeled cost exceeds the sort family's by
#                         this factor


@dataclasses.dataclass(frozen=True)
class EngineDecision:
    """A resolved engine choice for one kernel shape."""
    family: str                 # 'dense' | 'sort'
    dense: tuple | None         # (s_lo, S, P) when family == 'dense'
    dedup: str                  # DEDUP_* (sort family's dedup engine)
    reason: str
    costs: dict                 # modeled per-history element-ops
    # measured per-history device-seconds per compared variant, when a
    # ready calibration priced the decision (see jepsen_tpu.calibrate)
    seconds: dict | None = None


def engine_variant(dec: "EngineDecision") -> str:
    """The calibration variant a decision actually runs: 'dense', or
    the sort family at its resolved dedup engine ('hash' for the
    Pallas kernel, 'sort' for the XLA lex-sort)."""
    if dec.family == "dense":
        return "dense"
    return "hash" if dec.dedup == DEDUP_PALLAS else "sort"


def engine_cost(dec: "EngineDecision") -> float:
    """The chosen engine's modeled element-ops — the single place the
    family/dedup -> costs-key mapping lives (the screen's escalation
    pricing and the service's chunk budget both use it)."""
    return float(dec.costs.get(engine_variant(dec)) or 0.0)


def _family_costs(S: int, p_dense: int, p_sort: int, F: int,
                  n_events: int) -> dict:
    """Modeled total element-ops per engine variant for a history of
    n_events over S states and an F frontier. The two families run at
    DIFFERENT slot counts — the dense table is exact-P (2^p_dense
    wide) while the sort kernel buckets its slots up (p_sort) — so
    each row is priced at the count its kernel actually runs."""
    n = max(int(n_events), 1)
    C = 1 << min(p_dense, 31)
    K = F * (1 + p_sort)                  # stage-B dedup candidates
    W = max(1, (p_sort + 31) // 32)
    # dense: per invoke, CLOSURE_ROUNDS of the (P,S,S)x(S,C) product
    # (MXU) + the butterfly OR-accumulate over the table (VPU); plus
    # the one-off table allocation/init
    dense = n * CLOSURE_ROUNDS * (p_dense * S * S * C / MXU_ADVANTAGE
                                  + S * C) + S * C
    # sort family: per invoke, one lex sort of K rows on (W+2) lanes
    srt = n * (W + 2) * K * max(np.log2(K), 1.0)
    # hash dedup: per invoke, one serial probe pass over K keys
    hsh = n * HASH_PROBE_COST * K
    return {"dense": dense, "sort": srt, "hash": hsh}


def _note_engine(dec: "EngineDecision", reason: str) -> "EngineDecision":
    """Count a select_engine outcome. `reason` is the COARSE bucket
    (forced | slot-cap | dense-caps | image | cost-model | calibrated) — the
    free-text dec.reason would blow up label cardinality. Also accumulates the
    chosen engine's modeled element-ops, so rate(elementops)/rate(
    chunk_seconds) is the pipeline's modeled throughput."""
    _M_ENGINE.labels(family=dec.family, dedup=dec.dedup,
                     reason=reason).inc()
    cost = engine_cost(dec)
    if cost:
        _M_ELEMENTOPS.labels(family=dec.family).inc(cost)
    return dec


def select_engine(srange: tuple[int, int], p_exact: int, n_events: int,
                  *, model: str, slots: int | None = None,
                  frontier: int = 256, engine: str = "auto",
                  dense_slot_cap: int | None = None, pallas=None,
                  calibration=None) -> EngineDecision:
    """Pick the kernel family (and the sort family's dedup engine) for
    one history shape of the device model `model` (a DEVICE_MODELS
    name). engine='dense'/'sort' force a family ('dense'
    raises _dense_caps_error when the table cannot fit, the offline
    contract); 'auto' runs the cost model. dense_slot_cap bounds the
    slot count the dense table may be asked to absorb (each slot
    doubles the table; a checker that knows its histories' tail
    concurrency can cap the blowup early). pallas=True/False forces
    the Pallas variants on/off (None = env gate / backend default).

    calibration: a `jepsen_tpu.calibrate.Calibration` (None = the
    process-wide active one, usually nothing). When it holds trusted
    measured coefficients for BOTH compared variants, the dense-vs-
    sort comparison runs in measured device-seconds instead of raw
    modeled element-ops — the same DENSE_EXACT_BIAS preference for
    exact verdicts, applied to ground truth.

    A model with a DeviceModel.image takes the dense family wherever
    its table fits: the sort family's frontier would have to hold what
    the table holds. Its dense cost is the transition product's, which
    bounds the image closure's from above."""
    if engine not in ("auto", "dense", "sort"):
        raise ValueError(f"unknown WGL engine {engine!r}")
    if slots is None:
        slots = _bucket(p_exact, lo=8)
    S = _bucket(srange[1] - srange[0] + 1, lo=4)
    costs = _family_costs(S, p_exact, slots, frontier, n_events)
    dedup = dedup_engine(frontier, slots, _pack_params(srange, slots),
                         pallas)
    # the sort family's modeled cost is whichever dedup it will
    # actually run at this shape — the kernel never mixes engines
    sort_variant = "hash" if dedup == DEDUP_PALLAS else "sort"
    sort_cost = costs[sort_variant]
    cal = calibration if calibration is not None \
        else _calibrate.active()
    seconds = None
    if cal is not None and cal.ready("dense", sort_variant):
        seconds = {
            "dense": cal.seconds("dense", costs["dense"]),
            sort_variant: cal.seconds(sort_variant, sort_cost)}
    dense = None
    if engine in ("auto", "dense"):
        if dense_slot_cap is not None and p_exact > dense_slot_cap:
            if engine == "dense":
                raise ValueError(
                    f"dense engine requested but the history needs "
                    f"{p_exact} slots, over dense_slot_cap="
                    f"{dense_slot_cap}")
            return _note_engine(EngineDecision(
                "sort", None, dedup,
                f"p={p_exact} over dense_slot_cap={dense_slot_cap}",
                costs), "slot-cap")
        dense = _dense_shape(srange, p_exact)
        if dense is None and engine == "dense":
            raise _dense_caps_error(srange, p_exact)
    if engine == "sort" or dense is None:
        why = ("forced" if engine == "sort"
               else f"S={S} x 2^{p_exact} exceeds the dense caps")
        return _note_engine(
            EngineDecision("sort", None, dedup, why, costs, seconds),
            "forced" if engine == "sort" else "dense-caps")
    if DEVICE_MODELS[model].image is not None:
        # the sort family's frontier would have to hold S x 2^P
        # configurations, and escalates towards max_frontier where it
        # does not; the table decides exactly
        return _note_engine(EngineDecision(
            "dense", dense, DEDUP_NONE,
            f"S={S}: the dense closure by image", costs, seconds),
            "forced" if engine == "dense" else "image")
    if seconds is not None:
        # measured comparison: same exactness bias, ground-truth units
        dense_v, sort_v = seconds["dense"], seconds[sort_variant]
        if engine == "dense" or dense_v <= DENSE_EXACT_BIAS * sort_v:
            why = ("forced" if engine == "dense" else
                   f"measured dense {dense_v:.3g}s <= "
                   f"{DENSE_EXACT_BIAS:g}x {dedup} {sort_v:.3g}s")
            return _note_engine(
                EngineDecision("dense", dense, DEDUP_NONE, why, costs,
                               seconds),
                "forced" if engine == "dense" else "calibrated")
        return _note_engine(EngineDecision(
            "sort", None, dedup,
            f"measured dense {dense_v:.3g}s > {DENSE_EXACT_BIAS:g}x "
            f"{dedup} {sort_v:.3g}s", costs, seconds), "calibrated")
    if engine == "dense" or \
            costs["dense"] <= DENSE_EXACT_BIAS * sort_cost:
        why = ("forced" if engine == "dense" else
               f"dense {costs['dense']:.3g} <= {DENSE_EXACT_BIAS:g}x "
               f"{dedup} {sort_cost:.3g}")
        return _note_engine(
            EngineDecision("dense", dense, DEDUP_NONE, why, costs),
            "forced" if engine == "dense" else "cost-model")
    return _note_engine(EngineDecision(
        "sort", None, dedup,
        f"dense {costs['dense']:.3g} > {DENSE_EXACT_BIAS:g}x "
        f"{dedup} {sort_cost:.3g}", costs), "cost-model")


# ---------------------------------------------------------------------------
# Device-fault recovery ladder (shared by every device-checking entry)
# ---------------------------------------------------------------------------
#
# A backend failure mid-check used to be terminal: check_safe mapped the
# RuntimeError to {'valid?': 'unknown', 'degraded': True} and the run
# lost its verdict. Every public entry below now runs under a ladder
# instead — detect cheaply (classify_backend_error), recover from the
# last good state, re-verify only what's lost (the GCN-ABFT / A-QED
# posture, PAPERS.md):
#
#   oom          shrink the device working set (halve chunk_entries;
#                under 'auto', re-select the engine with dense_slot_cap
#                0, i.e. the sort family — the dense table is the
#                memory hog) — batch entries instead SPLIT a dispatch
#                of more than one key in half (the batch entry its
#                group, the sharded entry its batch) and recover each
#                half independently
#   device-lost  one backend re-init (jax.clear_caches + drop this
#                module's kernel LRUs, whose jitted fns hold
#                executables bound to the lost device), then retry
#   compile      retry without the Pallas kernel variants (the usual
#                compile-failure source is a Mosaic rejection)
#   wedged       plain bounded retry (includes watchdog'd syncs and any
#                backend error the classifier can't place)
#
# and when the budget is spent, the FINAL rung decides on the host
# mirror (exact, slow) for histories under HOST_FALLBACK_MAX_OPS
# instead of reporting unknown. Results that went through the ladder
# carry a 'recovered' trail; only a ladder that fell off the bottom
# reports 'degraded'.

MAX_RECOVERY_RETRIES = 3
HOST_FALLBACK_MAX_OPS = 20_000


class _RecoveryTrail:
    """Bookkeeping for one checking entry's ladder: classify each
    backend fault, enforce the retry budget, back off with
    control.retry's decorrelated jitter between attempts, and stamp
    the 'recovered' trail on the eventual result. Exceptions the
    classifier rejects re-raise immediately — a checker bug must never
    look like a device fault."""

    def __init__(self, max_retries: int | None = None):
        self.max = (MAX_RECOVERY_RETRIES if max_retries is None
                    else max(0, int(max_retries)))
        self.faults: list[str] = []
        self._delays = None

    def absorb(self, exc: BaseException, site: str) -> bool:
        """Record exc's bucket; True when another retry is allowed
        (after the backoff sleep), False when the budget is spent and
        the caller must take the final rung."""
        kind = classify_backend_error(exc)
        if kind is None:
            raise exc
        self.faults.append(kind)
        _M_RUNGS.labels(kind=kind, site=site).inc()
        if len(self.faults) > self.max:
            log.warning("%s: %s fault after %d recovery retries; "
                        "taking the final rung (%s)", site, kind,
                        self.max, exc)
            return False
        if self._delays is None:
            from ..control.retry import backoff
            self._delays = backoff()
        delay = next(self._delays)
        log.warning("%s: %s fault (%s); recovering, retry %d/%d in "
                    "%.2fs", site, kind, exc, len(self.faults),
                    self.max, delay)
        _time.sleep(delay)
        return True

    def stamp(self, result) -> None:
        """Mark a decided result as recovered (no-op when the entry
        never faulted)."""
        if self.faults and isinstance(result, dict):
            result["recovered"] = {"faults": list(self.faults),
                                   "retries": len(self.faults)}


def _apply_recovery_rung(kind: str, kw: dict) -> None:
    """Mutate a retry's kwargs per the fault bucket (only the knobs the
    entry actually accepts — `kw` is the exact kwargs of the next
    attempt)."""
    if kind == FAULT_OOM:
        if "chunk_entries" in kw:
            kw["chunk_entries"] = max(
                256, int(kw["chunk_entries"] or 4096) // 2)
        if kw.get("engine") != "dense":
            # re-run select_engine under the tightest dense_slot_cap:
            # every slot doubles the dense table, so cap 0 routes the
            # retry to the sort family (a forced 'dense' keeps its
            # contract and relies on the other rungs / the final rung)
            kw["dense_slot_cap"] = 0
    elif kind == FAULT_DEVICE_LOST:
        _device_reinit()
    elif kind == FAULT_COMPILE:
        kw["pallas"] = False
    # FAULT_CORRUPT (an ABFT attestation mismatch) needs no knob
    # mutation: the retry re-stages every device buffer from canonical
    # host data, which IS the rung — like FAULT_WEDGED, a plain
    # bounded retry


def _device_reinit() -> None:
    """The device-lost rung: drop jax's executable caches AND this
    module's kernel LRUs — their jitted fns hold compiled executables
    bound to the lost device — so the retry rebuilds device state
    from scratch."""
    backend_reinit()
    _clear_sort_caches()
    _clear_dense_caches()


def _final_rung(model, hist, trail: _RecoveryTrail,
                exc: BaseException, budget_s: float | None = None,
                cancel=None) -> dict:
    """The ladder's last rung: the host mirror decides histories under
    HOST_FALLBACK_MAX_OPS (exact, device-free); longer ones report a
    degraded 'unknown' carrying the fault trail — still strictly more
    informative than the old blanket degradation."""
    h = as_history(hist)
    if len(h) <= HOST_FALLBACK_MAX_OPS:
        from .linear import analysis_host
        a = analysis_host(model, h, budget_s=budget_s, cancel=cancel)
        a["analyzer"] = "host-jit-linear (backend-fault fallback)"
        trail.stamp(a)
        a["recovered"]["fallback"] = "host"
        return a
    return {
        "valid?": "unknown", "analyzer": "tpu-wgl", "degraded": True,
        "op-count": len(h),
        "error": (f"backend faults exhausted the recovery budget "
                  f"(trail: {trail.faults}) and the history exceeds "
                  f"the {HOST_FALLBACK_MAX_OPS}-op host-fallback cap; "
                  f"last fault: {exc}"),
        "recovery-failed": {"faults": list(trail.faults),
                            "retries": trail.max},
        "configs": [], "final-paths": [],
    }


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def encode_ops_for_model(model, hist) -> OpArray:
    """Encode a history with the model's value codec, honoring the model's
    rules about which pending ops are droppable. Raises ValueError when
    the history exceeds the device encoding (checkers fall back to the
    host model)."""
    name = model.device_model
    if name is None or name not in DEVICE_MODELS:
        raise ValueError(f"model {model!r} has no device form")
    dm = DEVICE_MODELS[name]
    try:
        ops = encode_ops(as_history(hist), dm.codec, dm.droppable)
    except OverflowError as e:   # value outside int32
        raise DeviceEncodingError(str(e)) from e
    if dm.validate is not None:
        dm.validate(ops, model)
    return ops


def analysis_tpu(model, hist, frontier: int = 256, slots: int | None = None,
                 max_frontier: int = 65536,
                 chunk_entries: int = 4096,
                 budget_s: float | None = None,
                 cancel=None,
                 explain: bool = True,
                 slot_overflow_fallback: bool = True,
                 engine: str = "auto",
                 dense_slot_cap: int | None = None,
                 pallas=None,
                 max_recovery_retries: int | None = None) -> dict:
    """Check one history on the device. Its crashed ops are checked in
    rounds of deadlines, as the batch path checks a key's (see
    _decide_in_rounds): first each may take effect only until the next
    crash of its kind is invoked, which keeps the dense table as narrow
    as the crashes that can still land, and only a history that then
    fails past its first deadline is checked again. Every verdict is
    exact. budget_s and cancel cover all rounds together.

    Each round's search runs under the device-fault recovery ladder
    (see _search): a classified backend fault (oom / device-lost /
    compile / wedged) re-runs the search down the appropriate rung
    instead of surfacing as a degraded 'unknown', and a decided result
    that went through the ladder reports its 'recovered' trail.
    max_recovery_retries bounds the ladder (None = MAX_RECOVERY_RETRIES)
    over all rounds; past it, histories under HOST_FALLBACK_MAX_OPS are
    decided on the host mirror, whose verdict is the history's own and
    ends the rounds.

    explain: on a definite invalid verdict, re-run the host oracle on
    the prefix ending at the culprit op to reconstruct configs and
    final-paths (the reference renders these via knossos.linear.report,
    `checker.clj:205-216`). See _analysis_tpu_once for the search itself
    and the remaining knobs."""
    t0 = _time.monotonic()
    kw = dict(frontier=frontier, slots=slots, max_frontier=max_frontier,
              chunk_entries=chunk_entries, cancel=cancel,
              slot_overflow_fallback=slot_overflow_fallback,
              engine=engine, dense_slot_cap=dense_slot_cap,
              pallas=pallas)
    trail = _RecoveryTrail(max_recovery_retries)
    final = None   # the final rung's verdict, the history's own

    def remaining():
        if budget_s is None:
            return None
        return max(0.0, budget_s - (_time.monotonic() - t0))

    with _telemetry.section("wgl.encode"):
        pre = encode_ops_for_model(model, hist)
    rounds = 0

    def run(_keys, sent):
        nonlocal rounds, final
        rounds += 1
        if rounds > 1 and (remaining() == 0.0 or
                           (cancel is not None and cancel())):
            return [{"valid?": "unknown", "analyzer": "tpu-wgl",
                     "error": "budget exhausted/cancelled before "
                              "the history's next round",
                     "configs": [], "final-paths": []}]
        if final is None:
            a, last = _search(model, hist, sent[0], trail, kw, remaining)
            if not last:
                return [a]
            final = a
        return [final]

    a, = _decide_in_rounds([pre], run, DEVICE_MODELS[model.device_model],
                           deadlines=engine != "sort", site="offline")
    a["duration-ms"] = (_time.monotonic() - t0) * 1e3
    if explain:
        _explain(model, hist, a)
    return a


def _search(model, hist, ops: OpArray, trail: _RecoveryTrail, kw: dict,
            remaining) -> tuple[dict, bool]:
    """One round's search: check `ops`, a round's encoding of `hist`,
    in one _analysis_tpu_once call under the recovery ladder (see the
    ladder comment above). A classified backend fault re-runs the
    search down its rung, mutating `kw`; once `trail` is spent the
    final rung decides `hist` itself. remaining() is the budget left.
    Returns the result and whether the final rung gave it."""
    while True:
        try:
            a = _analysis_tpu_once(model, hist, ops, budget_s=remaining(),
                                   **kw)
        except RuntimeError as e:
            if trail.absorb(e, "offline"):
                _apply_recovery_rung(trail.faults[-1], kw)
                continue
            return _final_rung(model, hist, trail, e, budget_s=remaining(),
                               cancel=kw["cancel"]), True
        trail.stamp(a)
        return a, False


def _explain(model, hist, a: dict) -> None:
    """Give a definite invalid device verdict the host oracle's configs
    and final-paths, from the prefix ending at its culprit op."""
    if a["valid?"] is False and "op-index" in a \
            and a["analyzer"] in ("tpu-wgl", "tpu-wgl-dense"):
        from .linear import explain_failure
        with _telemetry.section("wgl.explain"):
            ex = explain_failure(model, hist, a["op-index"])
        if ex is not None:
            a["configs"] = ex["configs"]
            a["final-paths"] = ex["final-paths"]
            if ex.get("previous-ok") is not None:
                a["previous-ok"] = ex["previous-ok"]


def _analysis_tpu_once(model, hist, ops: OpArray, frontier: int = 256,
                       slots: int | None = None,
                       max_frontier: int = 65536,
                       chunk_entries: int = 4096,
                       budget_s: float | None = None,
                       cancel=None,
                       slot_overflow_fallback: bool = True,
                       engine: str = "auto",
                       dense_slot_cap: int | None = None,
                       pallas=None) -> dict:
    """Check `ops`, one round's encoding of `hist`, on the device. The
    slot count is sized to the ops' actual peak concurrency (crashed ops
    pend to their deadline, or forever on the sort family, which has no
    release step); long histories run as a sequence
    of bounded-duration chunked kernel calls with the frontier carried
    (and checkpointable) between them, so a 100k-op search never holds
    the device in one multi-minute call. Escalates the frontier on
    overflow-with-invalid (a dropped config could have been the
    witness); falls back to the host search past 256 slots.

    budget_s caps total wall time: past it, an undecided search returns
    'unknown' instead of escalating further (histories with many
    crashed mutating ops are genuinely exponential — the reference's
    checker hits the same wall as an OOM or its 1 h timeout).

    cancel: zero-arg callable polled between chunks — truthy stops the
    search with 'unknown' (competition racing).

    engine: 'auto' picks by the cost model (see select_engine) over
    the dense reachable-set kernel (exact verdicts, no frontier,
    eligible when S x 2^P fits DENSE_TABLE_CAP) and the sort-frontier
    family; 'dense' / 'sort' force one. dense_slot_cap bounds the slot
    count 'auto' lets the dense table absorb; pallas=True/False forces
    the Pallas kernel variants (dense closure round, sort-family hash
    dedup) on/off, None defers to the JEPSEN_TPU_PALLAS_* env gates
    (default ON for real TPU backends).

    Latency shape: the event stream ships as ONE packed matrix (one
    host->device transfer), and histories that fit a single chunk run
    as ONE fused device call (init + search + verdict) — the
    small-history path costs two round-trips total, not a dozen. The
    kernel consumes the merged step stream (see Steps); definite
    invalid verdicts re-run the unmerged stream through the same
    compiled kernel to name the culprit op."""
    import jax
    import jax.numpy as jnp

    t0 = _time.monotonic()
    name = model.device_model
    section = _telemetry.section
    with section("wgl.encode"):
        p_exact = required_slots(ops)
        if slots is None or p_exact > slots:
            slots = _bucket(p_exact, lo=8)
        if slots <= 256:
            srange = _state_range(name, model, [ops])
            decision = select_engine(srange, p_exact, event_count(ops),
                                     model=name, slots=slots,
                                     frontier=frontier, engine=engine,
                                     dense_slot_cap=dense_slot_cap,
                                     pallas=pallas)
            if decision.dense is None:
                pending = _pending_forever(ops)
                if pending is not ops:
                    # the sort family has no release step
                    ops = pending
                    slots = max(slots, _bucket(required_slots(ops), lo=8))
    if slots > 256:
        if not slot_overflow_fallback:
            # competition racing: a parallel host thread is already
            # running this search — don't duplicate it
            return {"valid?": "unknown", "analyzer": "tpu-wgl",
                    "error": f"slot overflow ({slots} slots needed)"}
        from .linear import analysis_host
        a = analysis_host(model, hist, budget_s=budget_s, cancel=cancel)
        a["analyzer"] = "host-jit-linear (slot overflow)"
        return a
    dense = decision.dense
    if dense is not None:
        slots = dense[2]   # exact-P: the dense table is 2^P wide
    with section("wgl.steps"):
        steps = build_steps(ops, slots)
        # capacity covers the unmerged stream so the blame re-run below
        # shares this compiled kernel
        E = _bucket(max(event_count(ops), 1))
        steps = steps.pad_to(E)
    with section("wgl.stage"):
        # ABFT staged-buffer attestation: ship (possibly
        # bitflip-injected) data, then compare a device-side digest of
        # the shipped buffer with the host digest of the canonical one
        # — corruption on the staging/DMA path raises
        # CorruptDeviceResult, which the recovery ladder absorbs by
        # re-staging from the canonical host copy.
        attest_on = attest_enabled()
        x = jnp.asarray(maybe_corrupt("offline", steps.x))
        att_info = None
        if attest_on:
            from . import abft
            abft.verify_steps("offline", guarded_device_get(
                abft.digest_device(x), site="offline steps attest"),
                abft.digest_host(steps.x))
            att_info = {"steps": 1, "carry": 0}
        init_state = jnp.int32(model.device_state())
    F = frontier
    timed_out = cancelled = False
    while True:
        if dense is not None:
            k = _dense_kernel(name, dense[0], dense[1], dense[2], E,
                              pallas=pallas)
        else:
            k = _kernel(name, F, slots, E, _pack_params(srange, slots),
                        pallas=pallas)
        fam = "dense" if dense is not None else "sort"
        chunk_obs = _M_CHUNK.labels(site="offline", family=fam)
        if steps.n <= chunk_entries:
            # single fused call: init + full search + verdict
            maybe_inject_fault("offline")
            with chunk_obs.time(), \
                    section("wgl.offline.check"):
                ok, death, overflow, max_count, att = \
                    guarded_device_get(
                        k.check(x, jnp.int32(steps.n), init_state),
                        site="offline check")
            _check_att(att, "offline")
        else:
            with section("wgl.stage"):
                carry = k.init_carry(init_state)
            # Pipelined chunk loop: enqueue chunk i (dispatch is async),
            # THEN read chunk i-1's liveness flag — the device computes
            # chunk i while the host waits on the already-finished
            # flag, so the per-chunk host<->device sync overlaps with
            # compute instead of serializing after it.  Safe to
            # speculate one chunk past a death: an empty frontier stays
            # empty, and on death we discard the speculated carry.
            e = 0
            # measured-cost-model feed: modeled element-ops per step
            # entry, so each chunk's latency pairs with its share of
            # the decision's modeled cost (both linear in entries)
            cal_ops_per_entry = engine_cost(decision) / max(steps.n, 1)
            chunk_i = 0
            prev_span = 0
            while e < steps.n:
                e0 = e
                stop = min(e + chunk_entries, steps.n)
                maybe_inject_fault("offline")
                t_chunk = _time.monotonic()
                with section("wgl.offline.chunk"):
                    nxt = k.check_chunk(x, jnp.int32(stop), carry)
                    prev, carry = carry, nxt
                    e = stop
                    dead = int(guarded_device_get(
                        prev[-2], site="offline liveness")) == 0
                dt_chunk = _time.monotonic() - t_chunk
                chunk_obs.observe(dt_chunk)
                if chunk_i >= 2 and DEVICE_MODELS[name].image is None:
                    # the blocking flag read is one chunk behind, so
                    # dt_chunk measures chunk i-1: pair it with THAT
                    # chunk's op share, and start at i>=2 so chunk 0
                    # (which carries the compile) never enters the fit.
                    # An image closure's chunks would fit the dense
                    # variant's coefficient to work its price overstates
                    _calibrate.observe(engine_variant(decision),
                                       cal_ops_per_entry * prev_span,
                                       dt_chunk)
                prev_span = stop - e0
                chunk_i += 1
                if dead:
                    carry = prev   # frontier died last chunk: definite
                    break
                # only give up when chunks remain — a search that just
                # finished is definitive regardless of elapsed time
                if e < steps.n:
                    over = budget_s is not None and \
                        _time.monotonic() - t0 > budget_s
                    stop_req = cancel is not None and cancel()
                    if over or stop_req:
                        # the in-flight chunk may already have decided:
                        # block on its flag before downgrading a
                        # definite death to 'unknown'
                        if int(guarded_device_get(
                                carry[-2],
                                site="offline budget liveness")) == 0:
                            break
                        timed_out = True
                        cancelled = stop_req and not over
                        break
            with section("wgl.summarize"):
                if attest_on:
                    # chunk-boundary carry attestation: fetch the carry
                    # with its device-computed digest, recompute on
                    # host, and check the structural invariants
                    # (att == 0) — silent corruption of the frontier in
                    # HBM or on the fetch path surfaces here instead of
                    # in the verdict
                    from . import abft
                    hc, hd = guarded_device_get(
                        (carry, k.digest(carry)),
                        site="offline carry attest")
                    abft.verify_carry("offline", hd, hc)
                    att_info["carry"] += 1
                ok, death, overflow, max_count, att = guarded_device_get(
                    k.summarize(carry), site="offline summarize")
                _check_att(att, "offline")
        ok = bool(ok) and not timed_out
        overflow = bool(overflow) or timed_out
        if ok or not overflow or F >= max_frontier or timed_out:
            break
        if budget_s is not None and _time.monotonic() - t0 > budget_s:
            timed_out = True
            break
        F *= 4  # invalid + overflow: the witness may have been dropped
    _M_OPS.labels(site="offline").inc(len(ops))
    out = {
        "valid?": (True if ok else
                   "unknown" if overflow else False),
        "analyzer": "tpu-wgl-dense" if dense is not None else "tpu-wgl",
        # the dedup engine the FINAL kernel ran (escalation grows F,
        # which can push the hash working set out of VMEM mid-search)
        "dedup": (DEDUP_NONE if dense is not None else
                  dedup_engine(F, slots, _pack_params(srange, slots),
                               pallas)),
        "engine-reason": decision.reason,
        "op-count": len(ops),
        "max-frontier": int(max_count),
        "frontier-size": F,
        "duration-ms": (_time.monotonic() - t0) * 1e3,
        "configs": [],
        "final-paths": [],
    }
    if dense is not None:
        out["closure"] = closure_engine(name, dense[1], dense[2], pallas)
    if att_info is not None:
        out["attested"] = att_info
    if not ok:
        if cancelled:
            out["error"] = "search cancelled (competition loser)"
        elif timed_out:
            out["error"] = (
                f"search exceeded the {budget_s} s budget at frontier "
                f"{F}; verdict unknown")
        elif overflow:
            # The death point is an artifact of dropped configs — do not
            # name a culprit op for an 'unknown' verdict.
            out["error"] = (
                f"frontier overflowed at {F} configs; verdict unknown "
                f"(re-run with a larger frontier or the host checker)")
        else:
            # the merged stream can't name a single culprit op: re-run
            # the unmerged stream (same T capacity -> same compiled
            # kernel); it dies at the same event, cheaply
            with section("wgl.blame"):
                row = _death_row(k, ops, slots, E, init_state)
                if row >= 0:
                    src_index = int(ops.index[row])
                    out["op"] = _find_op(hist, src_index)
                    out["op-index"] = src_index
    return out


def _death_row(k: Kernel, ops: OpArray, slots: int, E: int,
               init_state) -> int:
    """Op row where the frontier died, from an unmerged re-run."""
    import jax
    import jax.numpy as jnp

    steps = build_steps(ops, slots, merge=False).pad_to(E)
    ok, death, *_ = guarded_device_get(
        k.check(jnp.asarray(steps.x), jnp.int32(steps.n), init_state),
        site="offline blame")
    d = int(death)
    if bool(ok) or d < 0:
        return -1
    row = int(steps.inv_row[d])
    return row if row >= 0 else int(steps.ret_row[d])


def _find_op(hist, index: int):
    """The completion op for the invocation with the given :index (the
    completion carries the observed value; knossos reports it too)."""
    hist = as_history(hist)
    if hist.ops and "index" not in hist.ops[0]:
        hist = hist.index()
    for pos, o in enumerate(hist.ops):
        if o.get("index") == index:
            comp = hist.completion(pos)
            return comp if comp is not None else o
    return None


def _state_range(name: str, model, entries_list) -> tuple[int, int]:
    """Combined inclusive state bounds over a batch of entry streams."""
    lo = hi = int(model.device_state())
    rng = DEVICE_MODELS[name].state_range
    for e in entries_list:
        l2, h2 = rng(int(model.device_state()), e.f, e.a, e.b)
        lo, hi = min(lo, l2), max(hi, h2)
    return int(lo), int(hi)


def _slot_bucket(p: int, p_max: int | None = None) -> int:
    """Bucket a slot count UP to the next even P so nearby keys share
    one compiled kernel, floored at 4 (the smallest dense table worth
    dispatching) and capped at the batch's true max so rounding never
    exceeds what any key actually needs. The cap itself respects the
    floor, so a batch of all-tiny keys still coalesces into one P=4
    group instead of splitting per exact P."""
    pg = max(4, ((p + 1) // 2) * 2)
    return min(pg, max(p_max, 4)) if p_max is not None else pg


def _dense_caps_error(srange, p: int, key=None) -> ValueError:
    """The forced-dense contract violation (one message, two raise
    sites: select_engine for one history, _plan_groups for a batch)."""
    who = f"key {key}'s" if key is not None else "the"
    return ValueError(
        f"dense engine requested but {who} {srange} state range x "
        f"2^{p} table exceeds the dense caps")


def _check_att(att, site: str) -> None:
    """Raise the corrupt fault when a fetched attestation accumulator
    is nonzero — an in-kernel invariant (frontier/table occupancy,
    hash-dedup digest) failed on device. att is constant 0 when
    attestation is disabled, so the check is unconditional."""
    a = int(np.asarray(att))
    if a != 0:
        from . import abft
        from .._platform import CorruptDeviceResult
        abft.note_failure("att")
        raise CorruptDeviceResult(
            site, f"in-kernel attestation accumulator = {a} — a "
                  f"frontier/table invariant or dedup digest failed "
                  f"on device")


def _unknown_result(ops, error: str, t0: float) -> dict:
    """The batch path's 'unknown' verdict shape (one definition for its
    budget, cancel and escalation-cap exits)."""
    return {"valid?": "unknown", "analyzer": "tpu-wgl-batch",
            "op-count": len(ops), "error": error,
            "configs": [], "final-paths": [],
            "duration-ms": (_time.monotonic() - t0) * 1e3}


class _Group(NamedTuple):
    """One dispatch group: the keys (indices into the round's batch)
    that run together in one vmapped kernel shape, its dense shape
    (s_lo, S, P), or None for the sort family, and its slot count."""
    keys: list[int]
    dense: tuple | None
    slots: int


def _plan_groups(model, ops: list, p_req: list[int], engine: str,
                 frontier: int = 1024, dense_slot_cap: int | None = None,
                 pallas=None, slots: int = 32,
                 srange=None) -> list[_Group]:
    """Plan a batch's dispatch groups: which keys run together, at which
    shape, in which order. `ops` are the keys' encoded histories and
    `p_req` their required_slots; the sort group takes `slots`. `srange`
    is their _state_range when the caller has it.

    The dense table is S * 2^P wide, so padding every key to the worst
    key's slot count multiplies the whole batch's device work by
    2^(Pmax - P_key): keys bucket by slot need (_slot_bucket), one
    compiled kernel a bucket, for only a few more sub-ms dispatches.
    Under 'auto' the cost model (select_engine) can route a
    dense-eligible bucket to the sort family when its table work is
    modeled slower; the event count it weighs is the batch's largest
    (per-key streams share the verdict of the comparison, which is
    length-invariant except for the one-off table init). Dense-
    ineligible keys gain nothing from grouping (the sort frontier isn't
    2^P-sized), so they spill into a single sort group instead of
    paying one sort-kernel compile per bucket — or, under a forced dense
    engine, raise. Each dense bucket then splits by its keys' lengths
    (_length_parts), unless the model's keys share lanes
    (DeviceModel.reset), where _check_group balances the lengths
    (_pack_lanes) and a bucket joins the next wider one where that
    costs less.

    Dense groups come cheapest-first and the sort group last, so a
    pathological dense-ineligible key can only starve itself of budget,
    not the cheap keys. A batch of one key or one bucket plans to one
    group."""
    if engine == "sort":
        return [_Group(list(range(len(ops))), None, slots)]
    if srange is None:
        srange = _state_range(model.device_model, model, ops)
    n_events = max(event_count(o) for o in ops)
    buckets: dict[int, tuple[tuple, list[int]]] = {}
    sort_idx: list[int] = []
    p_max = max(p_req)
    for i, p in enumerate(p_req):
        d = (_dense_shape(srange, _slot_bucket(p, p_max))
             or _dense_shape(srange, p))
        if d is not None and engine == "auto" and select_engine(
                srange, d[2], n_events, model=model.device_model,
                frontier=frontier, dense_slot_cap=dense_slot_cap,
                pallas=pallas).family != "dense":
            d = None
        if d is not None:
            buckets.setdefault(d[2], (d, []))[1].append(i)
        elif engine == "dense":
            raise _dense_caps_error(srange, p, key=i)
        else:
            sort_idx.append(i)
    dense = [buckets[pg] for pg in sorted(buckets)]
    if DEVICE_MODELS[model.device_model].reset is None:
        groups = [_Group(part, d, d[2]) for d, idx in dense
                  for part in _length_parts(idx, ops)]
    else:
        # keys share lanes (_check_group): a bucket joins the next
        # wider one where one packed group costs less than the two
        merged: list = []
        for d, idx in dense:
            if merged:
                d0, idx0 = merged[-1]
                if _pack_lanes(idx0 + idx, ops, d[1], d[2])[0] < (
                        _pack_lanes(idx0, ops, d0[1], d0[2])[0]
                        + _pack_lanes(idx, ops, d[1], d[2])[0]):
                    merged[-1] = (d, idx0 + idx)
                    continue
            merged.append((d, idx))
        groups = [_Group(idx, d, d[2]) for d, idx in merged]
    if sort_idx:
        groups.append(_Group(sort_idx, None, slots))
    return groups


SPLIT_ROWS = 1 << 15   # staged rows (lanes x E) a dispatch of its own costs


def _length_parts(idx: list[int], pre: list) -> list[list[int]]:
    """Split a dense dispatch group's keys by the E bucket of their event
    streams. A vmapped group stages every lane with the longest key's E
    rows and steps it to the longest key's end, so under skewed key
    lengths one long key makes the many short ones mostly padding. From
    the longest down, a bucket joins the longer keys unless that stages
    SPLIT_ROWS more rows than two dispatches would (about what one more
    dispatch costs the host), so keys of like length stay one group."""
    parts: dict[int, list[int]] = {}
    for i in idx:
        parts.setdefault(_bucket(max(event_count(pre[i]), 1)), []).append(i)
    out: list[tuple[int, list[int]]] = []
    for e in sorted(parts, reverse=True):
        keys = parts[e]
        if out:
            e_long, longer = out[-1]
            lanes = _bucket(len(longer), lo=1)
            joined = _bucket(len(longer) + len(keys), lo=1) * e_long
            apart = lanes * e_long + _bucket(len(keys), lo=1) * e
            if joined - apart < SPLIT_ROWS:
                longer.extend(keys)
                continue
        out.append((e, list(keys)))
    return [keys for _, keys in reversed(out)]


# A dense step's fixed latency, in table cells stepped (lanes x S x 2^P):
# fit to three checks of the yugabyte-multi-key-acid cell on one v5e
# (PERF.md), where a step costs ~0.2 ms and a lane at S 256, P 11 ~0.03
# ms more
DENSE_STEP_CELLS = 1 << 22
# steps a packed group's chunk advances: a lane whose key died waits at
# most two chunks for its next key
PACKED_CHUNK = 256


def _pack_lanes(idx: list[int], pre: list, S: int, P: int
                ) -> tuple[int, list[list[int]]]:
    """Share a dense group's lanes among its keys, for a model with a
    DeviceModel.reset: a lane checks its keys one after another, so the
    short keys fill what one key a lane leaves as padding behind the
    longest. Keys go longest first to the least loaded lane (by event
    count); the lane count is the power of two whose longest lane's
    steps cost least, at DENSE_STEP_CELLS a step plus S x 2^P a lane.
    Returns that cost and the lanes."""
    size = [max(event_count(pre[i]), 1) for i in idx]
    order = sorted(range(len(idx)), key=lambda j: -size[j])
    best, L = None, 1
    while True:
        loads = [(0, lane) for lane in range(L)]
        lanes: list[list[int]] = [[] for _ in range(L)]
        for j in order:
            load, lane = heapq.heappop(loads)
            lanes[lane].append(idx[j])
            heapq.heappush(loads, (load + size[j], lane))
        cost = max(loads)[0] * (DENSE_STEP_CELLS + (L * S << P))
        if best is None or cost < best[0]:
            best = (cost, [lane for lane in lanes if lane])
        if L >= len(idx):
            return best
        L *= 2


def _then_reset(ops: OpArray, fab: tuple[int, int, int]) -> OpArray:
    """`ops` with its crashed ops released after its last event, and then
    the op `fab` (f, a, b) invoked and returned: the next key's steps
    start with every slot free. A crash taking effect after every other
    op of its key is one never seen, so the key's verdict stands."""
    inv, ret, kind = (np.asarray(x) for x in (ops.inv, ops.ret, ops.kind))
    pos = int(max(inv.max(initial=-1),
                  ret[ret != PENDING_RET].max(initial=-1))) + 1
    crashed = kind == KIND_INFO

    def add(x, v):
        x = np.asarray(x)
        return np.append(x, np.asarray(v, x.dtype))

    f, a, b = fab
    return OpArray(f=add(ops.f, f), a=add(ops.a, a), b=add(ops.b, b),
                   kind=add(np.where(crashed, KIND_RELEASED, kind),
                            KIND_OK),
                   inv=add(inv, pos),
                   ret=add(np.where(crashed, pos, ret), pos + 1),
                   process=add(ops.process, -1), index=add(ops.index, -1))


def _lane_steps(lanes: list[list[int]], pre: list, P: int,
                fab: tuple[int, int, int]):
    """The step stack of packed lanes: each lane's keys' steps end to
    end, each key but the lane's last _then_reset by `fab`. Returns the
    (lanes, E, W+4) stack (lanes bucketed, E the _bucket of the longest
    lane), each lane's step count, and each lane's keys' first steps
    with its end."""
    segs = [_then_reset(pre[i], fab) if j + 1 < len(lane) else pre[i]
            for lane in lanes for j, i in enumerate(lane)]
    st = build_steps_batch(segs, P)
    n = st.n.astype(np.int64)
    bounds, k = [], 0
    for lane in lanes:
        bounds.append(np.concatenate([[0], np.cumsum(n[k:k + len(lane)])]))
        k += len(lane)
    ns = np.array([b[-1] for b in bounds], np.int32)
    E = _bucket(max(int(ns.max(initial=0)), 1))
    x = np.zeros((_bucket(len(lanes), lo=1), E, st.x.shape[2]), np.int32)
    x[:, :, st.w:] = (-1, 0, NIL, NIL)   # padding: no invoke
    k = 0
    for j, lane in enumerate(lanes):
        x[j, :ns[j]] = np.concatenate(
            [st.x[k + m, :n[k + m]] for m in range(len(lane))])
        k += len(lane)
    return x, np.pad(ns, (0, len(x) - len(lanes))), bounds


@functools.lru_cache(maxsize=1)
def _reviver():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def revive(carry, fresh, mask, at):
        """The dense batch carry with the lanes in `mask` started afresh
        at step `at`, their attestation residue and largest count
        kept."""
        out = [jnp.where(mask.reshape(mask.shape + (1,) * (c.ndim - 1)),
                         f, c) for c, f in zip(carry, fresh)]
        out[0] = jnp.where(mask, at, carry[0])
        out[-3], out[-1] = carry[-3], carry[-1]
        return tuple(out)

    return revive


def analysis_tpu_batch(model, hists: list, frontier: int = 1024,
                       slots: int = 32, chunk_entries: int = 4096,
                       budget_s: float | None = None,
                       cancel=None, engine: str = "auto",
                       max_frontier: int = 65536,
                       dense_slot_cap: int | None = None,
                       pallas=None,
                       max_recovery_retries: int | None = None
                       ) -> list[dict]:
    """Check a batch of independent histories (e.g. per-key subhistories
    from the independent workload) in vmapped device calls. Each history
    is encoded once; then the batch is decided in rounds
    (_decide_in_rounds): crashed ops first get release_crashed's
    deadlines, and only a key that then fails past its first deadline is
    checked again, with twice as many of a kind open at once, until none
    has a deadline. Every verdict is exact, and a key whose crashes took
    effect soon or never pays no slot for each crash it ever saw.

    Each round plans its dispatch groups once (_plan_groups) and checks
    them in that order, each in one _check_group call under the
    recovery ladder: a classified backend fault re-runs that group down
    the standard ladder, except that the OOM rung SPLITS the group in
    half (halving the vmapped working set) and recovers each half, and
    moves a lone dense key to the sort family (_search under
    dense_slot_cap 0, as the single-history path does); the final rung
    decides each of the group's keys via _final_rung (host mirror under
    the size cap). Results that went through the ladder carry a
    'recovered' trail.

    Escalation is batched: a group's overflow-suspect keys run again
    together at 4x the frontier, up to max_frontier, instead of
    degrading to serial per-key searches; culprit-op blame for definite
    invalids runs as one vmapped unmerged pass. A sort-family key past
    the batch's `slots` takes the single-history search (_search) with
    the round's ops. budget_s and cancel are polled before each group
    and between chunks, so a pathological key cannot stall the batch;
    undecided keys at the budget report 'unknown'."""
    t0 = _time.monotonic()
    section = _telemetry.section

    def remaining():
        if budget_s is None:
            return None
        return max(0.0, budget_s - (_time.monotonic() - t0))

    def spent():
        return remaining() == 0.0 or (cancel is not None and cancel())

    def alone(hs, ops, i, F, trail, kw):
        """Key i by itself on the single-history search (_search),
        continuing `trail`; an invalid verdict gets its explanation."""
        r, _ = _search(model, hs[i], ops[i], trail,
                       dict(kw, frontier=F, slots=None,
                            max_frontier=max_frontier, cancel=cancel,
                            slot_overflow_fallback=True, engine=engine),
                       remaining)
        _explain(model, hs[i], r)
        return r

    def ladder(hs, ops, group, F, kw):
        """_check_group under the recovery ladder; returns its
        verdicts and overflow suspects."""
        kw = dict(kw)
        trail = _RecoveryTrail(max_recovery_retries)
        while True:
            try:
                rs, suspects = _check_group(
                    model, hs, ops, group, F, budget_s=remaining(),
                    cancel=cancel, chunk_entries=kw["chunk_entries"],
                    pallas=kw["pallas"])
            except RuntimeError as e:
                if not trail.absorb(e, "batch"):
                    return {i: _final_rung(model, hs[i], trail, e,
                                           budget_s=remaining(),
                                           cancel=cancel)
                            for i in group.keys}, []
                kind = trail.faults[-1]
                if kind == FAULT_OOM and len(group.keys) > 1:
                    # each half gets the full ladder (and half the
                    # device working set); its trail merges with this
                    mid = len(group.keys) // 2
                    log.warning("batch: splitting %d keys into %d + %d "
                                "after OOM", len(group.keys), mid,
                                len(group.keys) - mid)
                    rs, suspects = {}, []
                    for half in (group.keys[:mid], group.keys[mid:]):
                        r2, s2 = ladder(hs, ops, group._replace(keys=half),
                                        F, kw)
                        rs.update(r2)
                        suspects += s2
                    for r in rs.values():
                        # never stamp 'recovered' on a half that fell
                        # off its own ladder (degraded + recovered is a
                        # contradiction; its fault list lives under
                        # 'recovery-failed'), and keep sub-trail markers
                        # like {'fallback': 'host'}
                        if r.get("degraded"):
                            rf = r.get("recovery-failed")
                            if isinstance(rf, dict):
                                rf["faults"] = list(trail.faults) \
                                    + list(rf.get("faults", []))
                            continue
                        inner = dict(r.get("recovered") or {})
                        faults = list(trail.faults) \
                            + list(inner.get("faults", []))
                        inner.update(faults=faults, retries=len(faults),
                                     split=True)
                        r["recovered"] = inner
                    return rs, suspects
                _apply_recovery_rung(kind, kw)
                if kind == FAULT_OOM and group.dense is not None \
                        and engine == "auto":
                    # one key cannot split: as on the single-history
                    # path, the rung's dense_slot_cap 0 moves it to the
                    # sort family
                    (i,) = group.keys
                    return {i: alone(hs, ops, i, F, trail, kw)}, []
                continue
            for r in rs.values():
                trail.stamp(r)
            return rs, suspects

    def run_round(keys, ops):
        hs = [hists[k] for k in keys]
        ops = list(ops)
        with section("wgl.encode"):
            p_req = required_slots_batch(ops)
            groups = _plan_groups(model, ops, p_req, engine,
                                  frontier=frontier,
                                  dense_slot_cap=dense_slot_cap,
                                  pallas=pallas, slots=slots)
        results: list[dict | None] = [None] * len(ops)
        kw = dict(chunk_entries=chunk_entries, pallas=pallas,
                  dense_slot_cap=dense_slot_cap)
        for g in groups:
            if g.dense is None and not spent():
                batched = []
                for i in g.keys:
                    # the sort family has no release step
                    pending = _pending_forever(ops[i])
                    if pending is not ops[i]:
                        ops[i], p_req[i] = pending, required_slots(pending)
                    if p_req[i] <= g.slots:
                        batched.append(i)
                        continue
                    # this key alone exceeds the batch's slot budget: the
                    # single-history search re-sizes (and host-falls-back
                    # past 256), checking this round's ops once
                    results[i] = alone(hs, ops, i, frontier,
                                       _RecoveryTrail(max_recovery_retries),
                                       kw)
                g = g._replace(keys=batched)
            todo, F = g.keys, frontier
            while todo:
                if spent():
                    # budget gone: report the rest without dispatching
                    # even one chunk for them
                    for i in todo:
                        results[i] = _unknown_result(
                            ops[i], "batch budget exhausted/cancelled "
                            "before this key's search started", t0)
                    break
                rs, todo = ladder(hs, ops, g._replace(keys=todo), F, kw)
                for i, r in rs.items():
                    results[i] = r
                if todo and F >= max_frontier:
                    for i in todo:
                        results[i] = _unknown_result(
                            ops[i], f"frontier overflowed at {F}; "
                            f"escalation cap {max_frontier} reached — "
                            "verdict unknown", t0)
                    break
                # invalid + overflow: the witness may have been dropped
                F *= 4
        return results

    with section("wgl.encode"):
        pre = [encode_ops_for_model(model, h) for h in hists]
    results = _decide_in_rounds(pre, run_round,
                                DEVICE_MODELS[model.device_model],
                                deadlines=engine != "sort", site="batch")
    dur = (_time.monotonic() - t0) * 1e3
    for r in results:
        r.setdefault("duration-ms", dur)
    return results


def _check_group(model, hists: list, ops: list, group: _Group,
                 frontier: int, chunk_entries: int = 4096,
                 budget_s: float | None = None, cancel=None,
                 pallas=None) -> tuple[dict, list[int]]:
    """Check one dispatch group's keys (`ops[i]`, the encoding of
    `hists[i]`, for i in group.keys) in vmapped device calls: stage
    their steps, run the pipelined chunk loop, summarize, and blame the
    definite invalids in one pass. Long groups run as bounded-duration
    chunks with the vmapped frontier carried between calls, polling
    budget_s / cancel like the scalar path; keys still undecided report
    'unknown'. Returns {key: result} and the keys whose frontier
    overflowed on an invalid verdict, for the caller to check again at
    a larger frontier.

    A dense group of a model with a DeviceModel.reset packs its keys
    into lanes (_pack_lanes), each lane checking its keys one after
    another; a lane found dead before its last key starts afresh at the
    next, in chunks of at most PACKED_CHUNK steps. A packed key's
    max-frontier is its lane's."""
    import jax
    import jax.numpy as jnp

    t0 = _time.monotonic()
    name = model.device_model
    section = _telemetry.section
    dense, slots = group.dense, group.slots
    srange = None   # the dense kernel's shape carries it
    if dense is None:
        with section("wgl.encode"):
            srange = _state_range(name, model,
                                  [ops[i] for i in group.keys])
    results: dict[int, dict] = {}
    reset = DEVICE_MODELS[name].reset if dense is not None else None
    with section("wgl.steps"):
        items = [(i, ops[i]) for i in group.keys]
        E = _bucket(max(max(event_count(o) for _, o in items), 1))
        if reset is None:
            # a lane a key. Bucket the batch axis like E: the vmapped
            # kernels are jitted per (B, E) shape, so an exact B would
            # recompile the whole family for every distinct key count —
            # pad with zero-step lanes (n=0: never consumed, frontier
            # stays at the initial config), skipped by the reads of the
            # real lanes below
            packed = [[i] for i, _ in items]
            stack = build_steps_batch([o for _, o in items], slots, t=E,
                                      lanes=_bucket(len(items), lo=1))
            x_np, ns = stack.x, stack.n
            bounds = [np.array([0, n]) for n in ns[:len(items)]]
        else:
            _, packed = _pack_lanes(group.keys, ops, dense[1], dense[2])
            x_np, ns, bounds = _lane_steps(packed, ops, dense[2],
                                           reset(model.device_state()))
            chunk_entries = min(chunk_entries, PACKED_CHUNK)
        lanes, real, rows = len(x_np), len(packed), x_np.shape[1]

    family = "dense" if dense is not None else "sort"
    _M_GROUPS.labels(family=family).inc()
    # annotation only: the phases and chunk sites inside are timed on
    # their own
    with section("wgl.batch.group", P=slots, lanes=lanes,
                 keys=len(items), steps=int(ns.max())):
        with section("wgl.stage"):
            if dense is not None:
                k = _dense_kernel(name, dense[0], dense[1], dense[2], rows,
                                  pallas=pallas)
            else:
                k = _kernel(name, frontier, slots, E,
                            _pack_params(srange, slots), pallas=pallas)
            attest_on = attest_enabled()
            x = jnp.asarray(maybe_corrupt("batch", x_np))
            if attest_on:
                # staged-buffer attestation (see the offline twin): the
                # whole vmapped stack ships as one buffer, one digest
                from . import abft
                abft.verify_steps("batch", guarded_device_get(
                    abft.digest_device(x), site="batch steps attest"),
                    abft.digest_host(x_np))
            s0 = jnp.full(lanes, model.device_state(), jnp.int32)
            carry = fresh = jax.vmap(k.init_carry)(s0)
        e, all_dead = 0, False
        n_max = int(ns.max())
        # a packed lane whose frontier died before its last key starts
        # afresh at its next; dead[j]: the keys (places in packed[j])
        # lane j found invalid
        dead: dict[int, set] = {}

        def revive(src, dst):
            """`dst` with each lane that `src` shows dead before its last
            key started afresh at the next (`src` is `dst`, or the carry
            before a chunk, in which a dead lane takes no step); and
            src's steps and counts. Syncs on `src`."""
            at, counts = (np.asarray(a) for a in guarded_device_get(
                (src[0], src[-2]), site="batch lane liveness"))
            mask = np.zeros(lanes, bool)
            start = np.zeros(lanes, np.int32)
            for j in np.flatnonzero(counts[:real] == 0):
                seg = int(np.searchsorted(bounds[j], at[j] - 1,
                                          side="right")) - 1
                dead.setdefault(j, set()).add(seg)
                if seg + 1 < len(packed[j]):
                    mask[j], start[j] = True, bounds[j][seg + 1]
            if mask.any():
                dst = _reviver()(dst, fresh, jnp.asarray(mask),
                                 jnp.asarray(start))
                at, counts = np.where(mask, start, at), \
                    np.where(mask, 1, counts)
            return dst, mask.any(), at, counts

        # pipelined like the scalar loop: enqueue the next vmapped
        # chunk, then read the PREVIOUS chunk's frontier counts while
        # the device computes — all-dead detection lags one chunk
        # (safe: dead frontiers stay dead) in exchange for overlapping
        # the per-chunk sync with compute
        chunk_obs = _M_CHUNK.labels(site="batch", family=family)
        cells = lanes * dense[1] << dense[2] if dense is not None else 0
        while e < n_max:
            stop = min(e + chunk_entries, n_max)
            maybe_inject_fault("batch")
            t_chunk = _time.monotonic()
            with section("wgl.batch.chunk"):
                nxt = k.check_chunk_batch(
                    x, jnp.asarray(np.minimum(ns, stop)), carry)
                if cells:
                    _M_TABLE_CELLS.labels(family=family).inc(
                        cells * (stop - e))
                prev, carry = carry, nxt
                e = stop
                # pad entries never consume, so their frontiers stay
                # alive forever — only the real lanes' liveness counts
                if reset is None:
                    revived, counts = False, np.asarray(guarded_device_get(
                        prev[-2], site="batch liveness"))
                else:
                    carry, revived, _, counts = revive(prev, carry)
                all_dead = not revived and not counts[:real].any()
            chunk_obs.observe(_time.monotonic() - t_chunk)
            if all_dead:
                carry = prev   # every frontier died: all definite
                break
            if e < n_max:
                if (budget_s is not None
                        and _time.monotonic() - t0 > budget_s) \
                        or (cancel is not None and cancel()):
                    break
        while reset is not None and not all_dead and not (
                (budget_s is not None and _time.monotonic() - t0 > budget_s)
                or (cancel is not None and cancel())):
            # lanes revived or found dead after the last chunk: run the
            # keys they have left
            carry, _, at, counts = revive(carry, carry)
            left = int(np.where(counts > 0, ns - at, 0)[:real].max())
            if left <= 0:
                break
            with section("wgl.batch.chunk"):
                carry = k.check_chunk_batch(x, jnp.asarray(ns), carry)
            _M_TABLE_CELLS.labels(family=family).inc(cells * left)
            e += left
        # every lane runs every step advanced, over the rows staged for
        # it; a real key needs only its own steps
        _M_LANE_STEPS.labels(family=family, kind="staged").inc(
            lanes * rows)
        _M_LANE_STEPS.labels(family=family, kind="dispatched").inc(
            lanes * e)
        _M_LANE_STEPS.labels(family=family, kind="useful").inc(
            int(np.minimum(ns[:real], e).sum()))
        with section("wgl.summarize"):
            if attest_on:
                # per-key carry attestation at the batch's final
                # boundary
                from . import abft
                hc, hd = guarded_device_get(
                    (carry, jax.vmap(k.digest)(carry)),
                    site="batch carry attest")
                for bi in range(len(np.asarray(hd))):
                    abft.verify_carry(
                        "batch", np.asarray(hd)[bi],
                        tuple(np.asarray(a)[bi] for a in hc))
            # ONE guarded fetch for the verdicts AND the carry
            # components the decided-mask below needs: the
            # consumed/count buffers were previously pulled via raw
            # np.asarray — an unguarded implicit sync (JTS103) and a
            # second device round-trip
            (ok, death, overflow, max_count, att), consumed, counts = \
                guarded_device_get(
                    (jax.vmap(k.summarize)(carry), carry[0], carry[-2]),
                    site="batch summarize")
            _check_att(np.asarray(att).sum(), "batch")
            _M_OPS.labels(site="batch").inc(
                sum(len(o) for _, o in items))
            batch_dedup = (DEDUP_NONE if dense is not None else
                           dedup_engine(frontier, slots,
                                        _pack_params(srange, slots),
                                        pallas))
            # a key is invalid where its lane's frontier died (death is
            # definitive no matter how many entries remain), valid where
            # its lane went past its steps alive, else undecided
            consumed = np.asarray(consumed)
            suspects = []    # overflow + invalid: escalate together
            invalids = []    # definite invalid: blame together
            for j, lane in enumerate(packed):
                last = int(np.searchsorted(bounds[j], consumed[j] - 1,
                                           side="right")) - 1
                for seg, i in enumerate(lane):
                    if seg in dead.get(j, ()) or (
                            counts[j] == 0 and seg == last):
                        if bool(overflow[j]):
                            suspects.append(i)
                        else:
                            invalids.append((j, i, ops[i]))
                    elif consumed[j] >= bounds[j][seg + 1] and (
                            counts[j] > 0 or seg < last):
                        results[i] = {
                            "valid?": True, "analyzer": "tpu-wgl-batch",
                            "dedup": batch_dedup,
                            "op-count": len(ops[i]),
                            "max-frontier": int(max_count[j]),
                            "configs": [], "final-paths": []}
                    else:
                        results[i] = _unknown_result(
                            ops[i], "batch budget exhausted/cancelled "
                            "before this key's search finished", t0)
        if invalids:
            with section("wgl.blame"):
                # one vmapped unmerged pass names every culprit op (the
                # unmerged streams fit E by construction)
                st2 = build_steps_batch([ops for _, _, ops in invalids],
                                        slots, merge=False, t=E,
                                        lanes=_bucket(len(invalids), lo=1))
                okb, deathb, *_ = guarded_device_get(k.check_batch(
                    jnp.asarray(st2.x), jnp.asarray(st2.n),
                    jnp.full(len(st2.n), model.device_state(),
                             jnp.int32)),
                    site="batch blame")
                for t, (j, i, ops) in enumerate(invalids):
                    r = {"valid?": False, "analyzer": "tpu-wgl-batch",
                         "dedup": batch_dedup,
                         "op-count": len(ops),
                         "max-frontier": int(max_count[j]),
                         "configs": [], "final-paths": []}
                    d = int(deathb[t])
                    if not bool(okb[t]) and d >= 0:
                        row = int(st2.inv_row[t, d])
                        if row < 0:
                            row = int(st2.ret_row[t, d])
                        if row >= 0:
                            src = int(ops.index[row])
                            r["op"] = _find_op(hists[i], src)
                            r["op-index"] = src
                    results[i] = r
    dur = (_time.monotonic() - t0) * 1e3
    for i, r in results.items():
        if attest_on:
            r.setdefault("attested", {"steps": 1, "carry": 1})
        r.setdefault("duration-ms", dur)
    return results, suspects


def _sharded_runner(name, dense, frontier, slots, srange, E, mesh, axis,
                    pallas=None):
    """The jitted, mesh-sharded batch checker for one kernel shape.

    Cached on the full compilation key (kernel shape + mesh) so repeated
    check_batch_sharded calls — and the several per-slot-bucket dispatch
    groups inside one call — reuse one traced+compiled executable per
    shape. A fresh closure per call would force shard_map to re-trace
    and XLA to recompile every time, which costs seconds per dispatch
    and was the bulk of the sharded path's wall time. The dense kernel
    ignores frontier/slots/srange, so they are normalized out of the
    cache key here — spurious misses can't be reintroduced by a call
    site. The Pallas-vs-XLA choices (closure
    round for the dense family, hash dedup for the sort family) are
    resolved here and included in the key, so flipping the
    JEPSEN_TPU_PALLAS_* gates mid-process affects sharded checks the
    same way it affects scalar/batch ones.
    """
    if dense is not None:
        frontier = slots = srange = None
        use_pallas, on_tpu = _pallas_enabled(PALLAS_CLOSURE_ENV, pallas)
    else:
        use_pallas, on_tpu = _pallas_enabled(PALLAS_DEDUP_ENV, pallas)
    return _sharded_runner_cached(name, dense, frontier, slots, srange,
                                  E, mesh, axis, use_pallas, on_tpu,
                                  attest_enabled())


@functools.lru_cache(maxsize=256)
def _sharded_runner_cached(name, dense, frontier, slots, srange, E,
                           mesh, axis, use_pallas, on_tpu,
                           use_attest=True):
    import jax
    from functools import partial
    from jax.sharding import PartitionSpec as P

    if dense is not None:
        check_batch = _dense_kernel_cached(
            name, dense[0], dense[1], dense[2], E,
            use_pallas, on_tpu, use_attest).check_batch
    else:
        check_batch = _kernel_cached(name, frontier, slots, E,
                                     _pack_params(srange, slots),
                                     use_pallas, on_tpu,
                                     use_attest).check_batch

    # check_vma=False: the kernel's inner lax loops create fresh constants
    # whose varying-manual-axes tags can't match the sharded carries; the
    # math is still replication-safe (the only cross-shard op is the psum).
    shard_map = partial(jax.shard_map, check_vma=False)

    @partial(shard_map, mesh=mesh,
             in_specs=(P(axis), P(axis), P(axis)),
             out_specs=(P(), P(axis), P(axis), P()))
    def run(x, n, s0):
        ok, death, overflow, max_count, att = check_batch(x, n, s0)
        # every shard's verdict, reduced over ICI: 1 iff all keys valid
        bad = (~ok).sum()
        total_bad = jax.lax.psum(bad, axis)
        # attestation accumulators reduced the same way: the host
        # checks one scalar per group instead of gathering per-key atts
        total_att = jax.lax.psum(att.sum(), axis)
        return (total_bad == 0)[None], ok, overflow, total_att[None]

    return jax.jit(run)


def check_batch_sharded(model, hists: list, mesh=None, axis: str = "keys",
                        frontier: int = 1024, slots: int = 32,
                        engine: str = "auto",
                        dense_slot_cap: int | None = None,
                        pallas=None, return_info: bool = False,
                        max_recovery_retries: int | None = None):
    """Recovery wrapper around _check_batch_sharded_once (which holds
    the sharding contract — see its docstring): a classified backend
    fault re-runs the dispatch down the standard ladder, the OOM rung
    splits the key batch in half (each half re-shards over the same
    mesh), and the final rung delegates every key to
    analysis_tpu_batch — whose own ladder ends at the host mirror —
    so an exhausted sharded ladder still yields verdicts. Keys the
    fallback could not decide report False under the boolean contract
    (conservative: unverified, not a proven anomaly) and are named in
    info['unknown-keys'] with info['degraded']=True. The trail is
    surfaced via return_info=True (info['recovered'], or
    info['recovery-failed'] when verdicts were lost)."""
    kw = dict(mesh=mesh, axis=axis, frontier=frontier, slots=slots,
              engine=engine, dense_slot_cap=dense_slot_cap,
              pallas=pallas)
    trail = _RecoveryTrail(max_recovery_retries)
    while True:
        try:
            all_ok, per_key, info = _check_batch_sharded_once(
                model, hists, return_info=True, **kw)
        except RuntimeError as e:
            if not trail.absorb(e, "sharded"):
                # hand the batch fallback the rung-mutated knobs, not
                # the originals — a persistent compile fault already
                # taught this ladder pallas=False; re-learning it
                # would burn the batch entry's own retry budget
                subs = analysis_tpu_batch(
                    model, hists, frontier=frontier, slots=slots,
                    engine=kw["engine"],
                    dense_slot_cap=kw["dense_slot_cap"],
                    pallas=kw["pallas"],
                    max_recovery_retries=max_recovery_retries)
                per_key = np.asarray(
                    [r["valid?"] is True for r in subs], bool)
                info = {"groups": []}
                trail_d = {"faults": list(trail.faults),
                           "retries": len(trail.faults),
                           "fallback": "batch"}
                unknown = [i for i, r in enumerate(subs)
                           if r.get("valid?") not in (True, False)]
                if unknown:
                    # keys the fallback never decided (over the host
                    # cap + spent budget): the boolean contract has no
                    # third value, so per_key conservatively reports
                    # them False — but they are NOT proven anomalies.
                    # Surface the distinction for return_info callers
                    # and keep the trail under recovery-failed (this
                    # aggregate lost verdicts: degraded, not recovered)
                    log.warning(
                        "sharded: %d key(s) undecided after the "
                        "recovery budget; per-key False for them is "
                        "'unverified', not a found anomaly: %s",
                        len(unknown), unknown)
                    info["degraded"] = True
                    info["unknown-keys"] = unknown
                    info["recovery-failed"] = trail_d
                else:
                    info["recovered"] = trail_d
                all_ok = bool(per_key.all())
                break
            kind = trail.faults[-1]
            if kind == FAULT_OOM and len(hists) > 1:
                mid = len(hists) // 2
                log.warning("sharded: splitting %d keys into %d + %d "
                            "after OOM", len(hists), mid,
                            len(hists) - mid)
                l_ok, l_pk, l_info = check_batch_sharded(
                    model, hists[:mid], return_info=True,
                    max_recovery_retries=max_recovery_retries, **kw)
                r_ok, r_pk, r_info = check_batch_sharded(
                    model, hists[mid:], return_info=True,
                    max_recovery_retries=max_recovery_retries, **kw)
                per_key = np.concatenate([l_pk, r_pk])

                def _half_faults(i):
                    # a half's trail lives under 'recovered' when it
                    # healed, 'recovery-failed' when it fell off
                    return list((i.get("recovered")
                                 or i.get("recovery-failed")
                                 or {}).get("faults", []))

                faults = list(trail.faults) \
                    + _half_faults(l_info) + _half_faults(r_info)
                trail_d = {"faults": faults, "retries": len(faults),
                           "split": True}
                info = {"groups": l_info["groups"] + r_info["groups"]}
                unknown = list(l_info.get("unknown-keys", [])) \
                    + [mid + i for i in r_info.get("unknown-keys", [])]
                if l_info.get("degraded") or r_info.get("degraded"):
                    # a half lost verdicts: the aggregate is degraded,
                    # not recovered — keep the undecided-key list
                    # (right half re-indexed) so per-key False stays
                    # distinguishable from a found anomaly
                    info["degraded"] = True
                    if unknown:
                        info["unknown-keys"] = unknown
                    info["recovery-failed"] = trail_d
                else:
                    info["recovered"] = trail_d
                all_ok = bool(l_ok and r_ok)
                break
            _apply_recovery_rung(kind, kw)
            continue
        if trail.faults:
            info = dict(info)
            info["recovered"] = {"faults": list(trail.faults),
                                 "retries": len(trail.faults)}
        break
    if return_info:
        return all_ok, per_key, info
    return all_ok, per_key


def _check_batch_sharded_once(model, hists: list, mesh=None,
                              axis: str = "keys",
                              frontier: int = 1024, slots: int = 32,
                              engine: str = "auto",
                              dense_slot_cap: int | None = None,
                              pallas=None, return_info: bool = False):
    """Shard a batch of independent histories across a device mesh and
    reduce the aggregate verdict with a psum-OR over ICI.

    Returns (all_valid: bool, per_key_ok: np.ndarray[bool]). The per-key
    verdicts stay sharded until fetched; the scalar verdict is computed
    with an explicit collective so multi-chip runs never gather full
    frontiers to one chip.

    engine / dense_slot_cap / pallas: the same autoselect knobs as
    analysis_tpu, applied per dispatch group. return_info=True appends
    a third element: {'groups': [{family, dedup, keys, slots,
    staged-devices}, ...]} — which engine each slot-bucketed group
    actually ran, and over how many devices its staged input was
    spread (bench artifacts and chip_smoke.py report this).
    """
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    name = model.device_model
    if mesh is None:
        devs = np.array(jax.devices())
        mesh = Mesh(devs, (axis,))
    n_dev = mesh.shape[axis]
    keys_sharding = NamedSharding(mesh, PartitionSpec(axis))
    k = len(hists)
    if k == 0:
        if return_info:
            return True, np.zeros(0, bool), {"groups": []}
        return True, np.zeros(0, bool)
    pad_k = -(-k // n_dev) * n_dev

    all_ops = [encode_ops_for_model(model, h) for h in hists]
    # OpArray exposes the same f/a/b arrays _state_range reads, so
    # eligibility costs no extra stream builds
    srange = _state_range(name, model, all_ops)
    p_req = required_slots_batch(all_ops)

    # analysis_tpu_batch's dispatch groups (see _plan_groups): on the
    # hazelcast bench shape (100 keys, ~2.5 crashes/key) the max-padded
    # table sums to 14x the per-key need; grouping recovers it for a
    # couple of extra sub-ms dispatches.
    groups = _plan_groups(model, all_ops, p_req, engine,
                          frontier=frontier, dense_slot_cap=dense_slot_cap,
                          pallas=pallas, slots=slots, srange=srange)
    group_info: list[dict] = []

    def run_group(idx: list[int], dense, g_slots: int):
        """One vmapped + mesh-sharded dispatch over the keys in idx."""
        if dense is None:
            # the sort group sizes itself to its own keys — never below
            # the caller's slots, never a SlotOverflow on a key the
            # dense caps rejected
            g_slots = max(g_slots, _bucket(max(p_req[i] for i in idx),
                                           lo=8))
        gk = len(idx)
        g_pad = -(-gk // n_dev) * n_dev
        stack = build_steps_batch([all_ops[i] for i in idx], g_slots,
                                  lanes=g_pad)
        x_np = stack.x

        group_info.append({
            "family": "dense" if dense is not None else "sort",
            "dedup": (DEDUP_NONE if dense is not None else
                      dedup_engine(frontier, g_slots,
                                   _pack_params(srange, g_slots),
                                   pallas)),
            "keys": gk, "slots": g_slots})
        run = _sharded_runner(name, dense, frontier, g_slots, srange,
                              x_np.shape[1], mesh, axis, pallas=pallas)
        maybe_inject_fault("sharded")
        # stage each key's rows straight onto the device that checks
        # them: a plain asarray lands the whole batch on one device
        # and leaves the reshard to the jitted shard_map
        xj = jax.device_put(maybe_corrupt("sharded", x_np), keys_sharding)
        group_info[-1]["staged-devices"] = len(
            {sh.device for sh in xj.addressable_shards})
        # staged-buffer attestation: the digest reduction runs on the
        # SAME device buffer the sharded kernel consumes; its scalar
        # is fetched with the group's verdicts below, so detection
        # costs no extra sync
        att = None
        if attest_on:
            from . import abft
            att = (abft.digest_device(xj), abft.digest_host(x_np))
        # async dispatch: return the device arrays unfetched so every
        # group's kernel is enqueued before the first blocking fetch —
        # serializing dispatch+fetch per group would re-add the
        # latency the grouping saved
        all_ok_g, ok_g, ov_g, att_g = run(
            xj,
            jax.device_put(stack.n, keys_sharding),
            jax.device_put(np.full(g_pad, model.device_state(), np.int32),
                           keys_sharding))
        return all_ok_g, ok_g, ov_g, att_g, att

    attest_on = attest_enabled()
    pending = [(g.keys, run_group(*g)) for g in groups]
    per_key = np.zeros(k, bool)
    overflow = np.zeros(k, bool)
    all_ok = True
    for gi, (idx, handles) in enumerate(pending):
        t_fetch = _time.monotonic()
        all_ok_g, ok_g, ov_g, att_g, att = guarded_device_get(
            handles, site="sharded fetch")
        _M_CHUNK.labels(site="sharded",
                        family=group_info[gi]["family"]).observe(
            _time.monotonic() - t_fetch)
        _check_att(np.asarray(att_g)[0], "sharded")
        if att is not None:
            from . import abft
            abft.verify_steps("sharded", att[0], att[1])
        all_ok &= bool(np.asarray(all_ok_g)[0])
        per_key[idx] = np.asarray(ok_g)[:len(idx)]
        overflow[idx] = np.asarray(ov_g)[:len(idx)]
    _M_OPS.labels(site="sharded").inc(
        sum(len(o) for o in all_ops))
    # An 'invalid' under frontier overflow is unsound (the witness config
    # may have been dropped): escalate those keys — together, as one
    # vmapped batch at 4x the frontier (recursing upward), never a
    # serial per-key degradation — and report 'unknown' keys as invalid
    # here (the boolean contract has no third value).
    suspect = ~per_key & overflow
    if suspect.any():
        idx = np.flatnonzero(suspect)
        subs = analysis_tpu_batch(model, [hists[int(i)] for i in idx],
                                  frontier=frontier * 4, slots=slots,
                                  engine=engine,
                                  dense_slot_cap=dense_slot_cap,
                                  pallas=pallas)
        per_key = per_key.copy()
        for t, i in enumerate(idx):
            per_key[i] = subs[t]["valid?"] is True
        all_ok = bool(per_key.all())
    if return_info:
        info = {"groups": group_info}
        if attest_on:
            # steps: one staged-buffer digest per group; carry: one
            # psum-reduced att check per group (see _sharded_runner)
            info["attested"] = {"steps": len(pending),
                                "carry": len(pending)}
        return all_ok, per_key, info
    return all_ok, per_key
