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

Batching: `vmap` over independent per-key histories;
`check_batch_sharded` shards the key axis over a `jax.sharding.Mesh` and
reduces verdicts with a psum-OR over ICI.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import heapq
import logging
import os
import time as _time
from typing import Callable

import numpy as np

from .. import calibrate as _calibrate, telemetry as _telemetry
from .._platform import (FAULT_COMPILE, FAULT_DEVICE_LOST,
                         FAULT_OOM, attest_enabled, backend_reinit,
                         classify_backend_error, guarded_device_get,
                         maybe_corrupt, maybe_inject_fault)
from ..history import (DeviceEncodingError, F_CAS, F_READ, F_WRITE,
                       KIND_OK, NIL, OpArray, default_register_codec,
                       encode_ops, history as as_history)

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

# Event kinds (host-side stream construction)
E_INVOKE = 0
E_RETURN = 1


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

    @classmethod
    def empty(cls, w: int, t: int = 0) -> "Steps":
        z = np.zeros((0, w + 4), np.int32)
        zn = np.zeros(0, np.int32)
        return cls(z, zn, zn, w, 0).pad_to(t)


def event_count(ops: OpArray) -> int:
    """Length of the unmerged event stream (invokes + ok returns) —
    the T capacity that lets merged and unmerged streams share one
    compiled kernel."""
    return len(ops) + int((np.asarray(ops.kind) == KIND_OK).sum())


def required_slots(ops: OpArray) -> int:
    """The peak number of simultaneously-pending ops (crashed ops pend
    forever) — the minimum slot count the kernel needs. Computing it up
    front avoids SlotOverflow escalation recompiles."""
    # same (position, order) tie-break as build_steps: invokes sort
    # before returns at equal positions
    events = []
    for r in range(len(ops)):
        events.append((int(ops.inv[r]), 0, 1))
        if ops.kind[r] == KIND_OK:
            events.append((int(ops.ret[r]), 1, -1))
    events.sort()
    cur = peak = 0
    for _, _, d in events:
        cur += d
        peak = max(peak, cur)
    return max(peak, 1)


def build_steps(ops: OpArray, p: int, merge: bool = True) -> Steps:
    """Lower an OpArray to packed event steps, assigning each op a slot
    in [0, p). Raises SlotOverflow if concurrency + crashed ops exceed
    p."""
    events = []  # (position, order, kind, row)
    for r in range(len(ops)):
        events.append((int(ops.inv[r]), 0, E_INVOKE, r))
        if ops.kind[r] == KIND_OK:
            events.append((int(ops.ret[r]), 1, E_RETURN, r))
    events.sort()
    w = max(1, (p + 31) // 32)
    free = list(range(p))
    heapq.heapify(free)
    slot_of_row: dict[int, int] = {}
    masks: list[list[int]] = []
    rest: list[tuple[int, int, int, int]] = []
    ret_row: list[int] = []
    inv_row: list[int] = []
    pend = [0] * w
    pend_rows: list[int] = []

    def flush(inv_slot: int, f: int, a: int, b: int, row: int) -> None:
        nonlocal pend, pend_rows
        masks.append(pend)
        rest.append((inv_slot, f, a, b))
        ret_row.append(pend_rows[0] if len(pend_rows) == 1 else -1)
        inv_row.append(row)
        pend = [0] * w
        pend_rows = []

    for _, _, k, r in events:
        if k == E_INVOKE:
            if not free:
                raise SlotOverflow(
                    f"more than {p} pending ops at op row {r} "
                    f"(crashed ops hold slots forever); raise p or check "
                    f"on the host")
            s = heapq.heappop(free)
            slot_of_row[r] = s
            flush(s, int(ops.f[r]), int(ops.a[r]), int(ops.b[r]), r)
        else:
            s = slot_of_row.pop(r)
            heapq.heappush(free, s)
            pend[s // 32] |= 1 << (s % 32)
            pend_rows.append(r)
            if not merge:
                flush(-1, 0, NIL, NIL, -1)
    if any(pend):
        flush(-1, 0, NIL, NIL, -1)
    n = len(masks)
    mask_arr = np.asarray(masks, np.uint32).reshape(n, w)
    rest_arr = np.asarray(rest, np.int32).reshape(n, 4)
    return Steps(np.concatenate([mask_arr.view(np.int32), rest_arr],
                                axis=1),
                 np.asarray(ret_row, np.int32),
                 np.asarray(inv_row, np.int32), w, n)


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


def closure_engine(S: int, P: int, pallas=None) -> str:
    """Which closure round the dense kernel runs at this shape — the
    same gate _dense_kernel_cached applies."""
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
    pallas_round = None
    if use_pallas:
        from . import wgl_pallas
        if wgl_pallas.eligible(S, P):
            pallas_round = wgl_pallas.closure_round_fn(
                S, P, interpret=not on_tpu)

    def closure(table, slot_f, slot_a, slot_b, slot_occ):
        """Close the table under linearization of every occupied slot."""
        legal, new = step(S_VALS[None, :], slot_f[:, None],
                          slot_a[:, None], slot_b[:, None])     # (P, S)
        legal = legal & slot_occ[:, None]
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
        # at every chunk boundary (abft.verify_carry).
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
            # identity gather.
            table = jnp.take(table, COLS | rm, axis=1) \
                & ((COLS & rm) == 0)[None, :]
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
                att = att + jnp.sum(table & badc[None, :], dtype=i32)
            count = table.sum().astype(i32)
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
    (forced | slot-cap | dense-caps | cost-model | calibrated) — the
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
                  *, slots: int | None = None, frontier: int = 256,
                  engine: str = "auto", dense_slot_cap: int | None = None,
                  pallas=None, calibration=None) -> EngineDecision:
    """Pick the kernel family (and the sort family's dedup engine) for
    one history shape. engine='dense'/'sort' force a family ('dense'
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
    exact verdicts, applied to ground truth."""
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
#                memory hog) — batch entries additionally SPLIT the
#                batch in half and recover each half independently
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
    """Check one history on the device, under the device-fault recovery
    ladder (see the ladder comment above): a classified backend fault
    (oom / device-lost / compile / wedged) re-runs the search down the
    appropriate rung instead of surfacing as a degraded 'unknown', and
    a decided result that went through the ladder reports its
    'recovered' trail. max_recovery_retries bounds the ladder (None =
    MAX_RECOVERY_RETRIES); past it, histories under
    HOST_FALLBACK_MAX_OPS are decided on the host mirror.

    See _analysis_tpu_once for the search itself and the remaining
    knobs."""
    kw = dict(frontier=frontier, slots=slots, max_frontier=max_frontier,
              chunk_entries=chunk_entries, budget_s=budget_s,
              cancel=cancel, explain=explain,
              slot_overflow_fallback=slot_overflow_fallback,
              engine=engine, dense_slot_cap=dense_slot_cap,
              pallas=pallas)
    trail = _RecoveryTrail(max_recovery_retries)
    while True:
        try:
            a = _analysis_tpu_once(model, hist, **kw)
        except RuntimeError as e:
            if not trail.absorb(e, "offline"):
                return _final_rung(model, hist, trail, e,
                                   budget_s=budget_s, cancel=cancel)
            _apply_recovery_rung(trail.faults[-1], kw)
            continue
        trail.stamp(a)
        return a


def _analysis_tpu_once(model, hist, frontier: int = 256,
                       slots: int | None = None,
                       max_frontier: int = 65536,
                       chunk_entries: int = 4096,
                       budget_s: float | None = None,
                       cancel=None,
                       explain: bool = True,
                       slot_overflow_fallback: bool = True,
                       engine: str = "auto",
                       dense_slot_cap: int | None = None,
                       pallas=None) -> dict:
    """Check one history on the device. The slot count is sized to the
    history's actual peak concurrency; long histories run as a sequence
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
    search with 'unknown' (competition racing). explain: on a definite
    invalid verdict, re-run the host oracle on the prefix ending at the
    culprit op to reconstruct configs and final-paths (the reference
    renders these via knossos.linear.report, `checker.clj:205-216`).

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
    ops = encode_ops_for_model(model, hist)
    p_exact = required_slots(ops)
    if slots is None or p_exact > slots:
        slots = _bucket(p_exact, lo=8)
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
    srange = _state_range(name, model, [ops])
    decision = select_engine(srange, p_exact, event_count(ops),
                             slots=slots, frontier=frontier,
                             engine=engine,
                             dense_slot_cap=dense_slot_cap,
                             pallas=pallas)
    dense = decision.dense
    if dense is not None:
        slots = dense[2]   # exact-P: the dense table is 2^P wide
    steps = build_steps(ops, slots)
    # capacity covers the unmerged stream so the blame re-run below
    # shares this compiled kernel
    E = _bucket(max(event_count(ops), 1))
    steps = steps.pad_to(E)
    # ABFT staged-buffer attestation: ship (possibly bitflip-injected)
    # data, then compare a device-side digest of the shipped buffer
    # with the host digest of the canonical one — corruption on the
    # staging/DMA path raises CorruptDeviceResult, which the recovery
    # ladder absorbs by re-staging from the canonical host copy.
    attest_on = attest_enabled()
    x = jnp.asarray(maybe_corrupt("offline", steps.x))
    att_info = None
    if attest_on:
        from . import abft
        abft.verify_steps("offline", guarded_device_get(
            abft.digest_device(x), site="offline attest"),
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
                    _telemetry.profile_section("wgl.offline.check"):
                ok, death, overflow, max_count, att = \
                    guarded_device_get(
                        k.check(x, jnp.int32(steps.n), init_state),
                        site="offline check")
            _check_att(att, "offline")
        else:
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
                with _telemetry.profile_section("wgl.offline.chunk"):
                    nxt = k.check_chunk(x, jnp.int32(stop), carry)
                    prev, carry = carry, nxt
                    e = stop
                    dead = int(guarded_device_get(
                        prev[-2], site="offline liveness")) == 0
                dt_chunk = _time.monotonic() - t_chunk
                chunk_obs.observe(dt_chunk)
                if chunk_i >= 2:
                    # the blocking flag read is one chunk behind, so
                    # dt_chunk measures chunk i-1: pair it with THAT
                    # chunk's op share, and start at i>=2 so chunk 0
                    # (which carries the compile) never enters the fit
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
                                carry[-2], site="offline liveness")) == 0:
                            break
                        timed_out = True
                        cancelled = stop_req and not over
                        break
            if attest_on:
                # chunk-boundary carry attestation: fetch the carry
                # with its device-computed digest, recompute on host,
                # and check the structural invariants (att == 0) —
                # silent corruption of the frontier in HBM or on the
                # fetch path surfaces here instead of in the verdict
                from . import abft
                hc, hd = guarded_device_get(
                    (carry, k.digest(carry)), site="offline attest")
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
        out["closure"] = closure_engine(dense[1], dense[2], pallas)
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
            row = _death_row(k, ops, slots, E, init_state)
            if row >= 0:
                src_index = int(ops.index[row])
                out["op"] = _find_op(hist, src_index)
                out["op-index"] = src_index
                if explain:
                    from .linear import explain_failure
                    ex = explain_failure(model, hist, src_index)
                    if ex is not None:
                        out["configs"] = ex["configs"]
                        out["final-paths"] = ex["final-paths"]
                        if ex.get("previous-ok") is not None:
                            out["previous-ok"] = ex["previous-ok"]
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
    """The forced-dense contract violation (one message, three raise
    sites: scalar, batch plain path, batch group split)."""
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
    """The batch paths' 'unknown' verdict shape (one definition so the
    grouped and plain paths can't drift)."""
    return {"valid?": "unknown", "analyzer": "tpu-wgl-batch",
            "op-count": len(ops), "error": error,
            "configs": [], "final-paths": [],
            "duration-ms": (_time.monotonic() - t0) * 1e3}


def _dispatch_groups(srange, p_req: list[int], engine: str,
                     n_events: int = 1, frontier: int = 1024,
                     dense_slot_cap: int | None = None, pallas=None):
    """Partition a batch's key indices into slot-bucketed dense dispatch
    groups plus one shared sort-frontier group.

    The dense table is S * 2^P wide, so padding every key to the worst
    key's slot count multiplies the whole batch's device work by
    2^(Pmax - P_key); bucketing nearby keys into one compiled kernel
    each recovers that while adding only a few sub-ms dispatches.
    Dense-ineligible keys gain nothing from grouping (the sort frontier
    isn't 2^P-sized), so they spill into a single sort group instead of
    paying one sort-kernel compile per bucket — or, under a forced
    dense engine, raise. Under 'auto' the cost model (select_engine)
    can also route a dense-*eligible* bucket to the sort family when
    its table work is modeled slower; n_events is the batch's largest
    event stream (per-key streams share the verdict of the comparison,
    which is length-invariant except for the one-off table init).

    Returns (dense_groups: {P: (dense_shape, [key indices])},
    sort_idx: [key indices])."""
    if engine == "sort":
        return {}, list(range(len(p_req)))
    sort_idx: list[int] = []
    dense_groups: dict[int, tuple[tuple, list[int]]] = {}
    p_max = max(p_req)
    for i, p in enumerate(p_req):
        pg = _slot_bucket(p, p_max)
        d = _dense_shape(srange, pg) or _dense_shape(srange, p)
        if d is not None and engine == "auto":
            dec = select_engine(srange, d[2], n_events,
                                frontier=frontier,
                                dense_slot_cap=dense_slot_cap,
                                pallas=pallas)
            if dec.family != "dense":
                d = None
        if d is None:
            if engine == "dense":
                raise _dense_caps_error(srange, p, key=i)
            sort_idx.append(i)
        else:
            if d[2] in dense_groups:
                dense_groups[d[2]][1].append(i)
            else:
                dense_groups[d[2]] = (d, [i])
    return dense_groups, sort_idx


def analysis_tpu_batch(model, hists: list, frontier: int = 1024,
                       slots: int = 32, chunk_entries: int = 4096,
                       budget_s: float | None = None,
                       cancel=None, engine: str = "auto",
                       max_frontier: int = 65536,
                       dense_slot_cap: int | None = None,
                       pallas=None,
                       max_recovery_retries: int | None = None,
                       _pre: list | None = None,
                       _dense=False,
                       _preq: list | None = None) -> list[dict]:
    """Recovery wrapper around _analysis_tpu_batch_once (which holds
    the batching contract — see its docstring): a classified backend
    fault re-runs the batch down the standard ladder, except the OOM
    rung SPLITS the batch in half (halving the vmapped working set)
    and recovers each half independently; the final rung decides each
    history via _final_rung (host mirror under the size cap). Results
    that went through the ladder carry a 'recovered' trail."""
    kw = dict(frontier=frontier, slots=slots,
              chunk_entries=chunk_entries, budget_s=budget_s,
              cancel=cancel, engine=engine, max_frontier=max_frontier,
              dense_slot_cap=dense_slot_cap, pallas=pallas,
              _pre=_pre, _dense=_dense, _preq=_preq)
    trail = _RecoveryTrail(max_recovery_retries)
    while True:
        try:
            rs = _analysis_tpu_batch_once(model, hists, **kw)
        except RuntimeError as e:
            if not trail.absorb(e, "batch"):
                return [_final_rung(model, h, trail, e,
                                    budget_s=budget_s, cancel=cancel)
                        for h in hists]
            kind = trail.faults[-1]
            if kind == FAULT_OOM and len(hists) > 1:
                # split/retry: each half re-enters the wrapped entry
                # with the full ladder (and half the device working
                # set); their own recovery trails merge with this one
                mid = len(hists) // 2
                log.warning("batch: splitting %d histories into "
                            "%d + %d after OOM", len(hists), mid,
                            len(hists) - mid)

                def sub(lo, hi):
                    return analysis_tpu_batch(
                        model, hists[lo:hi], frontier=frontier,
                        slots=slots, chunk_entries=kw["chunk_entries"],
                        budget_s=budget_s, cancel=cancel,
                        engine=kw["engine"], max_frontier=max_frontier,
                        dense_slot_cap=kw["dense_slot_cap"],
                        pallas=kw["pallas"],
                        max_recovery_retries=max_recovery_retries,
                        _pre=_pre[lo:hi] if _pre is not None else None,
                        _dense=_dense,
                        _preq=_preq[lo:hi] if _preq is not None
                        else None)

                rs = sub(0, mid) + sub(mid, len(hists))
                for r in rs:
                    # merge this level's trail into each sub-result —
                    # but never stamp 'recovered' on a half that fell
                    # off its own ladder (degraded + recovered is a
                    # contradiction; its fault list lives under
                    # 'recovery-failed'), and keep sub-trail markers
                    # like {'fallback': 'host'}
                    if not isinstance(r, dict):
                        continue
                    if r.get("degraded"):
                        rf = r.get("recovery-failed")
                        if isinstance(rf, dict):
                            rf["faults"] = list(trail.faults) \
                                + list(rf.get("faults", []))
                        continue
                    inner = r.get("recovered")
                    inner = dict(inner) if isinstance(inner, dict) \
                        else {}
                    faults = list(trail.faults) \
                        + list(inner.get("faults", []))
                    inner.update(faults=faults, retries=len(faults),
                                 split=True)
                    r["recovered"] = inner
                return rs
            _apply_recovery_rung(kind, kw)
            continue
        for r in rs:
            trail.stamp(r)
        return rs


def _analysis_tpu_batch_once(model, hists: list, frontier: int = 1024,
                             slots: int = 32, chunk_entries: int = 4096,
                             budget_s: float | None = None,
                             cancel=None, engine: str = "auto",
                             max_frontier: int = 65536,
                             dense_slot_cap: int | None = None,
                             pallas=None,
                             _pre: list | None = None,
                             _dense=False,
                             _preq: list | None = None) -> list[dict]:
    """Check a batch of independent histories (e.g. per-key subhistories
    from the independent workload) in vmapped device calls. Long batches
    run as bounded-duration chunks with the vmapped frontier carried
    between calls, polling budget_s / cancel like the scalar path —
    a pathological key can no longer stall an independent batch
    unboundedly. Undecided keys at the budget report 'unknown'.

    Escalation is batched: every overflow-suspect key re-runs together
    in one vmapped call at 4x the frontier (recursively), instead of
    degrading to serial per-key searches; likewise culprit-op blame for
    definite invalids runs as one vmapped unmerged pass.

    _pre: internal — pre-encoded OpArrays (one per history), passed by
    the group-split recursion so each history is encoded exactly once.
    _dense: internal — the group's dense shape from _dispatch_groups
    (False = derive it here), so bucketed groups share the bucket's
    compiled kernel instead of re-deriving a data-dependent shape from
    the group-local state range. _preq: internal — the group's
    required_slots values, already scanned by the parent (the
    group-local state range is deliberately NOT passed: recomputing it
    over a narrower group can make a spilled sort group dense-eligible)."""
    import jax
    import jax.numpy as jnp

    t0 = _time.monotonic()

    def _remaining():
        if budget_s is None:
            return None
        return max(0.0, budget_s - (_time.monotonic() - t0))

    name = model.device_model
    pre = (_pre if _pre is not None
           else [encode_ops_for_model(model, h) for h in hists])
    _srange = _p_needs = None   # pre-pass reuse for the one-bucket case
    if engine in ("auto", "dense") and len(hists) > 1 and _pre is None:
        # Slot-bucketed dispatch groups (see _dispatch_groups): recurse
        # per group — each group is then bucket-uniform and runs the
        # plain batched path below. Dense groups run cheapest-first and
        # the sort group last, so a pathological dense-ineligible key
        # can only starve itself of budget, not the cheap keys.
        p_req = [required_slots(ops) for ops in pre]
        srange_all = _state_range(name, model, pre)
        dense_groups, sort_idx = _dispatch_groups(
            srange_all, p_req, engine,
            n_events=max((event_count(o) for o in pre), default=1),
            frontier=frontier, dense_slot_cap=dense_slot_cap,
            pallas=pallas)
        group_list = [dense_groups[pg] for pg in sorted(dense_groups)]
        if sort_idx:
            group_list.append((False, sort_idx))
        if len(group_list) > 1:
            grouped: list[dict | None] = [None] * len(hists)
            for d, idx in group_list:
                rem = _remaining()
                if (rem == 0.0) or (cancel is not None and cancel()):
                    # budget gone: report the remaining groups without
                    # dispatching even one chunk for them
                    for i in idx:
                        grouped[i] = _unknown_result(
                            pre[i], "batch budget exhausted/cancelled "
                            "before this key's search started", t0)
                    continue
                sub = analysis_tpu_batch(
                    model, [hists[i] for i in idx], frontier=frontier,
                    slots=slots, chunk_entries=chunk_entries,
                    budget_s=rem, cancel=cancel, engine=engine,
                    max_frontier=max_frontier,
                    dense_slot_cap=dense_slot_cap, pallas=pallas,
                    _pre=[pre[i] for i in idx], _dense=d,
                    _preq=[p_req[i] for i in idx])
                for t, i in enumerate(idx):
                    grouped[i] = sub[t]
            return grouped
        # one bucket: fall through to the plain path, reusing the
        # pre-pass instead of rescanning every history
        if group_list and group_list[0][0] is not False:
            _dense = group_list[0][0]
        else:
            _srange, _p_needs = srange_all, dict(enumerate(p_req))

    results: list[dict | None] = [None] * len(hists)
    encoded = list(enumerate(pre))
    items = []           # (orig index, ops, steps)
    if encoded:
        if _dense is not False:
            # the bucket's shape, shared group-wide; the group-local
            # state range and slot needs would be dead recomputation
            # (the dense kernel's shape carries both)
            dense, srange, p_needs = _dense, None, None
        else:
            srange = (_srange if _srange is not None else
                      _state_range(name, model, [o for _, o in encoded]))
            if _p_needs is not None:
                p_needs = _p_needs
            elif _preq is not None:
                p_needs = dict(enumerate(_preq))
            else:
                p_needs = {i: required_slots(o) for i, o in encoded}
            dense = None
            if engine in ("auto", "dense"):
                # same contract as the scalar path and the multi-key
                # grouped split: a forced dense engine never silently
                # degrades to the sort kernel (select_engine raises).
                # Decided BEFORE the budget early-exit below so the
                # contract violation surfaces identically for
                # zero-budget calls.
                dense = select_engine(
                    srange, max(p_needs.values()),
                    max((event_count(o) for _, o in encoded),
                        default=1),
                    frontier=frontier, engine=engine,
                    dense_slot_cap=dense_slot_cap,
                    pallas=pallas).dense
        if dense is not None:
            slots = dense[2]
        if ((_remaining() == 0.0) or (cancel is not None and cancel())):
            # budget already gone: report unknown before the per-key
            # scalar fallback below can dispatch full searches
            for i, ops in encoded:
                results[i] = _unknown_result(
                    ops, "batch budget exhausted/cancelled before "
                    "this key's search started", t0)
            encoded = []
        for i, ops in encoded:
            if dense is None and p_needs[i] > slots:
                # this key alone exceeds the batch's slot budget:
                # scalar path re-sizes (and host-falls-back past 256)
                results[i] = analysis_tpu(
                    model, hists[i], frontier, budget_s=_remaining(),
                    cancel=cancel, engine=engine,
                    dense_slot_cap=dense_slot_cap, pallas=pallas)
            else:
                items.append((i, ops, build_steps(ops, slots)))
    if items and ((_remaining() == 0.0)
                  or (cancel is not None and cancel())):
        # budget already gone: report unknown without dispatching even
        # the first chunk (the chunk loop below always runs one)
        for i, ops, _st in items:
            results[i] = _unknown_result(
                ops, "batch budget exhausted/cancelled before "
                "this key's search started", t0)
        items = []
    if items:
        E = _bucket(max(max(event_count(ops) for _, ops, _ in items), 1))
        padded = [st.pad_to(E) for _, _, st in items]
        # bucket the batch axis like E: the vmapped kernels are jitted
        # per (B, E) shape, so an exact B would recompile the whole
        # family for every distinct key count — pad with zero-step
        # entries (n=0: never consumed, frontier stays at the initial
        # config), skipped by the per-item j < len(items) reads below
        padded += [Steps.empty(padded[0].w, E)] * (
            _bucket(len(padded), lo=1) - len(padded))
        if dense is not None:
            k = _dense_kernel(name, dense[0], dense[1], dense[2], E,
                              pallas=pallas)
        else:
            k = _kernel(name, frontier, slots, E,
                        _pack_params(srange, slots), pallas=pallas)
        x_np = np.stack([st.x for st in padded])
        attest_on = attest_enabled()
        x = jnp.asarray(maybe_corrupt("batch", x_np))
        if attest_on:
            # staged-buffer attestation (see the offline twin): the
            # whole vmapped stack ships as one buffer, one digest
            from . import abft
            abft.verify_steps("batch", guarded_device_get(
                abft.digest_device(x), site="batch attest"),
                abft.digest_host(x_np))
        ns = np.asarray([st.n for st in padded], np.int32)
        s0 = jnp.full(len(padded), model.device_state(), jnp.int32)
        carry = jax.vmap(k.init_carry)(s0)
        e = 0
        n_max = int(ns.max())
        # pipelined like the scalar loop: enqueue the next vmapped
        # chunk, then read the PREVIOUS chunk's frontier counts while
        # the device computes — all-dead detection lags one chunk
        # (safe: dead frontiers stay dead) in exchange for overlapping
        # the per-chunk sync with compute
        chunk_obs = _M_CHUNK.labels(
            site="batch", family="dense" if dense is not None
            else "sort")
        while e < n_max:
            stop = min(e + chunk_entries, n_max)
            maybe_inject_fault("batch")
            t_chunk = _time.monotonic()
            with _telemetry.profile_section("wgl.batch.chunk"):
                nxt = k.check_chunk_batch(
                    x, jnp.asarray(np.minimum(ns, stop)), carry)
                prev, carry = carry, nxt
                e = stop
                # pad entries never consume, so their frontiers stay
                # alive forever — only the real items' liveness counts
                all_dead = not np.asarray(guarded_device_get(
                    prev[-2],
                    site="batch liveness"))[:len(items)].any()
            chunk_obs.observe(_time.monotonic() - t_chunk)
            if all_dead:
                carry = prev   # every frontier died: all definite
                break
            if e < n_max:
                if (budget_s is not None
                        and _time.monotonic() - t0 > budget_s) \
                        or (cancel is not None and cancel()):
                    break
        if attest_on:
            # per-key carry attestation at the batch's final boundary
            from . import abft
            hc, hd = guarded_device_get(
                (carry, jax.vmap(k.digest)(carry)), site="batch attest")
            for bi in range(len(np.asarray(hd))):
                abft.verify_carry(
                    "batch", np.asarray(hd)[bi],
                    tuple(np.asarray(a)[bi] for a in hc))
        # ONE guarded fetch for the verdicts AND the carry components
        # the decided-mask below needs: the consumed/count buffers were
        # previously pulled via raw np.asarray — an unguarded implicit
        # sync (JTS103) and a second device round-trip
        (ok, death, overflow, max_count, att), consumed, counts = \
            guarded_device_get(
                (jax.vmap(k.summarize)(carry), carry[0], carry[-2]),
                site="batch summarize")
        _check_att(np.asarray(att).sum(), "batch")
        _M_OPS.labels(site="batch").inc(
            sum(len(o) for _, o, _ in items))
        batch_dedup = (DEDUP_NONE if dense is not None else
                       dedup_engine(frontier, slots,
                                    _pack_params(srange, slots),
                                    pallas))
        # a key is decided if it consumed all entries or its frontier
        # died (death is definitive no matter how many entries remain)
        decided = (np.asarray(consumed) >= ns) | (counts == 0)
        suspects = []    # overflow + invalid: escalate together
        invalids = []    # definite invalid: blame together
        for j, (i, ops, st) in enumerate(items):
            if not bool(decided[j]):
                results[i] = _unknown_result(
                    ops, "batch budget exhausted/cancelled before "
                    "this key's search finished", t0)
            elif bool(ok[j]):
                results[i] = {
                    "valid?": True, "analyzer": "tpu-wgl-batch",
                    "dedup": batch_dedup,
                    "op-count": len(ops),
                    "max-frontier": int(max_count[j]),
                    "configs": [], "final-paths": []}
            elif bool(overflow[j]):
                suspects.append((i, ops))
            else:
                invalids.append((j, i, ops))
        if invalids:
            # one vmapped unmerged pass names every culprit op (the
            # unmerged streams fit E by construction)
            st2s = [build_steps(ops, slots, merge=False).pad_to(E)
                    for _, _, ops in invalids]
            st2s += [Steps.empty(st2s[0].w, E)] * (
                _bucket(len(st2s), lo=1) - len(st2s))
            okb, deathb, *_ = guarded_device_get(k.check_batch(
                jnp.asarray(np.stack([s.x for s in st2s])),
                jnp.asarray(np.asarray([s.n for s in st2s], np.int32)),
                jnp.full(len(st2s), model.device_state(), jnp.int32)))
            for t, (j, i, ops) in enumerate(invalids):
                r = {"valid?": False, "analyzer": "tpu-wgl-batch",
                     "dedup": batch_dedup,
                     "op-count": len(ops),
                     "max-frontier": int(max_count[j]),
                     "configs": [], "final-paths": []}
                d = int(deathb[t])
                if not bool(okb[t]) and d >= 0:
                    row = int(st2s[t].inv_row[d])
                    if row < 0:
                        row = int(st2s[t].ret_row[d])
                    if row >= 0:
                        src = int(ops.index[row])
                        r["op"] = _find_op(hists[i], src)
                        r["op-index"] = src
                results[i] = r
        if suspects:
            if frontier < max_frontier:
                sub = analysis_tpu_batch(
                    model, [hists[i] for i, _ in suspects],
                    frontier=frontier * 4, slots=slots,
                    chunk_entries=chunk_entries, budget_s=_remaining(),
                    cancel=cancel, engine=engine,
                    max_frontier=max_frontier,
                    dense_slot_cap=dense_slot_cap, pallas=pallas)
                for t, (i, _ops) in enumerate(suspects):
                    results[i] = sub[t]
            else:
                for i, ops in suspects:
                    results[i] = _unknown_result(
                        ops, f"frontier overflowed at {frontier}; "
                        f"escalation cap {max_frontier} reached — "
                        "verdict unknown", t0)
        if attest_on:
            for i, _ops, _st in items:
                r = results[i]
                if isinstance(r, dict):
                    r.setdefault("attested", {"steps": 1, "carry": 1})
    dur = (_time.monotonic() - t0) * 1e3
    for r in results:
        if r is not None:
            r.setdefault("duration-ms", dur)
    return results  # type: ignore[return-value]


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
    p_req = [required_slots(ops) for ops in all_ops]

    # Slot-bucketed dispatch groups (see _dispatch_groups): on the
    # hazelcast bench shape (100 keys, ~2.5 crashes/key) the max-padded
    # table sums to 14x the per-key need; grouping recovers it for a
    # couple of extra sub-ms dispatches.
    dense_groups, sort_idx = _dispatch_groups(
        srange, p_req, engine,
        n_events=max((event_count(o) for o in all_ops), default=1),
        frontier=frontier, dense_slot_cap=dense_slot_cap, pallas=pallas)
    group_info: list[dict] = []

    def run_group(idx: list[int], dense):
        """One vmapped + mesh-sharded dispatch over the keys in idx."""
        if dense is not None:
            g_slots = dense[2]
        else:
            # the sort group sizes itself to its own keys — never below
            # the caller's slots, never a SlotOverflow on a key the
            # dense caps rejected
            g_slots = max(slots, _bucket(max(p_req[i] for i in idx),
                                         lo=8))
        steps_list = [build_steps(all_ops[i], g_slots) for i in idx]
        E = _bucket(max(max(st.n for st in steps_list), 1))
        w = steps_list[0].w
        gk = len(idx)
        g_pad = -(-gk // n_dev) * n_dev
        padded = [st.pad_to(E) for st in steps_list]
        padded += [Steps.empty(w, E)] * (g_pad - gk)

        group_info.append({
            "family": "dense" if dense is not None else "sort",
            "dedup": (DEDUP_NONE if dense is not None else
                      dedup_engine(frontier, g_slots,
                                   _pack_params(srange, g_slots),
                                   pallas)),
            "keys": gk, "slots": g_slots})
        run = _sharded_runner(name, dense, frontier, g_slots, srange,
                              E, mesh, axis, pallas=pallas)
        maybe_inject_fault("sharded")
        x_np = np.stack([st.x for st in padded])
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
            jax.device_put(np.asarray([st.n for st in padded], np.int32),
                           keys_sharding),
            jax.device_put(np.full(g_pad, model.device_state(), np.int32),
                           keys_sharding))
        return all_ok_g, ok_g, ov_g, att_g, att

    attest_on = attest_enabled()
    pending = [(idx, run_group(idx, d))
               for d, idx in (dense_groups[pg]
                              for pg in sorted(dense_groups))]
    if sort_idx:
        pending.append((sort_idx, run_group(sort_idx, None)))
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
