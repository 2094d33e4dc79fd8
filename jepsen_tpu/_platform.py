"""Platform plumbing: compile-cache placement, fault classification,
fault injection.

This module is the one place the checkers learn what a backend
failure *means*. jax surfaces every device-path failure as a
RuntimeError (a jax.errors.JaxRuntimeError), which tells a recovery
ladder nothing about what to do next; `classify_backend_error` buckets
them into the four faults a production checking service on preemptible
TPUs actually sees — OOM, device loss/preemption, compile failure, and
a wedged backend — and returns None for ordinary RuntimeErrors, which
are checker bugs, not device faults, and must never trigger recovery
(or masquerade as degradation in `check_safe`).

Because real faults are hard to produce on demand, the same module
carries the test-only injection shim: `maybe_inject_fault(site)` is
called immediately before every recovery-aware device dispatch, and
either the `JEPSEN_TPU_FAULT_INJECT` env knob (``kind@site:n`` — raise
an InjectedFault of `kind` at the n-th dispatch on `site`), an
installed :class:`FaultSchedule` (an ORDERED multi-event schedule:
each event arms only after the previous one fired, so `oom` at chunk
3 *then* `bitflip` one staging later lands the second fault inside
the first one's recovery replay), or the monkeypatchable `fault_hook`
makes each bucket deterministically reproducible in tier-1, on CPU,
with no hardware.

The chaos harness (jepsen_tpu/chaos/) additionally listens through
`probe_hook`: the pipeline emits tiny lifecycle/recovery *probe*
events (replay begin/end, fault absorbed, stream state transitions)
through :func:`probe`, which is a no-op unless a harness installed a
hook — production pays one attribute check."""

from __future__ import annotations

import fnmatch
import os
import threading

# Fault buckets (classify_backend_error return values). Anything the
# classifier recognizes as a backend failure but cannot place more
# precisely lands in FAULT_WEDGED — the "wedged-other" rung, handled
# with a plain bounded retry. FAULT_CORRUPT is raised by the checkers
# THEMSELVES (checker/abft.py): an ABFT checksum mismatch means a
# staged buffer or device result was silently corrupted — the rung is
# a re-stage/replay from canonical host data.
FAULT_OOM = "oom"
FAULT_DEVICE_LOST = "device-lost"
FAULT_COMPILE = "compile"
FAULT_WEDGED = "wedged"
FAULT_CORRUPT = "corrupt"
FAULT_KINDS = (FAULT_OOM, FAULT_DEVICE_LOST, FAULT_COMPILE,
               FAULT_WEDGED, FAULT_CORRUPT)

FAULT_INJECT_ENV = "JEPSEN_TPU_FAULT_INJECT"
SYNC_DEADLINE_ENV = "JEPSEN_TPU_SYNC_DEADLINE_S"
ATTEST_ENV = "JEPSEN_TPU_ATTEST"


class InjectedFault(RuntimeError):
    """A deterministic stand-in for a backend fault (test/bench only).

    Subclasses RuntimeError — the same surface jax's real backend
    errors present — so the recovery ladders exercise exactly the
    production catch/classify/retry path."""

    def __init__(self, kind: str, site: str, seq: int):
        super().__init__(
            f"injected {kind} fault at {site} dispatch #{seq}")
        self.kind = kind


class CorruptDeviceResult(RuntimeError):
    """An ABFT attestation checksum disagreed: a staged buffer, a
    device reduction, or a fetched carry was silently corrupted
    (bit-flip in HBM / on the transfer path / in a compute unit).

    Classified FAULT_CORRUPT so the recovery ladders treat silent
    corruption like any other backend fault: re-stage from canonical
    host data (offline/batch/sharded), or restore the last carry
    checkpoint and replay the host-side steps log (stream) — the
    resumed verdict is identical to an uncorrupted run's, instead of
    confidently wrong."""

    kind = FAULT_CORRUPT

    def __init__(self, site: str, detail: str):
        super().__init__(
            f"attestation mismatch at {site}: {detail}")
        self.site = site


class WedgedDeviceSync(RuntimeError):
    """A blocking device sync exceeded its watchdog deadline.

    Raised by guarded_device_get; per util.timeout semantics the
    blocked fetch is *abandoned*, not killed — it may still complete in
    the background, and its late result is discarded. Classified as
    FAULT_WEDGED so the recovery ladders treat a hung TPU call as a
    recoverable fault instead of hanging analyze forever."""

    kind = FAULT_WEDGED


def _xla_error_types() -> tuple:
    """jax's backend-error class, lazily (jax may not be imported —
    or even importable — when the host-only paths run)."""
    try:
        from jax.errors import JaxRuntimeError
    except ImportError:
        return ()
    return (JaxRuntimeError,)


# message fragments → bucket, checked in order (an OOM message may also
# contain "allocator", a preemption may mention the device — first
# match wins, and the more specific buckets come first)
_FAULT_PATTERNS = (
    (FAULT_OOM, ("resource_exhausted", "out of memory", "oom",
                 "allocation failure", "failed to allocate")),
    (FAULT_DEVICE_LOST, ("device_lost", "device lost", "unavailable",
                         "preempt", "halted", "device or chip",
                         "data_loss", "connection reset")),
    (FAULT_COMPILE, ("mosaic", "compilation", "compile",
                     "unimplemented", "lowering")),
    (FAULT_WEDGED, ("deadline_exceeded", "timed out", "timeout")),
)


# jax's backend-*initialization* failures are plain RuntimeErrors
# (xla_bridge.py raises RuntimeError(f"Unable to initialize backend
# '{platform}': ...")); libtpu init failures surface similarly. These
# exact signatures classify as device-lost even without the
# JaxRuntimeError type.
_PLAIN_INIT_FRAGS = ("unable to initialize backend",
                     "failed to initialize tpu")


def classify_backend_error(exc: BaseException) -> str | None:
    """Bucket a backend failure into one of FAULT_KINDS, or None when
    the exception is an ordinary bug rather than the device path
    falling over.

    Only jax's JaxRuntimeError family (plus this module's own fault
    types, which carry an explicit ``kind``) classify: a plain
    RuntimeError raised by checker logic returns None, so recovery
    ladders re-raise it and `check_safe` reports it as a checker error
    instead of device degradation. A JaxRuntimeError whose message
    matches no pattern still classifies — as FAULT_WEDGED, the
    retry-and-see bucket. The one plain-RuntimeError carve-out is
    backend *initialization* failure (_PLAIN_INIT_FRAGS): xla_bridge
    raises those untyped, and they are unambiguously the device path
    falling over. Those fragments are matched as substrings — jax
    prepends status prefixes like 'INTERNAL:' so anchoring to the
    message start would miss them — but each is a full distinctive
    phrase, not a keyword, so a checker bug only matches by quoting
    the backend's own failure text (in which case device-lost is the
    right call anyway)."""
    kind = getattr(exc, "kind", None)
    if kind in FAULT_KINDS:
        return kind
    if not isinstance(exc, _xla_error_types()):
        # one narrow exception to the JaxRuntimeError-only rule: jax's
        # xla_bridge raises a PLAIN RuntimeError when a backend fails
        # to initialize (a dead/unreachable device at first touch) —
        # that is the device path falling over, not a checker bug, so
        # it must reach the device-lost rung. The allowlist holds full
        # distinctive phrases (matched as substrings — jax prepends
        # status prefixes like 'INTERNAL:'), not keywords, so checker
        # bugs don't match unless they quote the backend's own text.
        if type(exc) is RuntimeError:
            msg = str(exc).lower()
            if any(f in msg for f in _PLAIN_INIT_FRAGS):
                return FAULT_DEVICE_LOST
        return None
    msg = str(exc).lower()
    for bucket, frags in _FAULT_PATTERNS:
        if any(f in msg for f in frags):
            return bucket
    return FAULT_WEDGED


def backend_reinit() -> None:
    """Best-effort in-process backend re-initialization after a
    device-lost fault: drop jax's live compiled-executable caches so
    the next dispatch rebuilds device state instead of re-poking dead
    buffers. The kernel-level LRU caches (wgl._kernel and friends) are
    cleared by the callers that own them."""
    try:
        import jax
        jax.clear_caches()
    except Exception:  # noqa: BLE001 — reinit is best-effort by design
        pass


# ---------------------------------------------------------------------------
# Fault injection (tests / bench only)
# ---------------------------------------------------------------------------

# Monkeypatchable hook around dispatch: fn(site) -> None, may raise.
# Checked on every maybe_inject_fault call, before the env knob.
fault_hook = None

# Monkeypatchable hook around staging: fn(site, arr) -> ndarray | None
# (None = leave the buffer alone). Checked on every maybe_corrupt
# call, before the env knob — the bitflip analog of fault_hook, for
# corruption schedules the env spec can't express.
corrupt_hook = None

# the deterministic bit a bitflip clause flips (bit 12 of the middle
# element): any single flipped bit is detected by the attestation
# digests, and a fixed site keeps the injected corruption reproducible
BITFLIP_KIND = "bitflip"
_BITFLIP_BIT = 12

_fault_seq: dict[str, int] = {}
_corrupt_seq: dict[str, int] = {}


class FaultEvent:
    """One scheduled fault: raise/flip `kind` at the `after`-th hit on
    a site matching `site` (fnmatch pattern — ``stream-chunk/*``
    matches every stream), counted from the moment the event ARMS.
    The first event arms at install; each later event arms when its
    predecessor fires — triggers are relative, which is what lets a
    schedule express "one staging into the recovery replay"."""

    __slots__ = ("kind", "site", "after")

    def __init__(self, kind: str, site: str, after: int = 1):
        if after < 1:
            raise ValueError(f"after must be >= 1, got {after}")
        self.kind = kind
        self.site = site
        self.after = int(after)

    def __repr__(self) -> str:
        return f"{self.kind}@{self.site}:{self.after}"

    def to_dict(self) -> dict:
        return {"kind": self.kind, "site": self.site,
                "after": self.after}

    @classmethod
    def from_dict(cls, d: dict) -> "FaultEvent":
        return cls(d["kind"], d["site"], int(d.get("after", 1)))


class FaultSchedule:
    """An ordered list of FaultEvents, advanced by the injection shim.

    Unlike the env knob's clauses — which all count the SAME absolute
    per-site counters and therefore cannot say "after the first fault
    fired" — schedule events arm strictly in order: event i+1 starts
    counting hits only once event i fired. ``bitflip`` events consume
    staging hits (maybe_corrupt); every other kind consumes dispatch
    hits (maybe_inject_fault). Thread-safe: the service pumps streams
    from many worker threads. `fired` records (kind, site, hit) per
    fired event for the chaos stamp-consistency oracle."""

    def __init__(self, events):
        self.events = [e if isinstance(e, FaultEvent)
                       else FaultEvent.from_dict(e) for e in events]
        self._lock = threading.Lock()
        self._i = 0             # guarded-by: _lock
        self._hits = 0          # hits on the armed event's site
        self.fired: list = []   # guarded-by: _lock

    @classmethod
    def from_clauses(cls, clauses) -> "FaultSchedule":
        """Build from ``kind@site:n`` strings (the env-knob grammar,
        but ordered: n counts hits after the previous clause fired)."""
        events = []
        for clause in clauses:
            clause = clause.strip()
            if not clause:
                continue
            kind, _, rest = clause.partition("@")
            site, _, after = rest.partition(":")
            events.append(FaultEvent(kind, site, int(after or 1)))
        return cls(events)

    def done(self) -> bool:
        with self._lock:
            return self._i >= len(self.events)

    def remaining(self) -> int:
        with self._lock:
            return len(self.events) - self._i

    def _advance(self, site: str, staging: bool):
        """One hit on `site`. Returns the armed event when it fires
        (caller raises/flips outside the lock), else None."""
        with self._lock:
            if self._i >= len(self.events):
                return None
            evt = self.events[self._i]
            if (evt.kind == BITFLIP_KIND) != staging:
                return None
            if not fnmatch.fnmatch(site, evt.site):
                return None
            self._hits += 1
            if self._hits < evt.after:
                return None
            self._i += 1
            self._hits = 0
            self.fired.append((evt.kind, site, evt.after))
            return evt

    def on_dispatch(self, site: str) -> None:
        evt = self._advance(site, staging=False)
        if evt is not None:
            probe("inject", kind=evt.kind, site=site,
                  source="schedule")
            raise InjectedFault(evt.kind, site, evt.after)

    def on_staging(self, site: str, arr):
        evt = self._advance(site, staging=True)
        if evt is None:
            return arr
        probe("corrupt", kind=evt.kind, site=site, source="schedule")
        return flip_bit(arr)


# the installed schedule, if any (chaos harness / tests only)
_schedule: FaultSchedule | None = None


def install_fault_schedule(
        schedule: "FaultSchedule | None") -> "FaultSchedule | None":
    """Install (or clear, with None) the process-wide fault schedule.
    Returns the previous one. reset_fault_injection() also clears it."""
    global _schedule
    prev, _schedule = _schedule, schedule
    return prev


def current_fault_schedule() -> "FaultSchedule | None":
    return _schedule


# -- chaos probes (jepsen_tpu/chaos/ and tests only) ------------------------

# fn(event: dict) -> None; None = probes are free (one attr check)
probe_hook = None


def probe(event: str, **info) -> None:
    """Emit one chaos probe event ({"event": ..., **info}) to the
    installed hook. Never raises — a broken harness must not take the
    pipeline down with it."""
    hook = probe_hook
    if hook is None:
        return
    d = {"event": event}
    d.update(info)
    try:
        hook(d)
    except Exception:  # noqa: BLE001 — observability must not break us
        pass


def reset_fault_injection() -> None:
    """Zero the per-site dispatch/staging counters and drop any
    installed schedule (each test starts its own deterministic
    injection schedule)."""
    global _schedule
    _fault_seq.clear()
    _corrupt_seq.clear()
    _schedule = None


def maybe_inject_fault(site: str) -> None:
    """Called immediately before each recovery-aware device dispatch.

    Sites in use: 'offline' (wgl.analysis_tpu), 'batch'
    (wgl.analysis_tpu_batch), 'sharded' (wgl.check_batch_sharded),
    'stream-chunk' (streaming.WglStream), 'elle'
    (elle.kernels._classify_batches). The env spec is a
    comma-separated list of ``kind@site:n`` clauses; the n-th dispatch
    on a matching site raises InjectedFault(kind) (n is 1-based and
    counts every dispatch since reset_fault_injection(), so a
    recovery retry advances the counter past the clause — the fault
    fires once, like a real transient). ``bitflip`` clauses never
    raise here — they corrupt staged buffers via maybe_corrupt, on a
    separate per-site staging counter."""
    n = _fault_seq.get(site, 0) + 1
    _fault_seq[site] = n
    hook = fault_hook
    if hook is not None:
        hook(site)
    sched = _schedule
    if sched is not None:
        sched.on_dispatch(site)
    spec = os.environ.get(FAULT_INJECT_ENV)
    if not spec:
        return
    for clause in spec.split(","):
        clause = clause.strip()
        if not clause:
            continue
        kind, _, rest = clause.partition("@")
        if kind == BITFLIP_KIND:
            continue   # silent-corruption clauses act at staging time
        tsite, _, seq = rest.partition(":")
        if tsite == site and n == int(seq or 1):
            probe("inject", kind=kind, site=site, source="env")
            raise InjectedFault(kind, site, n)


def maybe_corrupt(site: str, arr):
    """Called on each host-staged device buffer right before it ships.

    A ``bitflip@site:n`` clause in JEPSEN_TPU_FAULT_INJECT flips one
    bit (_BITFLIP_BIT of the middle element) in a COPY of the n-th
    staged buffer on that site — the caller ships the returned array
    while its canonical host copy (and therefore the attestation
    digest it computes from it) stays intact, exactly the shape of a
    silent DMA/HBM bit-flip. n counts stagings since
    reset_fault_injection(), so a recovery retry's re-stage advances
    past the clause and ships clean data, like a real transient.
    corrupt_hook(site, arr) -> ndarray|None is checked first, for
    schedules the env spec can't express. Returns the array to ship
    (the original object when nothing matched: zero-copy)."""
    n = _corrupt_seq.get(site, 0) + 1
    _corrupt_seq[site] = n
    hook = corrupt_hook
    if hook is not None:
        out = hook(site, arr)
        if out is not None:
            return out
    sched = _schedule
    if sched is not None:
        out = sched.on_staging(site, arr)
        if out is not arr:
            return out
    spec = os.environ.get(FAULT_INJECT_ENV)
    if not spec:
        return arr
    for clause in spec.split(","):
        clause = clause.strip()
        if not clause:
            continue
        kind, _, rest = clause.partition("@")
        if kind != BITFLIP_KIND:
            continue
        tsite, _, seq = rest.partition(":")
        if tsite == site and n == int(seq or 1):
            probe("corrupt", kind=kind, site=site, source="env")
            return flip_bit(arr)
    return arr


def flip_bit(arr, bit: int = _BITFLIP_BIT):
    """A copy of arr with one bit flipped in its middle element (the
    deterministic corruption bitflip clauses inject)."""
    import numpy as np

    out = np.array(arr, copy=True)
    flat = out.reshape(-1).view(np.uint32 if out.dtype.itemsize == 4
                                else np.uint8)
    flat[len(flat) // 2] ^= np.uint32(1 << bit) if flat.dtype.itemsize \
        == 4 else np.uint8(1 << (bit % 8))
    return out


def attest_enabled(override=None) -> bool:
    """Is ABFT attestation on? An explicit checker option beats the
    JEPSEN_TPU_ATTEST env gate (default ON — always-on verification is
    the point; =0 opts out, e.g. to measure the unguarded baseline).
    Resolved outside the kernel caches so flipping it mid-process
    takes effect on the next call."""
    if override is not None:
        return bool(override)
    return os.environ.get(ATTEST_ENV, "1") != "0"


# ---------------------------------------------------------------------------
# Watchdog: bounded device syncs
# ---------------------------------------------------------------------------

def sync_deadline_s() -> float | None:
    """The watchdog deadline for blocking device syncs, from
    JEPSEN_TPU_SYNC_DEADLINE_S (seconds; unset/0 = unbounded, the
    pre-watchdog behavior — the knob exists because a deadline costs
    one daemon thread per guarded sync)."""
    raw = os.environ.get(SYNC_DEADLINE_ENV)
    if not raw:
        return None
    try:
        v = float(raw)
    except ValueError:
        return None
    return v if v > 0 else None


def guarded_device_get(x, deadline_s: float | None = None,
                       site: str = "device-sync"):
    """jax.device_get under a watchdog deadline: a wedged TPU call
    becomes a WedgedDeviceSync (a classified, recoverable fault)
    instead of blocking its caller forever. deadline_s=None defers to
    the env knob; with neither set this is a plain device_get with no
    thread spawned."""
    import jax

    if deadline_s is None:
        deadline_s = sync_deadline_s()
    if not deadline_s:
        return jax.device_get(x)
    from .util import TIMED_OUT, timeout
    r = timeout(deadline_s, lambda: jax.device_get(x),
                default=TIMED_OUT, name=f"jepsen-watchdog {site}")
    if r is TIMED_OUT:
        raise WedgedDeviceSync(
            f"device sync at {site} still blocked after {deadline_s}s "
            f"(watchdog); treating the backend as wedged")
    return r


def compilation_cache_dir() -> str:
    """The one place JAX's persistent compilation cache lives:
    JAX_COMPILATION_CACHE_DIR when the environment sets it, else
    `<checkout>/.jax_cache` (gitignored). A fixed path matters — the
    path is part of the cache key, so a directory that moves with the
    store dir or HOME never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def use_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at
    `compilation_cache_dir()`. Entry points (CLI, service daemon,
    bench sections, chip_smoke.py) call this before their first
    compile; nothing calls it at import. It goes through
    `jax.config.update` because JAX reads the environment variable
    only when jax itself is imported, and resets JAX's cache handle so
    a compile that already ran (and found no cache) does not pin the
    cache off. Returns the directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    d = compilation_cache_dir()
    jax.config.update("jax_compilation_cache_dir", d)
    compilation_cache.reset_cache()
    return d
