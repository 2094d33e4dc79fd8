"""ABFT attestation: host-verifiable digests over staged buffers and
device carries.

The recovery ladder (doc/robustness.md) handles *loud* backend faults;
this module closes the silent half: a bit-flip in a staged step
buffer, in HBM under a live carry, or on the fetch path would
otherwise yield a confidently wrong verdict. Following GCN-ABFT
(arXiv 2412.18534), every guarded value is covered by a cheap
checksum computed twice through independent paths:

  * **Staged-buffer digests.** The host computes a position-weighted
    wrap-around int32 digest over the canonical numpy buffer; a tiny
    jitted reduction computes the same digest over the device copy.
    Disagreement means the data was corrupted between staging and the
    kernel's first read — the exact window a DMA/HBM flip occupies.
    Both sides run the identical modular arithmetic (sums and
    products mod 2^32 are independent of intermediate wrap points),
    so a mismatch is never a rounding artifact and any single flipped
    bit changes the digest.
  * **Carry digests.** The kernels expose ``Kernel.digest(carry)`` —
    an on-device mix over the carry arrays (including the in-kernel
    ``att`` invariant accumulator). At chunk boundaries where the
    carry is fetched anyway (stream checkpoints, offline summarize)
    the host recomputes the mix from the fetched arrays: a mismatch
    means the carry changed between the device's reduction and the
    fetch. ``verify_carry`` additionally checks the structural
    invariants the host can see (att == 0, count == live-config
    population).

A mismatch raises ``_platform.CorruptDeviceResult`` (fault kind
``corrupt``), which climbs the existing recovery ladder: offline /
batch / sharded entries re-stage from canonical host data, streams
restore the last carry checkpoint and replay the steps log — silent
corruption becomes a *resumed* verdict instead of a wrong one.

Float buffers (the Elle adjacency stacks) are digested over their BIT
PATTERNS (bitcast to int32), so detection is exact there too — no
float-tolerance window for a low-mantissa flip to hide in.
"""

from __future__ import annotations

import functools

import numpy as np

from .. import telemetry as _telemetry
from .._platform import CorruptDeviceResult

_M_VERIFY = _telemetry.counter(
    "jepsen_tpu_abft_verifications_total",
    "ABFT digest verifications by kind (steps = staged buffers, "
    "carry = fetched carries)", ("kind",))
_M_FAIL = _telemetry.counter(
    "jepsen_tpu_abft_failures_total",
    "ABFT attestation mismatches (silent corruption detected)",
    ("kind",))

_MASK = 0xFFFFFFFF
# position weight period: coprime-ish to power-of-two shapes so equal
# elements at different offsets contribute distinct terms
_W_PERIOD = 8191


def _to_i32(x: int) -> int:
    """Wrap a python int to signed 32-bit (the device digest dtype)."""
    x &= _MASK
    return x - (1 << 32) if x >= (1 << 31) else x


@functools.lru_cache(maxsize=None)
def _weights64(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.int64) % _W_PERIOD) + 1


def digest_host(arr: np.ndarray) -> int:
    """Position-weighted digest of a host buffer, as signed int32.

    Computed in int64 and masked: sums/products mod 2^32 match the
    device's wrapping int32 arithmetic exactly, regardless of where
    the intermediate wraps land."""
    a = np.asarray(arr)
    if a.dtype.kind == "f":
        a = a.view(np.int32)       # bit pattern, not value
    elif a.dtype == np.uint32:
        a = a.view(np.int32)
    flat = a.astype(np.int64, copy=False).reshape(-1)
    if flat.size == 0:
        return 0
    return _to_i32(int((flat * _weights64(flat.size)).sum()))


@functools.cache
def _digest_dev_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def digest(x):
        if x.dtype in (jnp.float32, jnp.uint32):
            x = jax.lax.bitcast_convert_type(x, jnp.int32)
        flat = x.astype(jnp.int32).reshape(-1)
        w = (jnp.arange(flat.shape[0], dtype=jnp.int32) % _W_PERIOD) + 1
        return jnp.sum(flat * w, dtype=jnp.int32)

    return digest


def digest_device(x):
    """Async device-side twin of digest_host over an already-staged
    device array. Returns an UNFETCHED scalar so callers can batch the
    sync with the fetch they were already doing."""
    return _digest_dev_fn()(x)


def note_failure(kind: str) -> None:
    """Count an attestation failure detected outside verify_* — the
    kernels' in-carry ``att`` accumulator read at summarize
    (wgl._check_att), which never fetches a whole carry."""
    _M_FAIL.labels(kind=kind).inc()


def verify_steps(site: str, fetched_digest, expected: int) -> None:
    """Compare a fetched device digest with the host's canonical one;
    raise CorruptDeviceResult on disagreement."""
    got = int(fetched_digest)
    _M_VERIFY.labels(kind="steps").inc()
    if got != expected:
        _M_FAIL.labels(kind="steps").inc()
        raise CorruptDeviceResult(
            site, f"staged-buffer digest {got} != host {expected} — "
                  f"the shipped buffer was corrupted in transit")


# ---------------------------------------------------------------------------
# Carry digests (host mirrors of Kernel.digest — see wgl._kernel*)
# ---------------------------------------------------------------------------

# per-component mixing primes, shared by the device digest builders in
# wgl.py and the host mirrors below: position i's component multiplies
# _PRIMES[i % len] before xor-folding
_PRIMES = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
           0x165667B1, 0x68B5A6D9, 0x7FEB352D, 0x846CA68B)


def prime_i32(i: int) -> int:
    return _to_i32(_PRIMES[i % len(_PRIMES)])


def _sum_i32(arr: np.ndarray) -> int:
    a = np.asarray(arr)
    if a.dtype == np.bool_:
        a = a.astype(np.int64)
    elif a.dtype.kind in "fu":
        a = a.view(np.int32) if a.dtype.itemsize == 4 \
            else a.astype(np.int64)
    return int(a.astype(np.int64, copy=False).sum())


def carry_digest_host(carry) -> int:
    """Recompute Kernel.digest's mix from a FETCHED host carry — the
    formula is xor-fold of (component wrap-sum * prime_i) over every
    carry element, scalars included, in carry order. Must stay in
    lockstep with the device builders in wgl.py."""
    h = 0
    for i, c in enumerate(carry):
        s = _sum_i32(c) * _PRIMES[i % len(_PRIMES)]
        h ^= s & _MASK
    return _to_i32(h)


# (e, table, slot_f, slot_a, slot_b, slot_occ, att, count, max_count):
# the dense kernels' carry, whose table is bool or uint32 words
DENSE_CARRY_LEN = 9


def verify_carry(site: str, fetched_digest, carry_host,
                 att_index: int = -3) -> None:
    """Check a fetched carry against its device-computed digest plus
    the structural invariants the host can see:

      * digest parity — the carry arrays the device mixed are the
        arrays the host received (transfer/fetch integrity);
      * att == 0 — the kernel's in-loop invariant accumulator (dedup
        digest mismatches, frontier/table occupancy violations) never
        fired;
      * count == live population — carry[-2] must equal the popcount
        of the liveness structure the digest already covers (a flip
        in either is caught even when the digest round-trips clean,
        because count is re-derived, not copied).
    """
    got = int(fetched_digest)
    want = carry_digest_host(carry_host)
    _M_VERIFY.labels(kind="carry").inc()
    if got != want:
        _M_FAIL.labels(kind="carry").inc()
        raise CorruptDeviceResult(
            site, f"carry digest {got} != host recompute {want} — the "
                  f"fetched carry differs from the device's")
    att = int(np.asarray(carry_host[att_index]))
    if att != 0:
        _M_FAIL.labels(kind="carry").inc()
        raise CorruptDeviceResult(
            site, f"in-kernel attestation accumulator = {att} — a "
                  f"frontier/table invariant or dedup digest failed "
                  f"on device")
    count = int(np.asarray(carry_host[-2]))
    # masks (sort) / table (dense), as fetched: maybe strided
    live = np.ascontiguousarray(carry_host[1])
    if live.dtype == np.bool_:      # dense table: count == popcount
        pop = int(live.sum())
    elif len(carry_host) == DENSE_CARRY_LEN:   # dense table as words
        pop = int(np.unpackbits(live.view(np.uint8)).sum())
    else:                           # sort frontier: count == sum(valid)
        pop = int(np.asarray(carry_host[3]).sum())
    if count != pop:
        _M_FAIL.labels(kind="carry").inc()
        raise CorruptDeviceResult(
            site, f"carry count {count} != live population {pop}")
