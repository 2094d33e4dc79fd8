"""Pallas TPU kernel: sort-free frontier dedup + compaction.

The sort-family WGL kernel's hot op is the frontier dedup: every
expand round lexicographically `lax.sort`s N candidate configurations
(N = F·(1+P) in stage B, 2·F at each invoke) just to drop duplicates
and compact the survivors to the front — O(N log N) work per event on
(W+2) sort lanes, the dominant cost named by `doc/plan.md`.  Dedup is
a *set* operation, not an order operation: this kernel replaces the
sort with a VMEM-resident open-addressing hash table and does dedup +
compaction in one pass, O(N) expected (cf. P-compositionality — the
win compounds exactly when per-key sub-histories keep N small — and
TrieJax's hash/trie set ops beating sort formulations on-matrix-unit).

Contract (pinned by tests/test_wgl_dedup.py against the sort path):

  * input: N packed config keys, **old frontier first** (both wgl.py
    call sites concatenate `[old configs, candidates]`), invalid
    entries = EMPTY (-1).  A key packs `(state - s_lo) << P | mask`
    into 31 bits (the sort path's `dedup_packed` single-lane key minus
    the invalid bit), so eligibility requires the packed
    representation: `_pack_params(...) is not None and W == 1`.
  * output: the distinct valid keys in **first-seen order**, compacted
    to the front of an F-slot frontier; a per-slot `new` flag (the
    key's first occurrence had input index >= F, i.e. it was a
    candidate, not an old config — the same "stable sort,
    old-configs-first wins" rule the sort path uses); and the total
    distinct count (count > F == the sort path's overflow flag).
  * the emitted frontier is **set-equal** to the sort path's (the sort
    path emits key order, this kernel first-seen order) whenever the
    sort path does not overflow.  Every downstream consumer is
    order-invariant — the completion phase is elementwise, `summarize`
    reads only the count, and blame re-runs the unmerged stream — so
    summaries, verdicts, and blame certificates are identical.
  * under frontier pressure the hash table is strictly *tighter* than
    the sort: sorted duplicate runs can push a key's first occurrence
    past row F, so the sort path drops configs and flags overflow even
    when the distinct count fits the frontier, while the hash path
    drops nothing and flags overflow exactly when distinct > F.  Same
    soundness argument either way (dropping only loses candidate
    linearizations, so 'valid' stays sound and invalid-under-overflow
    escalates) — the hash path just escalates less often.

Kernel layout: one grid step; three VMEM buffers — the key vector
(N, 1), the hash table (H, 1) with H = 2·next_pow2(N) (load factor
<= 1/2, so linear probing terminates fast), and the compacted output
(F, 1) — all int32 (keys are 31-bit, so EMPTY = -1 is unambiguous).
A `fori_loop` walks the keys in order; each key multiplicative-hashes
(murmur3 finalizer) to a bucket and linear-probes: EMPTY -> claim the
bucket, append to the output cursor; equal key -> duplicate, skip.
The scalar probe loop is the price of exactness — but it runs against
VMEM with zero HBM traffic, does one u32 compare per probe instead of
a (W+2)-lane sort network stage, and skips dead candidates (stage B's
legality mask is usually almost empty) in one compare each.

Status: opt-in everywhere via JEPSEN_TPU_PALLAS_DEDUP=1 (interpret
mode off-TPU), OFF by default on TPU too: Mosaic refuses the kernel
for v5e ("Cannot store scalars to VMEM" — the scalar table and output
stores), pinned by tests/test_chip_compile.py.  On a TPU the opt-in
raises that compiler error; it never switches paths quietly.  The
repair is a later perf PR with chip numbers (ROADMAP.md, Speed).
Correctness is pinned in interpret mode by tests/test_wgl_dedup.py.
"""

from __future__ import annotations

import functools

EMPTY = -1                # table/key sentinel; valid keys are 31-bit
# the key vector, the hash table (2x the padded key count), and the
# output frontier must all sit in VMEM together, with headroom for
# Mosaic temporaries (same budget discipline as wgl_pallas).
MAX_VMEM_BYTES = 12 << 20


def table_size(n: int) -> int:
    """Hash slots for n keys: next power of two at load factor 1/2."""
    from .wgl import _bucket

    return 2 * _bucket(n)


def eligible(F: int, P: int) -> bool:
    """Can the sort family's dedup run through the hash kernel at
    frontier F with P slots?  Sized for the LARGER call site (stage
    B's F·(1+P) candidates); the invoke-stage 2·F call then fits a
    fortiori.  The packed-key requirement (W == 1 and
    `_pack_params(...) is not None`) is checked by the caller — this
    gate is pure capacity."""
    n = F * (1 + P)
    vmem = (n + table_size(n) + 2 * F) * 4
    return vmem <= MAX_VMEM_BYTES


# digest mixing constant (golden-ratio prime): the occupancy count is
# folded into the XOR digest so a dropped-and-double-counted key pair
# (XOR-cancelling) still perturbs the digest
DIGEST_COUNT_MIX = -1640531527   # 0x9E3779B9 as int32


@functools.lru_cache(maxsize=32)
def dedup_fn(N: int, F: int, interpret: bool = False):
    """Build `dedup(keys (N,) int32) -> (out_keys (F,), new (F,),
    count (), digest ())` — distinct valid keys in first-seen order,
    compacted; `new[i]` set when out_keys[i] was first seen at input
    index >= F; `count` is the TOTAL distinct-valid count (count > F
    <=> the sort path's overflow).  Slots past min(count, F) hold
    EMPTY.

    `digest` is the kernel's ABFT self-attestation: the XOR of every
    key CLAIMED IN THE HASH TABLE, mixed with the occupancy count
    (digest = xor(inserted keys) ^ (count * DIGEST_COUNT_MIX)).  When
    the frontier did not overflow (count <= F) the caller can
    recompute the same value from the compacted output alone
    (wgl.dedup_hash does, folding any mismatch into the carry's att
    accumulator): table and output are written by different store
    paths, so a silent flip in either VMEM buffer — or a probe loop
    miscompare that drops/double-claims a key — makes the two digests
    disagree."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H = table_size(N)
    i32 = jnp.int32

    def _hash(k):
        # murmur3 finalizer over the 31-bit key; logical shifts keep
        # the mixing well-defined after the wrapping multiplies
        h = k ^ lax.shift_right_logical(k, i32(16))
        h = h * i32(-2048144789)          # 0x85ebca6b
        h = h ^ lax.shift_right_logical(h, i32(13))
        h = h * i32(-1028477387)          # 0xc2b2ae35
        h = h ^ lax.shift_right_logical(h, i32(16))
        return h & i32(H - 1)

    def kernel(keys_ref, out_keys_ref, out_new_ref, count_ref,
               digest_ref, table_ref):
        table_ref[:] = jnp.full((H, 1), EMPTY, i32)
        out_keys_ref[:] = jnp.full((F, 1), EMPTY, i32)
        out_new_ref[:] = jnp.zeros((F, 1), i32)

        def insert(i, carry):
            count, dig = carry
            k = keys_ref[i, 0]

            def probe(state):
                pos, _res = state
                t = table_ref[pos, 0]
                hit_empty = t == EMPTY

                @pl.when(hit_empty)
                def _():
                    table_ref[pos, 0] = k

                # 0 = keep probing, 1 = inserted (new distinct key),
                # 2 = duplicate of a table entry
                res = jnp.where(hit_empty, i32(1),
                                jnp.where(t == k, i32(2), i32(0)))
                return jnp.where(res == 0, (pos + 1) & (H - 1),
                                 pos), res

            # an EMPTY input slot starts resolved (res=2): dead
            # candidates cost one compare, no probes
            _pos, res = lax.while_loop(
                lambda s: s[1] == 0, probe,
                (_hash(k), jnp.where(k == EMPTY, i32(2), i32(0))))
            fresh = res == 1

            @pl.when(fresh & (count < F))
            def _():
                out_keys_ref[count, 0] = k
                out_new_ref[count, 0] = jnp.where(i >= F, i32(1),
                                                  i32(0))

            return (count + fresh.astype(i32),
                    jnp.where(fresh, dig ^ k, dig))

        count, dig = lax.fori_loop(0, N, insert, (i32(0), i32(0)))
        count_ref[0, 0] = count
        digest_ref[0, 0] = dig ^ (count * i32(DIGEST_COUNT_MIX))

    @jax.jit
    def dedup(keys):
        out_keys, out_new, count, digest = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((F, 1), jnp.int32),
                       jax.ShapeDtypeStruct((F, 1), jnp.int32),
                       jax.ShapeDtypeStruct((1, 1), jnp.int32),
                       jax.ShapeDtypeStruct((1, 1), jnp.int32)),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                       pl.BlockSpec(memory_space=pltpu.VMEM),
                       pl.BlockSpec(memory_space=pltpu.VMEM),
                       pl.BlockSpec(memory_space=pltpu.VMEM)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.int32)],
            interpret=interpret,
        )(keys.reshape(N, 1).astype(jnp.int32))
        return (out_keys[:, 0], out_new[:, 0] != 0, count[0, 0],
                digest[0, 0])

    return dedup
