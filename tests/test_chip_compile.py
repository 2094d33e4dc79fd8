"""Compile the checker's device kernels for a TPU v5e that is described,
not attached (on-chip-measurement guide, section 2): what Mosaic or XLA
would refuse on the chip fails here, at no chip time.

The topology is described inside a module fixture and nowhere else —
never at import, in a skipif or in a parametrize argument — so every
xdist worker collects the same tests and only the worker given this file
loads the TPU compiler. Keep these tests in this one file. The
persistent compilation cache is off for the module: a compile for a
described chip is written to it but cannot be read back without one.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from jepsen_tpu.checker import wgl, wgl_dedup, wgl_pallas
from jepsen_tpu.checker.elle import kernels as elle_kernels


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        compilation_cache.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    """ShapeDtypeStructs of an eval_shape tree, placed on the chip."""
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), tree)


@pytest.mark.parametrize("S,P", [(8, 7), (16, 12), (8, 14)])
def test_pallas_closure_round_compiles(one_chip, S, P):
    fn = wgl_pallas.closure_round_fn(S, P, interpret=False)
    compiled = jax.jit(fn).lower(
        _spec((S, 1 << P), jnp.float32, one_chip),
        _spec((P, S, S), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sort_kernel_compiles_at_headline_shape(one_chip):
    # the headline register's sort family: frontier 256, 8 slots, the
    # 10k-op history's 32768-entry capacity walked in 4096-entry chunks
    F, P, E = 256, 8, 32768
    k = wgl._kernel_cached("cas-register", F, P, E,
                           wgl._pack_params((-1, 4), P),
                           False, True, True)
    carry = _on(jax.eval_shape(k.init_carry, jnp.int32(-1)), one_chip)
    compiled = k.check_chunk.lower(
        _spec((E, 5), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip), carry).compile()
    assert compiled.as_text()


def test_dense_kernel_with_pallas_closure_compiles_at_p14(one_chip):
    # the adversarial register's shape: 8 register states x 2^14 masks
    E = 32768
    k = wgl._dense_kernel_cached("cas-register", -1, 8, 14, E,
                                 True, True, True)
    compiled = k.check.lower(
        _spec((E, 5), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("e,batch", [(8, 64), (64, 16)])
def test_elle_flags_kernel_compiles(one_chip, e, batch):
    steps = max(1, int(np.ceil(np.log2(e))))
    fn = elle_kernels._flags_batch_fn(e, steps)
    blocks = [_spec((batch, e, e), jnp.float32, one_chip)] * 3
    assert fn.lower(*blocks).compile().as_text()


def test_hash_dedup_is_refused_by_mosaic(one_chip):
    # the kernel ROADMAP's Speed queue has to repair: Mosaic refuses
    # its scalar stores to VMEM, which is why the sort dedup is the
    # TPU default. The PR that repairs the kernel flips this test.
    fn = wgl_dedup.dedup_fn(8, 4, interpret=False)
    with pytest.raises(Exception, match="Cannot store scalars to VMEM"):
        fn.lower(_spec((8,), jnp.int32, one_chip)).compile()
