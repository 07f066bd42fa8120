"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler that ships with jax compiles for a
``v5e:2x2`` topology described from its name, which enforces what
interpret mode does not — Mosaic's (8, 128) block tiling, its operand
layouts, and the scoped VMEM budget.  Each case compiles one kernel
wrapper at a real width with ``interpret=False`` and checks that the
kernel is in the program as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture (only the
worker that runs this file loads the TPU library), and the persistent
compilation cache is off around the compiles: a cache entry compiled for
a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


F32 = jnp.float32


# distance-free pair at the cnn workload's feature width (F = 144, padded
# to 256 lanes); k = 1024 is the fleet's largest budget bucket and sets
# the largest (block_m, K) tiles of the Δ-sweep
@pytest.mark.parametrize("m,k", [(512, 32), (2048, 1024)])
def test_distance_free_pair_compiles(one_chip, m, k):
    c, f = 4, 144
    _compile_text(
        lambda x, dn, vf: ops.kmedoids_build_cost_from_feats(
            x, dn, vf, use_kernel=True, interpret=False),
        one_chip, ((c, m, f), F32), ((c, m), F32), ((c, m), F32))
    compiled = _compile_text(
        lambda x, d1, d2, vf, oh: ops.kmedoids_delta_sweep_from_feats(
            x, d1, d2, vf, oh, use_kernel=True, interpret=False),
        one_chip, ((c, m, f), F32), ((c, m), F32), ((c, m), F32),
        ((c, m), F32), ((c, m, k), F32))
    # Mosaic refuses tiles beyond the scoped VMEM budget, so the compile
    # is the VMEM check; the HBM footprint is the (C, M, K) B table in
    # and out plus padding, 73 MB here of the chip's 16 GB
    mem = compiled.memory_analysis()
    hbm = (mem.argument_size_in_bytes + mem.output_size_in_bytes
           + mem.temp_size_in_bytes)
    assert hbm < 2 * (c * m * k + c * m * 256) * 4 + 2 ** 20


@pytest.mark.parametrize("m", [128, 256])
def test_d_input_pair_and_batched_pairwise_compile(one_chip, m):
    c, f, k = 16, 144, 64
    _compile_text(
        lambda D, dn, vf: ops.kmedoids_build_cost(
            D, dn, vf, use_kernel=True, interpret=False),
        one_chip, ((c, m, m), F32), ((c, m), F32), ((c, m), F32))
    _compile_text(
        lambda D, d1, d2, vf, oh: ops.kmedoids_delta_sweep(
            D, d1, d2, vf, oh, use_kernel=True, interpret=False),
        one_chip, ((c, m, m), F32), ((c, m), F32), ((c, m), F32),
        ((c, m), F32), ((c, m, k), F32))
    _compile_text(
        lambda x: ops.pairwise_l2_batched(x, use_kernel=True,
                                          zero_diag=True, interpret=False),
        one_chip, ((c, m, f), F32))


def test_pairwise_l2_compiles(one_chip):
    # FedCore-LM selection: per-sequence features at the 100m preset's
    # d_model = 768
    _compile_text(
        lambda x: ops.pairwise_l2(x, squared=True, zero_diag=True,
                                  interpret=False),
        one_chip, ((512, 768), F32))


def test_flash_attention_compiles(one_chip):
    # translm: batch 32, 2 heads of 16, sequence 16
    shape = ((32, 2, 16, 16), F32)
    _compile_text(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            interpret=False),
        one_chip, shape, shape, shape)
