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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fed.fleet.batched import FleetConfig, FleetEngine
from repro.kernels import ops
from repro.models.small import CharLSTM


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


def _hlo_computations(text):
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            name, comps[head.group(1)] = head.group(1), []
        elif line.startswith("}"):
            name = None
        elif name is not None and " = " in line:
            comps[name].append(line.strip())
    return comps


def _while_bodies(lines):
    return [re.search(r"body=%?([\w.\-]+)", l).group(1) for l in lines
            if re.search(r" while\(", l)]


def test_lstm_weight_gradients_leave_the_time_loop(one_chip):
    """A 2-lane SGD scan of the Shakespeare LSTM (H 256, S 80, B 32):
    no time loop nested in the SGD loop computes an f32[C, H, 4H] value.
    Autodiff of a scan that closes over its weights accumulates dWh (and
    dWx) there at every step; the recurrence's own VJP forms them once,
    after the loop."""
    c, m, s, b, t = 2, 64, 80, 32, 4
    model = CharLSTM(vocab=80, d_embed=8, d_hidden=256, n_layers=2)
    engine = FleetEngine(model, FleetConfig(batch_size=b))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p0 = jax.tree.map(lambda a: arg((c,) + a.shape, a.dtype), params)
    data = {"x": arg((c, m, s), jnp.int32), "y": arg((c, m, s), jnp.int32)}
    text = jax.jit(jax.vmap(engine._sgd_scan)).lower(
        p0, data, arg((c, m), F32), arg((c, t, b), jnp.int32)
    ).compile().as_text()
    comps = _hlo_computations(text)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)
    sgd_loops = [w for w in _while_bodies(comps[entry])
                 if _while_bodies(comps[w])]
    assert len(sgd_loops) == 1
    # forward and backward time loop of each of the two layers
    time_loops = _while_bodies(comps[sgd_loops[0]])
    assert len(time_loops) == 4
    weight_grad = f"f32[{c},256,1024]"
    computed = []
    for body in time_loops:
        for line in comps[body]:
            name, rhs = line.split(" = ", 1)
            op = re.search(r" ([a-z][\w\-]*)\(", rhs)
            if (weight_grad in rhs[:op.start()] and op.group(1) not in
                    ("parameter", "get-tuple-element", "tuple")):
                computed.append(name)
    assert computed == []
