"""Pallas TPU kernel: pairwise Euclidean distance matrix.

The FedCore hot-spot (§4.2/§4.3): building the (m, m) gradient-distance
matrix that the k-medoids clustering consumes.  The paper computes this as
a per-pair loop on GPU/CPU; the TPU-native formulation is a tiled matmul —
``‖a − b‖² = ‖a‖² + ‖b‖² − 2 a·b`` — so the cross term runs on the MXU:

  grid = (m/bm, n/bn, d/bk); each (i, j) tile accumulates the −2·X Yᵀ
  cross-term over k-steps in an fp32 VMEM scratch, and on the last k-step
  fuses the ‖·‖² rank-1 epilogue, the clamp, and the sqrt.

Block sizes default to MXU-aligned 128/256/512 and are clipped to the
(padded) problem shape.  The wrapper in ``ops.py`` pads inputs to block
multiples with zero rows (distance contributions of zero-padding cancel in
the cross-term; padded rows are sliced off on return).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import F32   # Mosaic's fp32 contract precision


def _pairwise_kernel(x_ref, y_ref, xsq_ref, ysq_ref, out_ref, acc_ref, *,
                     squared: bool, n_k: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)           # (bm, bk)
    y = y_ref[...].astype(jnp.float32)           # (bn, bk)
    acc_ref[...] += jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)      # x @ y.T

    @pl.when(k_step == n_k - 1)
    def _epilogue():
        xsq = xsq_ref[...].astype(jnp.float32)   # (bm, 1)
        ysq = ysq_ref[...].astype(jnp.float32)   # (1, bn)
        d = xsq + ysq - 2.0 * acc_ref[...]
        d = jnp.maximum(d, 0.0)
        if not squared:
            d = jnp.sqrt(d)
        out_ref[...] = d.astype(out_ref.dtype)


def pairwise_l2_pallas(x: jnp.ndarray, y: Optional[jnp.ndarray] = None, *,
                       squared: bool = False, block_m: int = 128,
                       block_n: int = 128, block_k: int = 512,
                       interpret: bool = False) -> jnp.ndarray:
    """x: (m, d); y: (n, d) or None (=x).  Returns (m, n) fp32 distances.

    Shapes must already be padded to block multiples (ops.py handles this).
    """
    y = x if y is None else y
    m, d = x.shape
    n = y.shape[0]
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, d)
    assert m % block_m == 0 and n % block_n == 0 and d % block_k == 0
    n_k = d // block_k

    # 2-D norm operands: (bm, 1) column / (1, bn) row blocks match
    # Mosaic's tiled layout, which 1-D (bm,) blocks do not.
    xsq = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)[:, None]
    ysq = jnp.sum(y.astype(jnp.float32) ** 2, axis=-1)[None, :]

    grid = (m // block_m, n // block_n, n_k)
    kernel = functools.partial(_pairwise_kernel, squared=squared, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, y, xsq, ysq)


def _pairwise_batched_kernel(xi_ref, xj_ref, sqi_ref, sqj_ref, out_ref,
                             acc_ref, *, squared: bool, n_k: int):
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xi = xi_ref[0].astype(jnp.float32)           # (bm, bk) rows
    xj = xj_ref[0].astype(jnp.float32)           # (bn, bk) cols
    acc_ref[...] += jax.lax.dot_general(
        xi, xj, (((1,), (1,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)      # xi @ xj.T for this client

    @pl.when(k_step == n_k - 1)
    def _epilogue():
        sqi = sqi_ref[0].astype(jnp.float32)     # (bm, 1)
        sqj = sqj_ref[0].astype(jnp.float32)     # (1, bn)
        d = sqi + sqj - 2.0 * acc_ref[...]
        d = jnp.maximum(d, 0.0)
        if not squared:
            d = jnp.sqrt(d)
        out_ref[0] = d.astype(out_ref.dtype)


def pairwise_l2_batched_pallas(x: jnp.ndarray, *, squared: bool = False,
                               block_m: int = 128, block_k: int = 512,
                               interpret: bool = False) -> jnp.ndarray:
    """Self-distance stacks for a client cohort: x (C, M, D) -> (C, M, M).

    The fleet engine's hot path (one distance matrix per client per round).
    Identical tiling to ``pairwise_l2_pallas`` with a leading client grid
    dimension — one (c, i, j) tile accumulates its −2·XXᵀ cross term over
    k-steps in VMEM and fuses the ‖·‖² epilogue on the last step.  Shapes
    must already be padded to block multiples (ops.py handles this).
    """
    c, m, d = x.shape
    block_m = min(block_m, m)
    block_k = min(block_k, d)
    assert m % block_m == 0 and d % block_k == 0
    n_k = d // block_k

    xsq = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)     # (C, M)
    # (C, M, 1) column / (C, 1, M) row norm operands keep each block's
    # last two dims Mosaic-tileable (a (1, bm) block of (C, M) is not).

    grid = (c, m // block_m, m // block_m, n_k)
    kernel = functools.partial(_pairwise_batched_kernel, squared=squared,
                               n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), lambda b, i, j, k: (b, i, k)),
            pl.BlockSpec((1, block_m, block_k), lambda b, i, j, k: (b, j, k)),
            pl.BlockSpec((1, block_m, 1), lambda b, i, j, k: (b, i, 0)),
            pl.BlockSpec((1, 1, block_m), lambda b, i, j, k: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_m),
                               lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((c, m, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_m), jnp.float32)],
        interpret=interpret,
    )(x, x, xsq[:, :, None], xsq[:, None, :])
