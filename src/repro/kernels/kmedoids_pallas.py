"""Pallas TPU kernels for the fused k-medoids selection fast path.

The FedCore selection phase (Eq. 5) spends its time in two dense
reductions over the per-client distance stack D (C, M, M):

* **BUILD** — each greedy add evaluates every candidate j's add-cost
      cost[c, j] = Σ_i min(d_near[c, i], D[c, i, j]) · vf[c, i]
  The jnp formulation materializes the (C, M, M) ``minimum`` tensor per
  step; ``build_cost_pallas`` streams D tile-by-tile and keeps only a
  (1, bm) accumulator in VMEM.

* **Δ-sweep** — one FasterPAM swap sweep needs (Schubert & Rousseeuw
  2021, see ``repro.core.kmedoids``):
      A[c, j]    = Σ_i (min(D_ij, d1_i) − d1_i) · vf_i
      B[c, j, l] = Σ_{i: n(i)=l} (clip(D_ij, d1_i, d2_i) − d1_i) · vf_i
  The jnp chain makes 3+ full O(M²) HBM passes per sweep (shift tensor,
  contrib tensor, one-hot einsum).  ``delta_sweep_pallas`` computes both
  reductions in a **single tiled pass** over D: each (c, j, i) tile
  builds shift/contrib in registers, folds shift into a row-sum
  accumulator and contrib into a (bm, K) MXU matmul against the
  nearest-medoid one-hot — the memory traffic finally matches the math.

* **Distance-free variants** — ``delta_sweep_from_feats_pallas`` and
  ``build_cost_from_feats_pallas`` compute the SAME reductions **without
  D ever existing**: distances are rebuilt on the fly from the (C, M, F)
  gradient-feature stack inside each (i, j) tile, flash-attention-style
  (``kernels/flash_attention.py`` is the tiling template).  The grid
  gains a minor-most F-step axis; each (c, j, i) cell accumulates the
  −2·XᵢXⱼᵀ cross term over F-tiles in an f32 VMEM scratch, and on the
  last F-step fuses the ‖·‖² epilogue, clamp, sqrt, exact self-distance
  zeroing (global row == global col), and the A/B (or add-cost) folds.
  Memory traffic drops from O(C·M²) to O(C·M·F) — per-client M in the
  thousands instead of hundreds.  Padded candidate columns (vf = 0) are
  masked to +BIG *in-kernel*: zero-padded feature rows are at distance 0
  from each other, so without the mask a padded lane could tie-win a
  medoid election over real rows.

Every kernel carries a leading client-batch grid dimension (one cohort
group = one launch), accepts masked lanes via ``vf`` (invalid rows
contribute exactly 0), and runs under ``interpret=True`` on CPU so the
whole fast path is exercised in CI.  Shapes must already be padded to
block multiples — ``repro.kernels.ops`` owns the padding and the jnp
fallback dispatch; ``repro.kernels.ref`` holds the mathematical oracles
the kernels are tested against (the from-feats refs DO materialize D —
that is exactly what makes them the parity gate).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import F32   # Mosaic's fp32 contract precision


# Per-point (C, M) vectors enter the kernels 3-D so that every block's
# last two dims satisfy Mosaic's (8, 128) tiling rule: a (1, block_m)
# block of a (C, M) array does not.  Vectors indexed by the tile's rows
# (i) ride as (C, M, 1) and read as (bi, 1) columns; vectors indexed by
# its columns (j), and the per-candidate outputs, ride as (C, 1, M) and
# read as (1, bj) rows.

def _col(v: jnp.ndarray) -> jnp.ndarray:
    return v[:, :, None]


def _row(v: jnp.ndarray) -> jnp.ndarray:
    return v[:, None, :]


def _build_cost_kernel(d_ref, dn_ref, vf_ref, out_ref, acc_ref, *, n_i: int):
    i_step = pl.program_id(2)

    @pl.when(i_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = d_ref[0].astype(jnp.float32)             # (bi, bj) distance tile
    dn = dn_ref[0].astype(jnp.float32)           # (bi, 1) current d_near
    vf = vf_ref[0].astype(jnp.float32)           # (bi, 1) valid mask
    add = jnp.minimum(dn, d) * vf
    acc_ref[...] += jnp.sum(add, axis=0, keepdims=True)   # (1, bj)

    @pl.when(i_step == n_i - 1)
    def _epilogue():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def build_cost_pallas(D: jnp.ndarray, d_near: jnp.ndarray, vf: jnp.ndarray,
                      *, block_m: int = 128,
                      interpret: bool = False) -> jnp.ndarray:
    """Fused BUILD add-cost: D (C, M, M), d_near/vf (C, M) -> (C, M).

    cost[c, j] = Σ_i min(d_near[c, i], D[c, i, j]) · vf[c, i], computed
    tile-by-tile without materializing the (C, M, M) minimum tensor.  M
    must be a multiple of ``block_m`` (ops.py pads; padded rows must
    carry vf = 0, padded cost columns are sliced off by the wrapper).
    """
    c, m, _ = D.shape
    block_m = min(block_m, m)
    assert m % block_m == 0
    n_i = m // block_m

    grid = (c, n_i, n_i)                          # (client, j-tile, i-step)
    kernel = functools.partial(_build_cost_kernel, n_i=n_i)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_m), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_m), lambda b, j, i: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((c, 1, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_m), jnp.float32)],
        interpret=interpret,
    )(D, _col(d_near), _col(vf))[:, 0]


def _delta_sweep_kernel(d_ref, d1_ref, d2_ref, vf_ref, oh_ref, a_ref, b_ref,
                        acc_a_ref, acc_b_ref, *, n_i: int):
    i_step = pl.program_id(2)

    @pl.when(i_step == 0)
    def _init():
        acc_a_ref[...] = jnp.zeros_like(acc_a_ref)
        acc_b_ref[...] = jnp.zeros_like(acc_b_ref)

    d = d_ref[0].astype(jnp.float32)             # (bi, bj)
    d1 = d1_ref[0].astype(jnp.float32)           # (bi, 1) nearest-medoid dist
    d2 = d2_ref[0].astype(jnp.float32)           # (bi, 1) second-nearest
    vf = vf_ref[0].astype(jnp.float32)           # (bi, 1) valid mask
    oh = oh_ref[0].astype(jnp.float32)           # (bi, K) one_hot(n_idx)

    # one read of the tile feeds both reductions
    shift = (jnp.minimum(d, d1) - d1) * vf                 # ≤ 0 removal gain
    contrib = (jnp.clip(d, d1, d2) - d1) * vf              # per-cluster term
    acc_a_ref[...] += jnp.sum(shift, axis=0, keepdims=True)        # (1, bj)
    acc_b_ref[...] += jax.lax.dot_general(                 # contribᵀ @ onehot
        contrib, oh, (((0,), (0,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)                # (bj, K)

    @pl.when(i_step == n_i - 1)
    def _epilogue():
        a_ref[0] = acc_a_ref[...].astype(a_ref.dtype)
        b_ref[0] = acc_b_ref[...].astype(b_ref.dtype)


def delta_sweep_pallas(D: jnp.ndarray, d1: jnp.ndarray, d2: jnp.ndarray,
                       vf: jnp.ndarray, n_onehot: jnp.ndarray, *,
                       block_m: int = 128, interpret: bool = False):
    """Fused FasterPAM Δ-sweep reductions in one pass over D.

    D (C, M, M); d1/d2/vf (C, M); n_onehot (C, M, K) = one_hot of each
    point's nearest-medoid slot.  Returns (A (C, M), B (C, M, K)) such
    that Δ(j, l) = A[:, j] + B[:, j, l].  M must be a multiple of
    ``block_m`` and K a lane-aligned pad of the true k (ops.py owns the
    padding; padded rows carry vf = 0, padded K columns have zero
    one-hot mass so the extra B columns are exactly 0).
    """
    c, m, _ = D.shape
    kp = n_onehot.shape[-1]
    block_m = min(block_m, m)
    assert m % block_m == 0
    n_i = m // block_m

    grid = (c, n_i, n_i)                          # (client, j-tile, i-step)
    kernel = functools.partial(_delta_sweep_kernel, n_i=n_i)
    A, B = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_m), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_m, kp), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_m), lambda b, j, i: (b, 0, j)),
            pl.BlockSpec((1, block_m, kp), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, 1, m), jnp.float32),
            jax.ShapeDtypeStruct((c, m, kp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_m), jnp.float32),
                        pltpu.VMEM((block_m, kp), jnp.float32)],
        interpret=interpret,
    )(D, _col(d1), _col(d2), _col(vf), n_onehot)
    return A[:, 0], B


# ---------------------------------------------------------------------------
# distance-free variants: D rebuilt per tile from the (C, M, F) features
# ---------------------------------------------------------------------------

BIG = 1e30      # matches repro.core.kmedoids.BIG (the +inf candidate mask)


def _dist_tile(dot, sqi, sqj, i_step, j_step, block_m):
    """One (bi, bj) L2-distance tile from its accumulated cross term.

    ``‖a − b‖ = sqrt(max(‖a‖² + ‖b‖² − 2ab, 0))`` with the self-distance
    diagonal (global row index == global col index) pinned to exact 0 —
    the float32 cancellation fix-up ``ops.zero_self_diag`` applies to
    materialized stacks, fused into the tile here."""
    d = jnp.sqrt(jnp.maximum(sqi + sqj - 2.0 * dot, 0.0))
    rows = i_step * block_m + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, block_m), 0)
    cols = j_step * block_m + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, block_m), 1)
    return jnp.where(rows == cols, 0.0, d)


def _build_cost_feats_kernel(xi_ref, xj_ref, sqi_ref, sqj_ref, dn_ref,
                             vfi_ref, vfj_ref, out_ref, dot_ref, acc_ref, *,
                             n_i: int, n_k: int, block_m: int):
    j_step = pl.program_id(1)
    i_step = pl.program_id(2)
    k_step = pl.program_id(3)

    @pl.when((i_step == 0) & (k_step == 0))
    def _init_cost():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k_step == 0)
    def _init_dot():
        dot_ref[...] = jnp.zeros_like(dot_ref)

    xi = xi_ref[0].astype(jnp.float32)           # (bi, bk) feature rows
    xj = xj_ref[0].astype(jnp.float32)           # (bj, bk) candidate rows
    dot_ref[...] += jax.lax.dot_general(
        xi, xj, (((1,), (1,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)      # xi @ xj.T

    @pl.when(k_step == n_k - 1)
    def _fold():
        d = _dist_tile(dot_ref[...], sqi_ref[0].astype(jnp.float32),
                       sqj_ref[0].astype(jnp.float32), i_step, j_step,
                       block_m)
        dn = dn_ref[0].astype(jnp.float32)       # (bi, 1) current d_near
        vf = vfi_ref[0].astype(jnp.float32)      # (bi, 1) valid rows
        add = jnp.minimum(dn, d) * vf
        acc_ref[...] += jnp.sum(add, axis=0, keepdims=True)   # (1, bj)

    @pl.when((i_step == n_i - 1) & (k_step == n_k - 1))
    def _epilogue():
        vfj = vfj_ref[0].astype(jnp.float32)     # (1, bj) valid candidates
        cost = jnp.where(vfj > 0.0, acc_ref[...], BIG)
        out_ref[0] = cost.astype(out_ref.dtype)


def build_cost_from_feats_pallas(x: jnp.ndarray, d_near: jnp.ndarray,
                                 vf: jnp.ndarray, *, block_m: int = 128,
                                 block_k: int = 128,
                                 interpret: bool = False) -> jnp.ndarray:
    """Distance-free BUILD add-cost: x (C, M, F), d_near/vf (C, M) -> (C, M).

    cost[c, j] = Σ_i min(d_near[c, i], ‖x_i − x_j‖)·vf[c, i] for valid
    candidates j, +BIG for padded ones (vf[c, j] = 0) — the (C, M, M)
    distance stack is never materialized; each tile's distances are
    rebuilt from an F-tiled cross-term accumulation.  M must be a
    multiple of ``block_m`` and F of ``block_k`` (ops.py pads; zero
    feature rows/cols are exact for the cross term)."""
    c, m, f = x.shape
    block_m = min(block_m, m)
    block_k = min(block_k, f)
    assert m % block_m == 0 and f % block_k == 0
    n_i = m // block_m
    n_k = f // block_k
    sq = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)        # (C, M)

    grid = (c, n_i, n_i, n_k)            # (client, j-tile, i-step, F-step)
    kernel = functools.partial(_build_cost_feats_kernel, n_i=n_i, n_k=n_k,
                               block_m=block_m)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), lambda b, j, i, k: (b, i, k)),
            pl.BlockSpec((1, block_m, block_k), lambda b, j, i, k: (b, j, k)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i, k: (b, i, 0)),
            pl.BlockSpec((1, 1, block_m), lambda b, j, i, k: (b, 0, j)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i, k: (b, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i, k: (b, i, 0)),
            pl.BlockSpec((1, 1, block_m), lambda b, j, i, k: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_m), lambda b, j, i, k: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((c, 1, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_m), jnp.float32),
                        pltpu.VMEM((1, block_m), jnp.float32)],
        interpret=interpret,
    )(x, x, _col(sq), _row(sq), _col(d_near), _col(vf), _row(vf))[:, 0]


def _delta_sweep_feats_kernel(xi_ref, xj_ref, sqi_ref, sqj_ref, d1_ref,
                              d2_ref, vfi_ref, vfj_ref, oh_ref, a_ref, b_ref,
                              dot_ref, acc_a_ref, acc_b_ref, *, n_i: int,
                              n_k: int, block_m: int):
    j_step = pl.program_id(1)
    i_step = pl.program_id(2)
    k_step = pl.program_id(3)

    @pl.when((i_step == 0) & (k_step == 0))
    def _init_acc():
        acc_a_ref[...] = jnp.zeros_like(acc_a_ref)
        acc_b_ref[...] = jnp.zeros_like(acc_b_ref)

    @pl.when(k_step == 0)
    def _init_dot():
        dot_ref[...] = jnp.zeros_like(dot_ref)

    xi = xi_ref[0].astype(jnp.float32)           # (bi, bk)
    xj = xj_ref[0].astype(jnp.float32)           # (bj, bk)
    dot_ref[...] += jax.lax.dot_general(
        xi, xj, (((1,), (1,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k - 1)
    def _fold():
        d = _dist_tile(dot_ref[...], sqi_ref[0].astype(jnp.float32),
                       sqj_ref[0].astype(jnp.float32), i_step, j_step,
                       block_m)
        d1 = d1_ref[0].astype(jnp.float32)            # (bi, 1)
        d2 = d2_ref[0].astype(jnp.float32)
        vf = vfi_ref[0].astype(jnp.float32)
        oh = oh_ref[0].astype(jnp.float32)            # (bi, K)
        shift = (jnp.minimum(d, d1) - d1) * vf        # ≤ 0 removal gain
        contrib = (jnp.clip(d, d1, d2) - d1) * vf     # per-cluster term
        acc_a_ref[...] += jnp.sum(shift, axis=0, keepdims=True)   # (1, bj)
        acc_b_ref[...] += jax.lax.dot_general(        # contribᵀ @ onehot
            contrib, oh, (((0,), (0,)), ((), ())), precision=F32,
            preferred_element_type=jnp.float32)       # (bj, K)

    @pl.when((i_step == n_i - 1) & (k_step == n_k - 1))
    def _epilogue():
        vfj = vfj_ref[0].astype(jnp.float32)          # (1, bj)
        a = jnp.where(vfj > 0.0, acc_a_ref[...], BIG)
        a_ref[0] = a.astype(a_ref.dtype)
        b_ref[0] = acc_b_ref[...].astype(b_ref.dtype)


def delta_sweep_from_feats_pallas(x: jnp.ndarray, d1: jnp.ndarray,
                                  d2: jnp.ndarray, vf: jnp.ndarray,
                                  n_onehot: jnp.ndarray, *,
                                  block_m: int = 128, block_k: int = 128,
                                  interpret: bool = False):
    """Distance-free FasterPAM Δ-sweep: the A/B reductions straight from
    the feature stack.

    x (C, M, F); d1/d2/vf (C, M); n_onehot (C, M, K).  Returns
    (A (C, M), B (C, M, K)) with Δ(j, l) = A[:, j] + B[:, j, l] and
    A[:, j] = +BIG for padded candidates (vf[:, j] = 0) so a zero-padded
    feature row can never tie-win a swap.  Distances are rebuilt per
    (i, j) tile from an F-tiled cross-term accumulation — no (C, M, M)
    intermediate.  M must be a multiple of ``block_m``, F of
    ``block_k``, K lane-aligned (ops.py owns the padding)."""
    c, m, f = x.shape
    kp = n_onehot.shape[-1]
    block_m = min(block_m, m)
    block_k = min(block_k, f)
    assert m % block_m == 0 and f % block_k == 0
    n_i = m // block_m
    n_k = f // block_k
    sq = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)        # (C, M)

    grid = (c, n_i, n_i, n_k)            # (client, j-tile, i-step, F-step)
    kernel = functools.partial(_delta_sweep_feats_kernel, n_i=n_i, n_k=n_k,
                               block_m=block_m)
    A, B = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), lambda b, j, i, k: (b, i, k)),
            pl.BlockSpec((1, block_m, block_k), lambda b, j, i, k: (b, j, k)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i, k: (b, i, 0)),
            pl.BlockSpec((1, 1, block_m), lambda b, j, i, k: (b, 0, j)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i, k: (b, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i, k: (b, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda b, j, i, k: (b, i, 0)),
            pl.BlockSpec((1, 1, block_m), lambda b, j, i, k: (b, 0, j)),
            pl.BlockSpec((1, block_m, kp), lambda b, j, i, k: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_m), lambda b, j, i, k: (b, 0, j)),
            pl.BlockSpec((1, block_m, kp), lambda b, j, i, k: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, 1, m), jnp.float32),
            jax.ShapeDtypeStruct((c, m, kp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, block_m), jnp.float32),
                        pltpu.VMEM((1, block_m), jnp.float32),
                        pltpu.VMEM((block_m, kp), jnp.float32)],
        interpret=interpret,
    )(x, x, _col(sq), _row(sq), _col(d1), _col(d2), _col(vf), _row(vf),
      n_onehot)
    return A[:, 0], B
