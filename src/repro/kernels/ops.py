"""Jit'd public wrappers around the Pallas kernels.

Handle padding to block multiples, GQA/odd shapes, backend selection
(interpret mode on CPU so the whole framework runs in this container;
compiled kernels on real TPU), and expose a jnp fallback for shapes the
kernels don't support.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.kmedoids_pallas import (build_cost_from_feats_pallas,
                                           build_cost_pallas,
                                           delta_sweep_from_feats_pallas,
                                           delta_sweep_pallas)
from repro.kernels.pairwise_l2 import (pairwise_l2_batched_pallas,
                                       pairwise_l2_pallas)
from repro.kernels.rmsnorm import rmsnorm_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_use_kernel(use_kernel: Optional[bool]) -> bool:
    """Resolve the tri-state kernel switch used across the selection path.

    ``True``/``False`` force the Pallas kernels on/off; ``None`` (auto)
    enables them on backends where they compile natively (TPU) and falls
    back to the identical-math jnp formulations elsewhere — interpret
    mode keeps CI coverage, but on CPU the fused jnp path is the fast
    one.  Resolve *before* any jit boundary so auto and its resolved
    value share one compilation cache entry.
    """
    return _on_tpu() if use_kernel is None else bool(use_kernel)


def zero_self_diag(d: jnp.ndarray) -> jnp.ndarray:
    """Exact zeros on the self-distance diagonal of (..., M, M) stacks.

    ``‖a‖² + ‖b‖² − 2ab`` cancels imperfectly in float32, leaving tiny
    nonzeros (or NaN-adjacent negatives pre-clamp) on the diagonal; every
    self-distance consumer (k-medoids BUILD/SWAP) needs literal zeros.
    This helper is the single owner of that fix-up — the pairwise
    wrappers apply it under ``zero_diag=True`` rather than each caller
    re-deriving it.
    """
    m = d.shape[-1]
    return d * (1.0 - jnp.eye(m, dtype=d.dtype))


def _pad_to(x, axis: int, multiple: int):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def _pow2_block(n: int, cap: int, shrink: bool, floor: int = 8) -> int:
    """Tile size for a dim of size ``n``: the next power of two, clipped to
    [floor, cap].  With ``shrink`` (interpret mode only), shapes smaller
    than the default MXU tile get a tile sized to the problem instead of
    padding up to the full block — for the fleet engine's small cohort
    groups (M = 32/64) this cuts the padded distance work by up to 16x.
    Compiled TPU kernels keep the MXU-aligned defaults: sub-(8, 128)
    blocks fight Mosaic's float32 tiling for no bandwidth win there."""
    if not shrink:
        return cap
    p = 1 << max(int(n) - 1, 0).bit_length()
    return max(floor, min(cap, p))


@functools.partial(jax.jit, static_argnames=("squared", "zero_diag",
                                             "block_m", "block_n",
                                             "block_k", "interpret"))
def pairwise_l2(x, y=None, *, squared: bool = False, zero_diag: bool = False,
                block_m: int = 128, block_n: int = 128, block_k: int = 512,
                interpret: Optional[bool] = None):
    """Pairwise Euclidean distances via the MXU-tiled kernel.

    Zero-row padding is exact for the cross term; padded rows/cols are
    sliced off before returning.  ``zero_diag`` (self-mode only) pins the
    self-distance diagonal to exact zeros for k-medoids consumers.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    self_mode = y is None
    y = x if y is None else y
    block_m = _pow2_block(x.shape[0], block_m, shrink=interpret)
    block_n = _pow2_block(y.shape[0], block_n, shrink=interpret)
    xp, m = _pad_to(x, 0, block_m)
    yp, n = _pad_to(y, 0, block_n)
    xp, d = _pad_to(xp, 1, 128)
    yp, _ = _pad_to(yp, 1, 128)
    bk = min(block_k, xp.shape[1])
    while xp.shape[1] % bk:
        bk //= 2
    out = pairwise_l2_pallas(xp, None if self_mode and xp.shape == yp.shape
                             else yp, squared=squared, block_m=block_m,
                             block_n=block_n, block_k=bk,
                             interpret=interpret)
    out = out[:m, :n]
    return zero_self_diag(out) if zero_diag and self_mode else out


@functools.partial(jax.jit, static_argnames=("squared", "use_kernel",
                                             "zero_diag", "block_m",
                                             "block_k", "interpret"))
def pairwise_l2_batched(x, *, squared: bool = False, use_kernel: bool = True,
                        zero_diag: bool = False, block_m: int = 128,
                        block_k: int = 512,
                        interpret: Optional[bool] = None):
    """Per-client self-distance stacks: x (C, M, D) -> (C, M, M).

    The fleet engine's batched coreset-selection front end.  Pads M and D
    to block multiples (zero rows are exact for the cross term, and padded
    rows/cols are sliced off before returning) and dispatches to the
    batched Pallas kernel; ``use_kernel=False`` is the identical-math jnp
    einsum formulation for backends/shapes the kernel doesn't cover.
    ``zero_diag`` pins each client's self-distance diagonal to exact
    zeros (the k-medoids contract).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    if not use_kernel:
        out = jax.vmap(lambda xi: ref.pairwise_l2_ref(xi, squared=squared)
                       )(x)
        return zero_self_diag(out) if zero_diag else out
    block_m = _pow2_block(x.shape[1], block_m, shrink=interpret)
    xp, m = _pad_to(x, 1, block_m)
    xp, _ = _pad_to(xp, 2, 128)
    bk = min(block_k, xp.shape[2])
    while xp.shape[2] % bk:
        bk //= 2
    out = pairwise_l2_batched_pallas(xp, squared=squared, block_m=block_m,
                                     block_k=bk, interpret=interpret)
    out = out[:, :m, :m]
    return zero_self_diag(out) if zero_diag else out


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None, block_q: int = 256,
                    block_k: int = 256, interpret: Optional[bool] = None):
    """q (B,Hq,S,hd), k/v (B,Hk,S,hd) -> (B,Hq,S,hd)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    s = q.shape[2]
    bq = min(block_q, s)
    bk = min(block_k, s)
    while s % bq:
        bq //= 2
    while s % bk:
        bk //= 2
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  scale=scale, block_q=bq, block_k=bk,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("use_kernel", "block_m",
                                             "interpret"))
def kmedoids_build_cost(D, d_near, vf, *, use_kernel: bool = True,
                        block_m: int = 128,
                        interpret: Optional[bool] = None):
    """Fused BUILD add-cost: D (C, M, M), d_near/vf (C, M) -> (C, M).

    One tiled pass over the distance stack per greedy add instead of a
    materialized (C, M, M) ``minimum`` tensor.  ``use_kernel=False`` is
    the identical-math jnp formulation (``ref.kmedoids_build_cost_ref``).
    Padded rows/cols (to the block multiple) carry vf = 0 so they add
    exactly nothing; padded cost columns are sliced off.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    if not use_kernel:
        return ref.kmedoids_build_cost_ref(D, d_near, vf)
    m = D.shape[1]
    block_m = _pow2_block(m, block_m, shrink=interpret)
    Dp, _ = _pad_to(D, 1, block_m)
    Dp, _ = _pad_to(Dp, 2, block_m)
    dnp, _ = _pad_to(d_near, 1, block_m)
    vfp, _ = _pad_to(vf, 1, block_m)
    out = build_cost_pallas(Dp, dnp, vfp, block_m=block_m,
                            interpret=interpret)
    return out[:, :m]


@functools.partial(jax.jit, static_argnames=("use_kernel", "block_m",
                                             "interpret"))
def kmedoids_delta_sweep(D, d1, d2, vf, n_onehot, *, use_kernel: bool = True,
                         block_m: int = 128,
                         interpret: Optional[bool] = None):
    """Fused FasterPAM Δ-sweep reductions: one pass over D per sweep.

    D (C, M, M); d1/d2/vf (C, M); n_onehot (C, M, k).  Returns
    (A (C, M), B (C, M, k)) with Δ(j, l) = A[:, j] + B[:, j, l] — see
    ``ref.kmedoids_delta_sweep_ref`` for the math, which is also the
    ``use_kernel=False`` fallback.  M pads to the block multiple
    (vf = 0 rows contribute nothing), k pads to a lane-aligned width
    with zero one-hot mass (extra B columns are exactly 0, sliced off).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    if not use_kernel:
        return ref.kmedoids_delta_sweep_ref(D, d1, d2, vf, n_onehot)
    m, k = D.shape[1], n_onehot.shape[-1]
    block_m = _pow2_block(m, block_m, shrink=interpret)
    k_pad = _pow2_block(k, 128, shrink=True) if interpret else -(-k // 128
                                                                 ) * 128
    Dp, _ = _pad_to(D, 1, block_m)
    Dp, _ = _pad_to(Dp, 2, block_m)
    d1p, _ = _pad_to(d1, 1, block_m)
    d2p, _ = _pad_to(d2, 1, block_m)
    vfp, _ = _pad_to(vf, 1, block_m)
    ohp, _ = _pad_to(n_onehot, 1, block_m)
    ohp, _ = _pad_to(ohp, 2, k_pad)
    A, B = delta_sweep_pallas(Dp, d1p, d2p, vfp, ohp, block_m=block_m,
                              interpret=interpret)
    return A[:, :m], B[:, :m, :k]


_BIG = 1e30      # candidate mask for padded lanes (matches core.kmedoids.BIG)


def _feat_blocks(m: int, f: int, block_m: int, block_k: int,
                 interpret: bool):
    """(block_m, block_k, f_multiple) for the feature-tiled kernels.

    The stack-path wrappers shrink block_m to the problem in interpret
    mode but always pad F up to 128, so a tiny cohort group (M = 32,
    F = 16) paid pow2 padding waste twice — once in M, once in F.  Here
    interpret mode sizes BOTH tiles to the problem (pow2, floor 8) and
    pads F only up to the shrunk tile; compiled TPU kernels keep the
    lane-aligned 128-multiple on F (Mosaic's float32 lane requirement)
    and the MXU-sized block_m.
    """
    bm = _pow2_block(m, block_m, shrink=interpret)
    if interpret:
        bk = _pow2_block(f, block_k, shrink=True)
        return bm, bk, bk
    fp = -(-f // 128) * 128
    bk = min(block_k, fp)
    while fp % bk:
        bk //= 2
    return bm, bk, 128


def _feats_dist_chunk(xf, sq, j0, chunk):
    """(C, M, chunk) distance slab for candidate columns [j0, j0+chunk).

    Exact-zero diagonal pinned via global row/col index comparison (the
    chunked analogue of ``zero_self_diag``).
    """
    xj = jax.lax.dynamic_slice_in_dim(xf, j0, chunk, axis=1)
    sqj = jax.lax.dynamic_slice_in_dim(sq, j0, chunk, axis=1)
    d2 = (sq[..., :, None] + sqj[..., None, :]
          - 2.0 * jnp.einsum("cif,cjf->cij", xf, xj, precision=ref.F32))
    d = jnp.sqrt(jnp.maximum(d2, 0.0))
    rows = jnp.arange(xf.shape[1])[None, :, None]
    cols = (j0 + jnp.arange(chunk))[None, None, :]
    return jnp.where(rows == cols, 0.0, d)


def _feats_prep_chunked(x, chunk: int):
    """Pad M to a chunk multiple and precompute fp32 features + sq norms."""
    m = x.shape[1]
    chunk = min(chunk, _pow2_block(m, chunk, shrink=True))
    xp, _ = _pad_to(x, 1, chunk)
    xf = xp.astype(jnp.float32)
    sq = jnp.sum(xf * xf, axis=-1)
    starts = jnp.arange(0, xp.shape[1], chunk)
    return xf, sq, starts, chunk, m


def _build_cost_from_feats_jnp(x, d_near, vf, *, chunk: int):
    """O(C·M·chunk) jnp fallback: lax.map over candidate-column chunks."""
    xf, sq, starts, chunk, m = _feats_prep_chunked(x, chunk)
    vfp, _ = _pad_to(vf, 1, chunk)
    dnp, _ = _pad_to(d_near, 1, chunk)

    def body(j0):
        d = _feats_dist_chunk(xf, sq, j0, chunk)
        cost = jnp.sum(jnp.minimum(dnp[..., None], d)
                       * vfp[..., None], axis=-2)
        vfj = jax.lax.dynamic_slice_in_dim(vfp, j0, chunk, axis=1)
        return jnp.where(vfj > 0.0, cost, _BIG)

    out = jax.lax.map(body, starts)               # (n_chunks, C, chunk)
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)[:, :m]


def _delta_sweep_from_feats_jnp(x, d1, d2, vf, n_onehot, *, chunk: int):
    """O(C·M·chunk) jnp fallback for the Δ-sweep reductions."""
    xf, sq, starts, chunk, m = _feats_prep_chunked(x, chunk)
    vfp, _ = _pad_to(vf, 1, chunk)
    ohp, _ = _pad_to(n_onehot, 1, chunk)
    d1p, _ = _pad_to(d1, 1, chunk)
    d2p, _ = _pad_to(d2, 1, chunk)
    d1e = d1p[..., None]
    d2e = d2p[..., None]
    vfe = vfp[..., None]

    def body(j0):
        d = _feats_dist_chunk(xf, sq, j0, chunk)
        shift = (jnp.minimum(d, d1e) - d1e) * vfe
        contrib = (jnp.clip(d, d1e, d2e) - d1e) * vfe
        a = jnp.sum(shift, axis=-2)               # (C, chunk)
        b = jnp.einsum("cij,cil->cjl", contrib, ohp, precision=ref.F32)
        vfj = jax.lax.dynamic_slice_in_dim(vfp, j0, chunk, axis=1)
        return jnp.where(vfj > 0.0, a, _BIG), b

    A, B = jax.lax.map(body, starts)
    c = x.shape[0]
    A = jnp.moveaxis(A, 0, 1).reshape(c, -1)[:, :m]
    B = jnp.moveaxis(B, 0, 1).reshape(c, -1, n_onehot.shape[-1])[:, :m]
    return A, B


@functools.partial(jax.jit, static_argnames=("use_kernel", "block_m",
                                             "block_k", "chunk",
                                             "interpret"))
def kmedoids_build_cost_from_feats(x, d_near, vf, *, use_kernel: bool = True,
                                   block_m: int = 128, block_k: int = 128,
                                   chunk: int = 256,
                                   interpret: Optional[bool] = None):
    """Distance-free BUILD add-cost: x (C, M, F), d_near/vf (C, M) -> (C, M).

    Same reduction as :func:`kmedoids_build_cost` but the (C, M, M)
    distance stack never exists — the Pallas kernel rebuilds each
    distance tile from F-tiled cross terms (O(C·M·F) memory), and the
    ``use_kernel=False`` fallback streams O(C·M·chunk) column slabs via
    ``lax.map``.  Padded candidate columns (vf = 0) return +BIG so they
    can never win the greedy argmin; padded rows contribute nothing.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    if not use_kernel:
        return _build_cost_from_feats_jnp(x, d_near, vf, chunk=chunk)
    m = x.shape[1]
    bm, bk, fmul = _feat_blocks(m, x.shape[2], block_m, block_k, interpret)
    xp, _ = _pad_to(x, 1, bm)
    xp, _ = _pad_to(xp, 2, fmul)
    dnp, _ = _pad_to(d_near, 1, bm)
    vfp, _ = _pad_to(vf, 1, bm)
    out = build_cost_from_feats_pallas(xp, dnp, vfp, block_m=bm, block_k=bk,
                                       interpret=interpret)
    return out[:, :m]


@functools.partial(jax.jit, static_argnames=("use_kernel", "block_m",
                                             "block_k", "chunk",
                                             "interpret"))
def kmedoids_delta_sweep_from_feats(x, d1, d2, vf, n_onehot, *,
                                    use_kernel: bool = True,
                                    block_m: int = 128, block_k: int = 128,
                                    chunk: int = 256,
                                    interpret: Optional[bool] = None):
    """Distance-free FasterPAM Δ-sweep: x (C, M, F) in, (A, B) out.

    Same (A, B) split as :func:`kmedoids_delta_sweep` with D rebuilt on
    the fly per tile; A carries +BIG at padded candidates (vf = 0) so a
    zero-padded feature row can never tie-win a swap over a valid point
    (zero rows are mutually at distance 0 — the election bug this
    masking closes).  ``use_kernel=False`` streams column slabs.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    if not use_kernel:
        return _delta_sweep_from_feats_jnp(x, d1, d2, vf, n_onehot,
                                           chunk=chunk)
    m, k = x.shape[1], n_onehot.shape[-1]
    bm, bk, fmul = _feat_blocks(m, x.shape[2], block_m, block_k, interpret)
    k_pad = _pow2_block(k, 128, shrink=True) if interpret else -(-k // 128
                                                                 ) * 128
    xp, _ = _pad_to(x, 1, bm)
    xp, _ = _pad_to(xp, 2, fmul)
    d1p, _ = _pad_to(d1, 1, bm)
    d2p, _ = _pad_to(d2, 1, bm)
    vfp, _ = _pad_to(vf, 1, bm)
    ohp, _ = _pad_to(n_onehot, 1, bm)
    ohp, _ = _pad_to(ohp, 2, k_pad)
    A, B = delta_sweep_from_feats_pallas(xp, d1p, d2p, vfp, ohp, block_m=bm,
                                         block_k=bk, interpret=interpret)
    return A[:, :m], B[:, :m, :k]


@functools.partial(jax.jit, static_argnames=("eps", "block_m", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, block_m: int = 256,
            interpret: Optional[bool] = None):
    """Fused RMSNorm over the last axis; leading axes are flattened."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    xp, m = _pad_to(x2, 0, min(block_m, max(1, x2.shape[0])))
    bm = min(block_m, xp.shape[0])
    while xp.shape[0] % bm:
        bm //= 2
    out = rmsnorm_pallas(xp, scale, eps=eps, block_m=bm, interpret=interpret)
    return out[:m].reshape(shape)
