"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematical definition the kernel must reproduce;
tests sweep shapes/dtypes and assert allclose between kernel (interpret
mode on CPU) and these references.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Distance and Δ-sweep contractions run at full f32 precision: on a TPU an
# f32 matmul at default precision is one bf16 pass, which would leave the
# oracles (and the jnp fallbacks built on them) ~1e-3 off the kernels.
F32 = jax.lax.Precision.HIGHEST


def pairwise_l2_ref(x: jnp.ndarray, y: Optional[jnp.ndarray] = None, *,
                    squared: bool = False) -> jnp.ndarray:
    """(m, d), (n, d) -> (m, n) Euclidean distances, fp32 accumulation."""
    y = x if y is None else y
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    sq = (jnp.sum(xf * xf, axis=-1)[:, None]
          + jnp.sum(yf * yf, axis=-1)[None, :]
          - 2.0 * jnp.matmul(xf, yf.T, precision=F32))
    sq = jnp.maximum(sq, 0.0)
    return sq if squared else jnp.sqrt(sq)


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B, Hq, S, hd); k/v: (B, Hk, S, hd) -> (B, Hq, S, hd)."""
    b, hq, s, hd = q.shape
    hk = k.shape[1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(
        jnp.asarray(hd, jnp.float32))
    kr = jnp.repeat(k, hq // hk, axis=1).astype(jnp.float32)
    vr = jnp.repeat(v, hq // hk, axis=1).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr) * scale
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    ok = jnp.ones((s, s), bool)
    if causal:
        ok &= ki <= qi
    if window is not None:
        ok &= ki > qi - window
    logits = jnp.where(ok, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vr).astype(q.dtype)


def rmsnorm_ref(x: jnp.ndarray, scale: jnp.ndarray,
                eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def kmedoids_build_cost_ref(D: jnp.ndarray, d_near: jnp.ndarray,
                            vf: jnp.ndarray) -> jnp.ndarray:
    """Greedy BUILD add-cost over a masked distance stack.

    D (..., M, M); d_near/vf (..., M).  Returns
    cost[..., j] = Σ_i min(d_near_i, D_ij)·vf_i — the cost of the point
    set after adding candidate j to the current medoids (``d_near`` is
    each point's distance to its nearest already-chosen medoid; pass
    +BIG for the first pick so cost reduces to the plain column sum).
    """
    add = jnp.minimum(d_near[..., None], D) * vf[..., None]
    return jnp.sum(add, axis=-2)


def kmedoids_delta_sweep_ref(D: jnp.ndarray, d1: jnp.ndarray,
                             d2: jnp.ndarray, vf: jnp.ndarray,
                             n_onehot: jnp.ndarray):
    """FasterPAM swap-sweep reductions (the Δ(j, l) = A_j + B_{j,l} split).

    D (..., M, M); d1/d2/vf (..., M); n_onehot (..., M, K) one-hot of each
    point's nearest-medoid slot.  Returns (A (..., M), B (..., M, K)):

        A[j]    = Σ_i (min(D_ij, d1_i) − d1_i) · vf_i
        B[j, l] = Σ_{i: n(i)=l} (clip(D_ij, d1_i, d2_i) − d1_i) · vf_i

    ``clip(D, d1, d2) − d1`` is the case-collapsed form of the textbook
    ``min(D, d2) − d1 − min(D − d1, 0)`` (bitwise equal for d1 ≤ d2):
    one elementwise pass instead of three.
    """
    d1e = d1[..., None]
    shift = (jnp.minimum(D, d1e) - d1e) * vf[..., None]
    contrib = (jnp.clip(D, d1e, d2[..., None]) - d1e) * vf[..., None]
    A = jnp.sum(shift, axis=-2)
    B = jnp.einsum("...ij,...il->...jl", contrib, n_onehot, precision=F32)
    return A, B


# ---------------------------------------------------------------------------
# distance-free oracles: these DO materialize D — that is the point.
# The parity gate is "fused feature-tiled kernel == materialize-then-reduce",
# exactly as PR 4 gated the Δ-sweep against the unfused stack.
# ---------------------------------------------------------------------------

_BIG = 1e30


def _pairwise_from_feats(x: jnp.ndarray) -> jnp.ndarray:
    """(..., M, F) -> (..., M, M) L2 stack with an exact-zero diagonal."""
    xf = x.astype(jnp.float32)
    sq = jnp.sum(xf * xf, axis=-1)
    d2 = (sq[..., :, None] + sq[..., None, :]
          - 2.0 * jnp.einsum("...if,...jf->...ij", xf, xf, precision=F32))
    d = jnp.sqrt(jnp.maximum(d2, 0.0))
    m = x.shape[-2]
    eye = jnp.eye(m, dtype=bool)
    return jnp.where(eye, 0.0, d)


def kmedoids_build_cost_from_feats_ref(x: jnp.ndarray, d_near: jnp.ndarray,
                                       vf: jnp.ndarray) -> jnp.ndarray:
    """Materializing oracle for ``build_cost_from_feats_pallas``.

    x (..., M, F); d_near/vf (..., M).  Builds the full distance stack,
    runs the BUILD reduction, then masks padded candidate columns
    (vf_j = 0) to +BIG — the same +inf election guard the fused kernel
    applies in its epilogue so a zero-padded feature row can never
    tie-win over a valid point.
    """
    D = _pairwise_from_feats(x)
    cost = kmedoids_build_cost_ref(D, d_near, vf)
    return jnp.where(vf > 0.0, cost, _BIG)


def kmedoids_delta_sweep_from_feats_ref(x: jnp.ndarray, d1: jnp.ndarray,
                                        d2: jnp.ndarray, vf: jnp.ndarray,
                                        n_onehot: jnp.ndarray):
    """Materializing oracle for ``delta_sweep_from_feats_pallas``.

    Same (A, B) split as :func:`kmedoids_delta_sweep_ref`, computed from
    the (..., M, F) feature stack by materializing D first, with
    A[..., j] = +BIG for padded candidates (vf_j = 0).
    """
    D = _pairwise_from_feats(x)
    A, B = kmedoids_delta_sweep_ref(D, d1, d2, vf, n_onehot)
    return jnp.where(vf > 0.0, A, _BIG), B
