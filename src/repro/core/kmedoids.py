"""k-medoids solvers for the FedCore coreset problem (Eq. 5).

Two implementations of the same (BUILD + PAM-objective SWAP) algorithm:

* ``kmedoids_numpy``  — host-side, loops until convergence.  Serves as the
  exactness oracle and matches the paper's FasterPAM usage (the swap step
  evaluates the full FasterPAM Δ(j, l) table each sweep, vectorized).
* ``kmedoids_batched`` — the TPU-native adaptation and the fleet engine's
  hot path: identical dense math over a whole (C, M, M) cohort distance
  stack inside one ``lax.while_loop`` (data-dependent early exit via the
  any-lane-still-improving predicate; converged lanes are fixed points of
  the sweep, so each lane's result equals its standalone solve).
  ``kmedoids_masked`` / ``kmedoids_jax`` are the C = 1 (and additionally
  all-valid) views of the same solver — one copy of the Δ-table math.

Both take precomputed distances and a budget ``k`` and return (medoid
indices, assignment, cluster-size weights, objective).

Swap Δ derivation (FasterPAM, Schubert & Rousseeuw 2021): with d1/d2 the
nearest/second-nearest medoid distance of each point and n(i) the nearest
medoid index,

    Δ(j, l) = Σ_i [ n(i)=l ? min(D[i,j], d2_i) − d1_i : min(D[i,j] − d1_i, 0) ]
            = A_j + B_{j,l}
    A_j     = Σ_i ( min(D[i,j], d1_i) − d1_i )
    B_{j,l} = Σ_{i: n(i)=l} ( clip(D[i,j], d1_i, d2_i) − d1_i )

(the clip form collapses the textbook ``min(D, d2) − d1 − min(D − d1, 0)``
case split — bitwise equal for d1 ≤ d2).  One sweep is therefore a single
pass over D producing a dense (m,) + (m, k) pair; the fused Pallas kernel
(``repro.kernels.kmedoids_pallas.delta_sweep_pallas``) computes both
reductions tile-by-tile, and ``repro.kernels.ref.kmedoids_delta_sweep_ref``
is the identical-math jnp fallback.  ``legacy_sweep=True`` keeps the
pre-fusion ``minimum``/``one_hot``/``einsum`` chain (3+ full O(M²) passes
per sweep) as the measured A/B baseline for
``benchmarks/fleet_sweep.py``'s selection-phase breakdown.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import F32

BIG = 1e30

# Ties: two candidates whose costs agree to float32 rounding are an
# equal-cost tie (a 2-point cluster's two members, a symmetric BUILD
# pick), and which one argmin returns then depends on reduction order —
# kernel vs jnp, vmapped lane vs single client.  The on-device solvers
# break such ties to the lowest index among the candidates within
# TIE_RTOL of the best (relative to the lane's objective scale), so every
# execution path of the same instance takes the same BUILD and SWAP
# steps.  The f64 numpy oracle breaks exact ties the same way.
TIE_RTOL = 1e-5
# A swap must lower the objective by more than SWAP_RTOL of it.  Δ sums
# hundreds of float32 distances, each off by up to ~sqrt(eps)·‖x‖ where
# the norm expansion cancels; an absolute threshold below that noise
# lets the sweep take phantom "improvements" and cycle until max_sweeps,
# ending wherever the noise of that execution path leaves it.
SWAP_RTOL = 1e-5


class KMedoidsResult(NamedTuple):
    medoids: jnp.ndarray     # (k,) int32 indices into the dataset
    assignment: jnp.ndarray  # (m,) int32 index into [0, k)
    weights: jnp.ndarray     # (k,) int32 cluster sizes (the paper's δ)
    objective: jnp.ndarray   # scalar Σ_i min_k D[i, medoid_k]
    # on-device solvers only (None from the numpy oracle): swap sweeps
    # the while_loop ran (every lane runs as many as its slowest one) and
    # how many of them still swapped a medoid, per lane
    sweeps: Optional[jnp.ndarray] = None     # scalar int32
    improving: Optional[jnp.ndarray] = None  # (C,) int32 (scalar if C = 1)


# ---------------------------------------------------------------------------
# numpy oracle
# ---------------------------------------------------------------------------

def _build_numpy(D: np.ndarray, k: int) -> np.ndarray:
    m = D.shape[0]
    medoids = np.empty(k, np.int64)
    medoids[0] = np.argmin(D.sum(axis=0))
    d_near = D[:, medoids[0]].copy()
    for i in range(1, k):
        # cost of adding candidate j: sum(min(d_near, D[:, j]))
        cost = np.minimum(d_near[:, None], D).sum(axis=0)
        cost[medoids[:i]] = BIG
        medoids[i] = np.argmin(cost)
        d_near = np.minimum(d_near, D[:, medoids[i]])
    return medoids


def swap_deltas_numpy(D: np.ndarray, medoids: np.ndarray
                      ) -> Tuple[np.ndarray, float]:
    """FasterPAM's swap table in float64, and the objective.

    ``delta[j, l]`` is the change of Σ_i min_s D[i, s] when medoid slot
    ``l`` is replaced by point ``j`` (BIG where ``j`` is already a
    medoid).  A medoid set is a fixed point of the swap search when no
    entry is below −threshold."""
    D = np.asarray(D, np.float64)
    m, k = D.shape[0], len(medoids)
    dm = D[:, medoids]                      # (m, k)
    order = np.argsort(dm, axis=1)
    n_idx = order[:, 0]                     # nearest medoid slot
    d1 = dm[np.arange(m), n_idx]
    d2 = dm[np.arange(m), order[:, 1]] if k > 1 else np.full(m, BIG)

    A = np.minimum(D - d1[:, None], 0.0).sum(axis=0)              # (m,)
    contrib = (np.minimum(D, d2[:, None]) - d1[:, None]
               - np.minimum(D - d1[:, None], 0.0))                # (m_i, m_j)
    B = np.zeros((m, k))
    np.add.at(B.T, n_idx, contrib)  # B[j, l] = Σ_{i: n(i)=l} contrib[i, j]
    delta = A[:, None] + B                                        # (m_j, k)
    delta[medoids, :] = BIG  # cannot swap a medoid in
    return delta, float(d1.sum())


def kmedoids_numpy(D: np.ndarray, k: int, max_sweeps: int = 100
                   ) -> KMedoidsResult:
    D = np.asarray(D, np.float64)
    m = D.shape[0]
    k = min(k, m)
    medoids = _build_numpy(D, k)

    for _ in range(max_sweeps):
        delta, _ = swap_deltas_numpy(D, medoids)
        j, l = np.unravel_index(np.argmin(delta), delta.shape)
        if delta[j, l] >= -1e-12:
            break
        medoids[l] = j

    dm = D[:, medoids]
    assignment = np.argmin(dm, axis=1)
    weights = np.bincount(assignment, minlength=k)
    objective = dm[np.arange(m), assignment].sum()
    return KMedoidsResult(jnp.asarray(medoids, jnp.int32),
                          jnp.asarray(assignment, jnp.int32),
                          jnp.asarray(weights, jnp.int32),
                          jnp.asarray(objective, jnp.float32))


# ---------------------------------------------------------------------------
# JAX on-device solver (natively batched; masked lanes)
# ---------------------------------------------------------------------------

def _argmin_tied(cost: jnp.ndarray) -> jnp.ndarray:
    """(C, N) -> (C,) lowest index within TIE_RTOL·|min| of each row's
    minimum (BUILD picks: costs are objective-sized and positive)."""
    best = jnp.min(cost, axis=1, keepdims=True)
    near = cost <= best + TIE_RTOL * jnp.abs(best)
    return jnp.argmax(near, axis=1).astype(jnp.int32)


def _pick_swap(delta: jnp.ndarray, medoids: jnp.ndarray, objective):
    """One FasterPAM step from the masked (C, M, k) Δ table.

    Takes the lowest flat (j, l) among the improving swaps within
    TIE_RTOL·objective of the best one; returns (medoids after the swap,
    and per lane whether it swapped: best Δ < −SWAP_RTOL·objective)."""
    c, m, k = delta.shape
    flat_d = delta.reshape(c, m * k)
    best = jnp.min(flat_d, axis=1)
    eps = SWAP_RTOL * objective
    improves = best < -eps
    thr = jnp.where(improves,
                    jnp.minimum(best + TIE_RTOL * objective, -eps), best)
    flat = jnp.argmax(flat_d <= thr[:, None], axis=1)
    j = (flat // k).astype(jnp.int32)
    l = (flat % k).astype(jnp.int32)
    swapped = jnp.where(jnp.arange(k, dtype=jnp.int32)[None]
                        == l[:, None], j[:, None], medoids)
    return jnp.where(improves[:, None], swapped, medoids), improves


def _take_col(D: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """D (C, M, M), idx (C,) -> (C, M) = D[c, :, idx[c]]."""
    return jnp.take_along_axis(D, idx[:, None, None], axis=2)[..., 0]


@partial(jax.jit, static_argnames=("k", "max_sweeps", "use_kernel",
                                   "legacy_sweep"))
def _kmedoids_batched(D: jnp.ndarray, valid: jnp.ndarray, k: int,
                      max_sweeps: int, use_kernel: bool,
                      legacy_sweep: bool) -> KMedoidsResult:
    from repro.kernels.ops import kmedoids_build_cost, kmedoids_delta_sweep

    D = D.astype(jnp.float32)
    c, m = D.shape[0], D.shape[1]
    vf = valid.astype(jnp.float32)          # (C, M) 1.0 on real samples
    invalid = ~valid.astype(bool)
    iota_m = jnp.arange(m, dtype=jnp.int32)

    # ---- BUILD (greedy adds; sums masked by vf, invalid candidates BIG) ---
    def add_cost(d_near):
        # Σ_i min(d_near_i, D_ij)·vf_i — the fused one-pass reduction
        # (d_near = +BIG for the first pick reduces it to the column sum)
        return kmedoids_build_cost(D, d_near, vf, use_kernel=use_kernel)

    def build_step(carry, _):
        d_near, chosen = carry
        cost = jnp.where(chosen | invalid, BIG, add_cost(d_near))
        nxt = _argmin_tied(cost)
        d_near = jnp.minimum(d_near, _take_col(D, nxt))
        chosen = chosen | (iota_m[None] == nxt[:, None])
        return (d_near, chosen), nxt

    with jax.named_scope("build"):
        cost0 = jnp.where(invalid, BIG, add_cost(jnp.full((c, m), BIG,
                                                          jnp.float32)))
        first = _argmin_tied(cost0)                                # (C,)
        d_near0 = _take_col(D, first)
        mask0 = iota_m[None] == first[:, None]
        if k > 1:
            (_, _), rest = jax.lax.scan(build_step, (d_near0, mask0), None,
                                        length=k - 1)
            medoids0 = jnp.concatenate([first[:, None], rest.T], axis=1)
        else:
            medoids0 = first[:, None]

    # ---- SWAP sweeps (FasterPAM Δ table; all reductions masked by vf) -----
    def sweep(state):
        medoids, _, it, improved = state
        dm = jnp.take_along_axis(D, medoids[:, None, :], axis=2)  # (C, M, k)
        if legacy_sweep:
            # pre-fusion baseline: top_k stats + 3-pass minimum/one_hot/
            # einsum chain (kept for the selection-phase A/B benchmark)
            if k > 1:
                top2_val, top2_idx = jax.lax.top_k(-dm, 2)
                d1, d2 = -top2_val[..., 0], -top2_val[..., 1]
                n_idx = top2_idx[..., 0]
            else:
                d1 = dm[..., 0]
                d2 = jnp.full((c, m), BIG)
                n_idx = jnp.zeros((c, m), jnp.int32)
            shift = jnp.minimum(D - d1[..., None], 0.0) * vf[..., None]
            A = jnp.sum(shift, axis=1)
            contrib = ((jnp.minimum(D, d2[..., None]) - d1[..., None])
                       * vf[..., None] - shift)
            onehot = jax.nn.one_hot(n_idx, k, dtype=contrib.dtype)
            B = jnp.einsum("cij,cil->cjl", contrib, onehot,
                           precision=F32)
        else:
            d1 = jnp.min(dm, axis=-1)
            n_idx = jnp.argmin(dm, axis=-1).astype(jnp.int32)
            n_onehot = (jnp.arange(k, dtype=jnp.int32)[None, None]
                        == n_idx[..., None])
            # second-nearest = min with the nearest slot masked out
            # (k = 1 masks everything, giving the conventional d2 = BIG)
            d2 = jnp.min(jnp.where(n_onehot, BIG, dm), axis=-1)
            A, B = kmedoids_delta_sweep(D, d1, d2, vf,
                                        n_onehot.astype(D.dtype),
                                        use_kernel=use_kernel)
        delta = A[..., None] + B                                  # (C, M, k)
        is_medoid = (iota_m[None, :, None] == medoids[:, None, :]).any(-1)
        delta = jnp.where((is_medoid | invalid)[..., None], BIG, delta)
        medoids, improves = _pick_swap(delta, medoids,
                                       jnp.sum(d1 * vf, axis=1))
        return (medoids, improves, it + 1,
                improved + improves.astype(jnp.int32))

    def cond(state):
        _, improves, it, _ = state
        return jnp.any(improves) & (it < max_sweeps)

    state = (medoids0.astype(jnp.int32), jnp.ones((c,), bool),
             jnp.asarray(0, jnp.int32), jnp.zeros((c,), jnp.int32))
    with jax.named_scope("swap"):
        medoids, _, sweeps, improving = jax.lax.while_loop(cond, sweep,
                                                           state)

    dm = jnp.take_along_axis(D, medoids[:, None, :], axis=2)
    assignment = jnp.where(valid, jnp.argmin(dm, axis=-1),
                           -1).astype(jnp.int32)
    weights = jnp.sum(jax.nn.one_hot(assignment, k, dtype=jnp.int32), axis=1)
    objective = jnp.sum(jnp.min(dm, axis=-1) * vf, axis=1)
    return KMedoidsResult(medoids.astype(jnp.int32), assignment, weights,
                          objective, sweeps, improving)


def kmedoids_batched(D: jnp.ndarray, valid: jnp.ndarray, k: int,
                     max_sweeps: int = 50,
                     use_kernel: Optional[bool] = None,
                     legacy_sweep: bool = False) -> KMedoidsResult:
    """One masked k-medoids solve per client over a cohort stack.

    D: (C, M, M) distance stack; valid: (C, M) sample masks; static ``k``
    shared across the cohort (the fleet engine groups clients by quantized
    budget).  Only rows/cols with ``valid[c, i]`` True are real samples;
    padded entries may hold arbitrary finite values, are never selected as
    medoids, contribute nothing to any objective or Δ sum, and get
    assignment −1 / weight 0.  Callers must guarantee
    ``k <= valid[c].sum()`` per lane (not checkable under jit).

    Returns a ``KMedoidsResult`` of stacked fields.  The batched
    ``while_loop`` runs until every lane's swap phase converges; converged
    lanes are fixed points of the sweep (no Δ < −SWAP_RTOL·objective
    remains, so the masked update is the identity), hence each lane's
    result equals its standalone ``kmedoids_masked`` solve.  ``sweeps``
    counts the loop's sweeps and ``improving`` (C,) the sweeps in which
    each lane still swapped: a lane converged after BUILD reads 0, and
    ``sweeps − improving`` is the work spent on a lane already converged.

    ``use_kernel`` is the tri-state Pallas switch (None = auto: kernels on
    TPU, jnp elsewhere — see ``repro.kernels.ops.resolve_use_kernel``);
    ``legacy_sweep`` selects the pre-fusion sweep chain (A/B baseline).
    """
    from repro.kernels.ops import resolve_use_kernel
    return _kmedoids_batched(D, valid, min(int(k), D.shape[-1]),
                             int(max_sweeps), resolve_use_kernel(use_kernel),
                             bool(legacy_sweep))


# ---------------------------------------------------------------------------
# distance-free solver: same BUILD+SWAP control flow, D never materialized
# ---------------------------------------------------------------------------

def _col_dists(xf: jnp.ndarray, sq: jnp.ndarray,
               idx: jnp.ndarray) -> jnp.ndarray:
    """(C, M) distances of every row to column idx[c], rebuilt from feats.

    Exact zero pinned at the self index (the ``zero_self_diag`` contract,
    one column at a time)."""
    m = xf.shape[1]
    xc = jnp.take_along_axis(xf, idx[:, None, None], axis=1)   # (C, 1, F)
    sqc = jnp.take_along_axis(sq, idx[:, None], axis=1)        # (C, 1)
    d2 = sq + sqc - 2.0 * jnp.sum(xf * xc, axis=-1)
    d = jnp.sqrt(jnp.maximum(d2, 0.0))
    return jnp.where(jnp.arange(m, dtype=jnp.int32)[None] == idx[:, None],
                     0.0, d)


def _medoid_dists(xf: jnp.ndarray, sq: jnp.ndarray,
                  medoids: jnp.ndarray) -> jnp.ndarray:
    """(C, M, k) distances to the current medoid set, rebuilt from feats."""
    m = xf.shape[1]
    xm = jnp.take_along_axis(xf, medoids[:, :, None], axis=1)  # (C, k, F)
    sqm = jnp.take_along_axis(sq, medoids, axis=1)             # (C, k)
    d2 = (sq[..., None] + sqm[:, None, :]
          - 2.0 * jnp.einsum("cmf,ckf->cmk", xf, xm, precision=F32))
    d = jnp.sqrt(jnp.maximum(d2, 0.0))
    self_mask = (jnp.arange(m, dtype=jnp.int32)[None, :, None]
                 == medoids[:, None, :])
    return jnp.where(self_mask, 0.0, d)


@partial(jax.jit, static_argnames=("k", "max_sweeps", "use_kernel"))
def _kmedoids_batched_from_feats(feats: jnp.ndarray, valid: jnp.ndarray,
                                 k: int, max_sweeps: int,
                                 use_kernel: bool) -> KMedoidsResult:
    from repro.kernels.ops import (kmedoids_build_cost_from_feats,
                                   kmedoids_delta_sweep_from_feats)

    xf = feats.astype(jnp.float32)
    c, m = xf.shape[0], xf.shape[1]
    sq = jnp.sum(xf * xf, axis=-1)          # (C, M) squared norms, once
    vf = valid.astype(jnp.float32)
    invalid = ~valid.astype(bool)
    iota_m = jnp.arange(m, dtype=jnp.int32)

    # ---- BUILD: identical greedy to _kmedoids_batched; the add-cost
    # reduction consumes feature tiles and the per-pick d_near update is a
    # single rebuilt column — never a (C, M, M) stack.
    def add_cost(d_near):
        return kmedoids_build_cost_from_feats(xf, d_near, vf,
                                              use_kernel=use_kernel)

    def build_step(carry, _):
        d_near, chosen = carry
        cost = jnp.where(chosen | invalid, BIG, add_cost(d_near))
        nxt = _argmin_tied(cost)
        d_near = jnp.minimum(d_near, _col_dists(xf, sq, nxt))
        chosen = chosen | (iota_m[None] == nxt[:, None])
        return (d_near, chosen), nxt

    with jax.named_scope("build"):
        cost0 = jnp.where(invalid, BIG, add_cost(jnp.full((c, m), BIG,
                                                          jnp.float32)))
        first = _argmin_tied(cost0)                                # (C,)
        d_near0 = _col_dists(xf, sq, first)
        mask0 = iota_m[None] == first[:, None]
        if k > 1:
            (_, _), rest = jax.lax.scan(build_step, (d_near0, mask0), None,
                                        length=k - 1)
            medoids0 = jnp.concatenate([first[:, None], rest.T], axis=1)
        else:
            medoids0 = first[:, None]

    # ---- SWAP: d1/d2/n(i) come from the O(C·M·k) medoid-distance slab;
    # the Δ reductions stream feature tiles.
    def sweep(state):
        medoids, _, it, improved = state
        dm = _medoid_dists(xf, sq, medoids)                       # (C, M, k)
        d1 = jnp.min(dm, axis=-1)
        n_idx = jnp.argmin(dm, axis=-1).astype(jnp.int32)
        n_onehot = (jnp.arange(k, dtype=jnp.int32)[None, None]
                    == n_idx[..., None])
        d2 = jnp.min(jnp.where(n_onehot, BIG, dm), axis=-1)
        A, B = kmedoids_delta_sweep_from_feats(xf, d1, d2, vf,
                                               n_onehot.astype(jnp.float32),
                                               use_kernel=use_kernel)
        delta = A[..., None] + B                                  # (C, M, k)
        is_medoid = (iota_m[None, :, None] == medoids[:, None, :]).any(-1)
        delta = jnp.where((is_medoid | invalid)[..., None], BIG, delta)
        medoids, improves = _pick_swap(delta, medoids,
                                       jnp.sum(d1 * vf, axis=1))
        return (medoids, improves, it + 1,
                improved + improves.astype(jnp.int32))

    def cond(state):
        _, improves, it, _ = state
        return jnp.any(improves) & (it < max_sweeps)

    state = (medoids0.astype(jnp.int32), jnp.ones((c,), bool),
             jnp.asarray(0, jnp.int32), jnp.zeros((c,), jnp.int32))
    with jax.named_scope("swap"):
        medoids, _, sweeps, improving = jax.lax.while_loop(cond, sweep,
                                                           state)

    dm = _medoid_dists(xf, sq, medoids)
    assignment = jnp.where(valid, jnp.argmin(dm, axis=-1),
                           -1).astype(jnp.int32)
    weights = jnp.sum(jax.nn.one_hot(assignment, k, dtype=jnp.int32), axis=1)
    objective = jnp.sum(jnp.min(dm, axis=-1) * vf, axis=1)
    return KMedoidsResult(medoids.astype(jnp.int32), assignment, weights,
                          objective, sweeps, improving)


def kmedoids_batched_from_feats(feats: jnp.ndarray, valid: jnp.ndarray,
                                k: int, max_sweeps: int = 50,
                                use_kernel: Optional[bool] = None
                                ) -> KMedoidsResult:
    """Distance-free twin of :func:`kmedoids_batched`.

    feats: (C, M, F) per-client feature stack; valid: (C, M) masks.  Same
    BUILD+SWAP control flow and masking contract, but the (C, M, M)
    distance stack is never materialized: the BUILD add-cost and Δ-sweep
    reductions consume feature tiles (Pallas kernels or the chunked jnp
    fallback under the tri-state ``use_kernel``), and the only per-round
    distance tensors are O(C·M) columns and the O(C·M·k) medoid slab.
    Peak selection memory drops from O(C·M²) to O(C·M·(F + k)) — per-
    client M in the thousands instead of hundreds.

    Padded lanes (valid False) carry zero feature rows, which are
    mutually at distance 0; the from-feats reductions mask those
    candidates to +BIG **in-kernel** so they can never tie-win a medoid
    election over a valid point.
    """
    from repro.kernels.ops import resolve_use_kernel
    return _kmedoids_batched_from_feats(feats, valid,
                                        min(int(k), feats.shape[1]),
                                        int(max_sweeps),
                                        resolve_use_kernel(use_kernel))


def kmedoids_masked(D: jnp.ndarray, valid: jnp.ndarray, k: int,
                    max_sweeps: int = 50,
                    use_kernel: Optional[bool] = None) -> KMedoidsResult:
    """Masked solve of a single *padded* instance — the C = 1 view of
    ``kmedoids_batched`` (one solver, one copy of the Δ-table math)."""
    res = kmedoids_batched(D[None], valid[None], k, max_sweeps, use_kernel)
    return KMedoidsResult(res.medoids[0], res.assignment[0], res.weights[0],
                          res.objective[0], res.sweeps, res.improving[0])


def kmedoids_jax(D: jnp.ndarray, k: int, max_sweeps: int = 50,
                 use_kernel: Optional[bool] = None) -> KMedoidsResult:
    """On-device BUILD+SWAP on an unpadded instance — the all-valid special
    case of ``kmedoids_masked`` (an all-True mask multiplies every
    reduction by exactly 1.0, so results are bitwise those of an unmasked
    implementation)."""
    return kmedoids_masked(D, jnp.ones((D.shape[0],), bool), k,
                           max_sweeps=max_sweeps, use_kernel=use_kernel)


def pairwise_sq_dists(x: jnp.ndarray,
                      use_kernel: Optional[bool] = None) -> jnp.ndarray:
    """(m, d) -> (m, m) squared Euclidean distances.

    ``use_kernel`` is the tri-state Pallas switch: True routes through the
    MXU-tiled kernel (``repro.kernels.ops.pairwise_l2``), False the jnp
    formulation (identical math, any backend), None auto-selects by
    backend.  Either way the self-distance diagonal is pinned to exact
    zeros by the shared ``zero_self_diag`` epilogue the pairwise wrappers
    own.
    """
    from repro.kernels.ops import (pairwise_l2, resolve_use_kernel,
                                   zero_self_diag)
    if resolve_use_kernel(use_kernel):
        return pairwise_l2(x, squared=True, zero_diag=True)
    sq = jnp.sum(jnp.square(x), axis=-1)
    d = jnp.maximum(sq[:, None] + sq[None, :]
                    - 2.0 * jnp.matmul(x, x.T, precision=F32), 0.0)
    return zero_self_diag(d)
