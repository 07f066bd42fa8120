"""FedCore coreset construction (paper §3.2, §4.2, §4.3).

The coreset problem (Eq. 2) is upper-bounded (Eq. 3-4) and solved as a
k-medoids instance (Eq. 5) over *gradient features*:

  * convex models      -> input-space features  (d̃ⱼₖ = ‖xⱼ − xₖ‖)
  * deep networks      -> last-layer gradient features
                          (d̂ⱼₖ = ‖∂Lⱼ/∂zⱼ − ∂Lₖ/∂zₖ‖, §4.3)

The budget (§4.2): the first epoch of a round runs the full set (mⁱ samples,
producing the features); the remaining E−1 epochs run the coreset, so

    bⁱ = ⌊(cⁱ·τ − mⁱ) / (E − 1)⌋.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kmedoids import (KMedoidsResult, kmedoids_batched,
                                 kmedoids_batched_from_feats, kmedoids_jax,
                                 kmedoids_numpy, pairwise_sq_dists)


class Coreset(NamedTuple):
    indices: jnp.ndarray   # (k,) int32 — selected sample indices Sⁱ
    weights: jnp.ndarray   # (k,) float32 — δⁱ (cluster sizes)
    objective: jnp.ndarray  # scalar — the Eq.(5) k-medoids objective
    assignment: jnp.ndarray  # (m,) int32 — Φⁱ mapping (by medoid slot)
    # the on-device solver's swap sweeps and, per lane, those that still
    # swapped (``KMedoidsResult.sweeps`` / ``improving``; None otherwise)
    sweeps: Optional[jnp.ndarray] = None
    improving: Optional[jnp.ndarray] = None


def coreset_budget(m: int, capability: float, deadline: float,
                   epochs: int, cost=None) -> int:
    """bⁱ = ⌊(cⁱτ − mⁱ·κ)/(κ(E−1))⌋ clipped to [1, mⁱ] (paper §4.2).

    ``cost`` is an optional ``repro.fed.cost.WorkloadCostModel`` (or a
    per-sample cost scalar): the deadline buys cⁱτ *cost units*, of which
    each sample-visit consumes κ.  ``cost=None`` is the legacy
    samples-cost-1.0 mode — byte-identical to the pre-cost formula.
    The arithmetic itself lives in ``repro.fed.cost`` (imported lazily:
    ``repro.fed`` imports this module at package-init time).
    """
    from repro.fed.cost import resolve_cost
    return resolve_cost(cost).budget(m, capability, deadline, epochs)


def needs_coreset(m: int, capability: float, deadline: float,
                  epochs: int, cost=None) -> bool:
    """Alg. 1 line 6: full-set training iff E·mⁱ·κ ≤ cⁱτ (see
    ``coreset_budget`` for the ``cost`` parameter)."""
    from repro.fed.cost import resolve_cost
    return resolve_cost(cost).needs_coreset(m, capability, deadline, epochs)


def build_coreset(features: jnp.ndarray, budget: int, *,
                  backend: str = "jax", use_kernel: Optional[bool] = None,
                  max_sweeps: int = 50,
                  projection_dim: Optional[int] = None) -> Coreset:
    """Solve Eq.(5) on the given per-sample feature matrix (m, F).

    Distances are Euclidean in feature space — exactly d̃ (input features) or
    d̂ (last-layer gradient features) depending on what the caller passes.
    ``use_kernel`` is the tri-state Pallas switch (None = auto: kernels on
    supported backends, jnp fallback otherwise) for both the pairwise
    distances and the fused k-medoids reductions.  ``projection_dim``
    applies a JL random projection first (§Perf H3).
    """
    m = features.shape[0]
    budget = min(budget, m)
    if projection_dim is not None:
        from repro.core.gradients import project_features
        features = project_features(features, projection_dim)
    D2 = pairwise_sq_dists(features, use_kernel=use_kernel)
    D = jnp.sqrt(jnp.maximum(D2, 0.0))
    if backend == "numpy":
        res = kmedoids_numpy(np.asarray(D), budget, max_sweeps=max_sweeps)
    else:
        res = kmedoids_jax(D, budget, max_sweeps=max_sweeps,
                           use_kernel=use_kernel)
    return Coreset(indices=res.medoids,
                   weights=res.weights.astype(jnp.float32),
                   objective=res.objective,
                   assignment=res.assignment)


def build_coreset_batched(features: jnp.ndarray, valid: jnp.ndarray,
                          budget: int, *, use_kernel: Optional[bool] = None,
                          max_sweeps: int = 50,
                          distance_free: bool = True,
                          materialize_below: int = 256) -> Coreset:
    """One coreset per client over a padded cohort stack (fleet engine).

    features: (C, M, F) per-client gradient features, rows with
    ``valid[c, i]`` False being padding; ``budget`` is the static per-client
    k (clients are grouped by quantized budget upstream).  Returns a
    ``Coreset`` of stacked fields — indices (C, k), weights (C, k), etc.
    Each lane solves exactly the instance ``build_coreset`` would solve on
    that client's unpadded features.  ``use_kernel`` (tri-state, None =
    auto by backend) routes the distance/reduction math through the Pallas
    kernels.

    ``distance_free`` (default on) solves straight from the feature stack
    — the (C, M, M) distance tensor is never materialized, so peak
    selection memory is O(C·M·F) instead of O(C·M²) and per-client M
    scales to the thousands.  ``distance_free=False`` keeps the
    materializing pairwise + D-input solver as the measured A/B baseline
    (``benchmarks/fleet_sweep.py --selection-memory``).

    ``materialize_below`` is the adaptive cutover: below it the (C, M, M)
    stack is a few MB and recomputing distances every BUILD step /
    Δ-sweep costs more than it saves (streaming trades O(k·C·M²·F)
    recompute FLOPs for O(C·M²) memory), so ``distance_free=True``
    materializes anyway — selection at typical fleet M is bit-identical
    to the D-input path.  At ``M >= materialize_below`` it streams.
    Pass ``materialize_below=0`` to force streaming at any size (the
    parity tests do).
    """
    from repro.kernels.ops import pairwise_l2_batched, resolve_use_kernel
    c, m, _ = features.shape
    budget = min(budget, m)
    uk = resolve_use_kernel(use_kernel)
    if distance_free and m >= materialize_below:
        # padded rows must be zero features: mutually-zero distances are
        # masked in-kernel (+BIG candidates), valid rows are untouched
        feats = features * valid.astype(features.dtype)[..., None]
        res = kmedoids_batched_from_feats(feats, valid, budget,
                                          max_sweeps=max_sweeps,
                                          use_kernel=uk)
    else:
        # zero_diag: the pairwise wrappers own the self-distance fix-up
        D = pairwise_l2_batched(features, squared=False, use_kernel=uk,
                                zero_diag=True)
        res = kmedoids_batched(D, valid, budget, max_sweeps=max_sweeps,
                               use_kernel=uk)
    return Coreset(indices=res.medoids,
                   weights=res.weights.astype(jnp.float32),
                   objective=res.objective,
                   assignment=res.assignment,
                   sweeps=res.sweeps, improving=res.improving)


def coreset_epsilon(grads_full: jnp.ndarray, coreset: Coreset) -> jnp.ndarray:
    """Audit Assumption A.3 on *true* per-sample gradients.

    grads_full: (m, P) matrix of per-sample gradients (flattened).
    Returns ε = (1/m)‖Σⱼ gⱼ − Σₖ δₖ g_{medoid k}‖₂.
    """
    m = grads_full.shape[0]
    full = jnp.sum(grads_full, axis=0)
    sel = grads_full[coreset.indices]
    approx = jnp.sum(sel * coreset.weights[:, None], axis=0)
    return jnp.linalg.norm(full - approx) / m


def coreset_batch(data: dict, coreset: Coreset, m_full: int) -> dict:
    """Materialize the weighted coreset training set from a client dataset.

    Weights are δₖ·(k not dropped)/mⁱ-normalized implicitly by the weighted
    loss (which divides by Σw), matching Eq.(9)'s (1/mⁱ)Σδₖ∇Lₖ since
    Σₖ δₖ = mⁱ.
    """
    idx = np.asarray(coreset.indices)
    out = {k: v[idx] for k, v in data.items() if k != "weights"}
    out["weights"] = jnp.asarray(coreset.weights, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# configuration record for the FL runtime
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FedCoreConfig:
    epochs: int = 10             # E
    deadline: Optional[float] = None  # τ (seconds); None = no deadline
    backend: str = "jax"         # kmedoids solver
    # tri-state Pallas switch: None = auto (kernels on supported backends,
    # jnp fallback otherwise); True/False force on/off
    use_kernel: Optional[bool] = None
    max_sweeps: int = 50
    refresh_every_round: bool = True  # paper: re-select each round
    projection_dim: Optional[int] = None  # JL projection (§Perf H3)
    # Alg. 1 drop path for clients that cannot meet τ even with the §4.4
    # minimal plan (coreset of 1, one partial epoch).  Default False:
    # train the minimal plan and mark ClientResult.deadline_violated.
    drop_infeasible: bool = False
