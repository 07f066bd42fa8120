"""Float64 judge of a k-medoids selection, and a plain FasterPAM.

A lane's medoid set is judged against FasterPAM's stopping rule: no
single swap may lower the lane's objective Σ_i min_s d(i, s) by more
than the solver's own threshold (SWAP_RTOL of the objective) plus the
error that computing in lower precision puts into the swap table.
That error is measured on the lane itself, so the judge adapts to the
lane's scale.

``fasterpam`` is a plain BUILD + best-swap solver, used only where the
reference stands in for the program (the lower-precision controls).
Its contractions can be rounded as a TPU rounds them below ``highest``:
``passes`` 1 takes one bfloat16 pass (``default``), 3 takes three
(``high``), each with float32 accumulation.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

BIG = 1e30
# the solver's stopping rule: a swap must gain more than this share of
# the lane's objective (the program's FasterPAM threshold)
SWAP_RTOL = 1e-5


def bf16_parts(a: np.ndarray, passes: int) -> list:
    """``a`` in float32 as bfloat16 terms: its rounding (one pass) or its
    rounding and the rounding of the rest (three passes)."""
    a = np.asarray(a, np.float32)
    hi = a.astype(ml_dtypes.bfloat16).astype(np.float32)
    if passes == 1:
        return [hi]
    return [hi, (a - hi).astype(ml_dtypes.bfloat16).astype(np.float32)]


def dists(x: np.ndarray, dtype, passes: int = 0) -> np.ndarray:
    """(M, M) Euclidean distances by the norm expansion the solvers use;
    with ``passes`` the norms stay float32 and x xᵀ is rounded."""
    x = np.asarray(x).astype(dtype)
    sq = (x * x).sum(-1)
    if passes:
        p = bf16_parts(x, passes)
        dot = p[0] @ p[0].T
        if passes == 3:
            dot = dot + p[0] @ p[1].T + p[1] @ p[0].T
    else:
        dot = x @ x.T
    d = np.sqrt(np.maximum(sq[:, None] + sq[None, :] - 2.0 * dot, 0.0))
    np.fill_diagonal(d, 0.0)
    return d


def swap_table(D: np.ndarray, medoids: np.ndarray, passes: int = 0):
    """FasterPAM's swap table in float64 and the objective.

    ``delta[j, l]`` is the change of Σ_i min_s D[i, s] when medoid slot
    ``l`` is replaced by point ``j`` (BIG where ``j`` is a medoid); with
    ``passes`` the contraction over points is rounded."""
    D = np.asarray(D, np.float64)
    m, k = D.shape[0], len(medoids)
    dm = D[:, medoids]
    order = np.argsort(dm, axis=1, kind="stable")
    n_idx = order[:, 0]
    d1 = dm[np.arange(m), n_idx]
    d2 = dm[np.arange(m), order[:, 1]] if k > 1 else np.full(m, BIG)
    shift = np.minimum(D - d1[:, None], 0.0)
    A = shift.sum(axis=0)
    contrib = np.minimum(D, d2[:, None]) - d1[:, None] - shift
    # B[j, l] = Σ_{i: n(i)=l} contrib[i, j]
    onehot = (n_idx[:, None] == np.arange(k)[None, :]).astype(np.float64)
    if passes:
        B = sum(p.T @ onehot.astype(np.float32)
                for p in bf16_parts(contrib, passes)).astype(np.float64)
    else:
        B = contrib.T @ onehot
    delta = A[:, None] + B
    delta[medoids, :] = BIG
    return delta, float(d1.sum())


def lane_gain(x: np.ndarray, medoids: np.ndarray, x_low=None,
              parts: bool = False):
    """(best swap gain, allowed gain, objective) of one lane in float64;
    with ``parts`` also the float32 and stated-precision table errors.

    ``x`` holds the lane's valid rows at full precision.  The allowance is
    SWAP_RTOL·objective plus twice the swap table's error from float32
    distances, and, where ``x_low`` (the same rows computed at the
    configuration's stated precision) is given, plus twice the table's
    error from those features."""
    medoids = np.asarray(medoids)
    delta, obj = swap_table(dists(x, np.float64), medoids)
    free = delta < BIG / 2
    if not free.any():
        out = (0.0, SWAP_RTOL * obj, obj)
        return out + (0.0, 0.0) if parts else out
    err32 = float(np.abs(swap_table(dists(x, np.float32), medoids)[0]
                         - delta)[free].max())
    err_low = 0.0
    if x_low is not None:
        err_low = float(np.abs(swap_table(dists(x_low, np.float64),
                                          medoids)[0] - delta)[free].max())
    gain = max(0.0, -float(delta[free].min()))
    out = (gain, SWAP_RTOL * obj + 2.0 * (err32 + err_low), obj)
    return out + (err32, err_low) if parts else out


def assign(x: np.ndarray, medoids: np.ndarray) -> np.ndarray:
    """Cluster sizes of the medoids over the rows of ``x`` (float64,
    nearest medoid, ties to the lowest slot)."""
    x = np.asarray(x, np.float64)
    xm = x[np.asarray(medoids)]
    d2 = ((x * x).sum(-1)[:, None] + (xm * xm).sum(-1)[None, :]
          - 2.0 * x @ xm.T)
    d = np.sqrt(np.maximum(d2, 0.0))
    d[np.asarray(medoids), np.arange(len(medoids))] = 0.0
    return np.bincount(np.argmin(d, axis=1), minlength=len(medoids))


def fasterpam(x: np.ndarray, k: int, dtype=np.float32, passes: int = 0,
              max_sweeps: int = 1000) -> np.ndarray:
    """Greedy BUILD then best-swap search to a fixed point, all in
    ``dtype``, the contractions in ``passes``; returns k medoid indices
    into the rows of ``x``."""
    D = dists(x, dtype, passes).astype(np.float64)
    m = D.shape[0]
    k = min(k, m)
    near = np.full(m, BIG)
    med = []
    for _ in range(k):
        cost = np.minimum(near[:, None], D).sum(axis=0)
        cost[med] = BIG
        j = int(np.argmin(cost))
        med.append(j)
        near = np.minimum(near, D[:, j])
    med = np.array(med)
    for _ in range(max_sweeps):
        delta, obj = swap_table(D, med, passes)
        j, l = np.unravel_index(np.argmin(delta), delta.shape)
        if delta[j, l] >= -SWAP_RTOL * obj:
            break
        med[l] = j
    return med
