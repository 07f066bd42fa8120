"""``delta_sweep_from_feats_pallas``: one FasterPAM Δ-sweep, distances
rebuilt from the (C, M, F) feature stack.

A[c, j]    = Σ_i (min(d_ij, d1_i) − d1_i) · vf_i
B[c, j, l] = Σ_{i: n(i)=l} (clip(d_ij, d1_i, d2_i) − d1_i) · vf_i
Operations: the cross term (2·C·M²·F), contribᵀ·onehot (2·C·M²·K) and
about 12 elementwise operations per (i, j).  Bytes: each input once and
each output once: features C·M·F, norms/d1/d2/mask 5·C·M, onehot C·M·K,
A C·M and B C·M·K, all float32.  Counted on the call's own shapes (M, F
and K padded to the kernel's blocks)."""

NAME = "delta_sweep_from_feats"
MATCH = r"^%kmedoids_delta_sweep_from_feats[.\d]* = "
SELECTION = True


def flops(c: int, m: int, f: int, k: int) -> float:
    return 2.0 * c * m * m * f + 2.0 * c * m * m * k + 12.0 * c * m * m


def bytes_moved(c: int, m: int, f: int, k: int) -> float:
    return 4.0 * (c * m * f + 6 * c * m + 2 * c * m * k)


def call_shape(operands) -> dict:
    """(C, M, F) features first, the (C, M, K) nearest-medoid one-hot
    last."""
    c, m, f = operands[0]
    return {"c": c, "m": m, "f": f, "k": operands[-1][-1]}
