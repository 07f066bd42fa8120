"""``build_cost_from_feats_pallas``: the BUILD add-cost of every
candidate, distances rebuilt from the (C, M, F) feature stack.

cost[c, j] = Σ_i min(d_near[c, i], ‖x_ci − x_cj‖) · vf[c, i]
Operations: the cross term x·xᵀ (2·C·M²·F) and, per (i, j), the distance
epilogue and the fold (about 8 operations).  Bytes: the least the call
needs, each input read once and the output written once: features
C·M·F, norms, d_near and mask 3·C·M, cost C·M, all float32.  Counted
on the call's own shapes (M and F padded to the kernel's blocks)."""

NAME = "build_cost_from_feats"
# the TPU trace names the call after its wrapper in repro.kernels.ops
MATCH = r"^%kmedoids_build_cost_from_feats[.\d]* = "
SELECTION = True


def flops(c: int, m: int, f: int, k: int) -> float:
    return 2.0 * c * m * m * f + 8.0 * c * m * m


def bytes_moved(c: int, m: int, f: int, k: int) -> float:
    return 4.0 * (c * m * f + 4 * c * m)


def call_shape(operands) -> dict:
    """(C, M, F) features come first."""
    c, m, f = operands[0]
    return {"c": c, "m": m, "f": f, "k": 0}
