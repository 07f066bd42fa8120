"""``build_cost_pallas``: the BUILD add-cost from a materialized
(C, M, M) distance stack.

cost[c, j] = Σ_i min(d_near[c, i], D[c, i, j]) · vf[c, i]
Operations: min, multiply and add per (i, j), 3·C·M².  Bytes: D once
(C·M²), d_near and mask 2·C·M, cost C·M, all float32."""

NAME = "build_cost"
MATCH = r"^%kmedoids_build_cost[.\d]* = "
SELECTION = True


def flops(c: int, m: int, f: int, k: int) -> float:
    return 3.0 * c * m * m


def bytes_moved(c: int, m: int, f: int, k: int) -> float:
    return 4.0 * (c * m * m + 3 * c * m)


def call_shape(operands) -> dict:
    c, m, _ = operands[0]
    return {"c": c, "m": m, "f": 0, "k": 0}
