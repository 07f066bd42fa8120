"""``delta_sweep_pallas``: one FasterPAM Δ-sweep from a materialized
(C, M, M) distance stack.

Operations: contribᵀ·onehot (2·C·M²·K) and about 8 elementwise
operations per (i, j).  Bytes: D once (C·M²), d1/d2/mask 3·C·M, onehot
C·M·K, A C·M and B C·M·K, all float32."""

NAME = "delta_sweep"
MATCH = r"^%kmedoids_delta_sweep[.\d]* = "
SELECTION = True


def flops(c: int, m: int, f: int, k: int) -> float:
    return 2.0 * c * m * m * k + 8.0 * c * m * m


def bytes_moved(c: int, m: int, f: int, k: int) -> float:
    return 4.0 * (c * m * m + 4 * c * m + 2 * c * m * k)


def call_shape(operands) -> dict:
    c, m, _ = operands[0]
    return {"c": c, "m": m, "f": 0, "k": operands[-1][-1]}
