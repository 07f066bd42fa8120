"""``pairwise_l2_batched_pallas``: the (C, M, M) distance stack of the
selection path below ``materialize_below``.

Operations: the cross term 2·C·M²·F and about 4 epilogue operations per
(i, j).  Bytes: features once (C·M·F), norms 2·C·M, distances C·M²,
float32.  Counted on the call's own shapes (padded M and F)."""

NAME = "pairwise_l2_batched"
MATCH = r"^%pairwise_l2_batched[.\d]* = "
SELECTION = True


def flops(c: int, m: int, f: int, k: int) -> float:
    return 2.0 * c * m * m * f + 4.0 * c * m * m


def bytes_moved(c: int, m: int, f: int, k: int) -> float:
    return 4.0 * (c * m * f + 2 * c * m + c * m * m)


def call_shape(operands) -> dict:
    c, m, f = operands[0]
    return {"c": c, "m": m, "f": f, "k": 0}
