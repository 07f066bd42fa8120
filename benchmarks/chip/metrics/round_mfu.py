"""Whole-round model FLOP utilization over the window: model operations
on real samples (forward+backward per sample visit, plus the stragglers'
forward feature pass) per second, over the chip's bf16 peak.  Selection
work is not model work and does not count."""


def read(ctx):
    pk = ctx["peaks"]
    if pk is None or ctx["window_s"] <= 0:
        return None
    rate = ctx["round_flops"] * ctx["rounds"] / ctx["window_s"]
    return 100.0 * rate / pk["bf16_flops"]
