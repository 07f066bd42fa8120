"""Roofline share of the Pallas selection kernels: Σ over their calls of
the least time the chip could take (operations over the bf16 peak or
bytes over HBM bandwidth, whichever is longer) over their summed device
time.  Their contractions run at float32 (HIGHEST) precision, for which
no peak is published; against the bf16 peak the share reads low."""


def read(ctx):
    tr, pk = ctx["trace"], ctx["peaks"]
    if tr is None or pk is None:
        return None
    ideal = busy = 0.0
    for kern in ctx["kernels"]:
        if not kern.SELECTION:
            continue
        for call in ctx["kernel_calls"](kern):
            shape, dur = call
            ideal += max(kern.flops(**shape) / pk["bf16_flops"],
                         kern.bytes_moved(**shape) / pk["hbm_bytes_per_s"])
            busy += dur * 1e-9
    if busy <= 0:
        return None
    return 100.0 * ideal / busy
