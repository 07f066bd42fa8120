"""Device time per round of the Pallas selection kernels: the summed
durations of their events in the trace (``kernels/*.py`` name them)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_rounds"]:
        return None
    total = sum(d for kern in ctx["kernels"] if kern.SELECTION
                for _, _, d in tr.events_matching(kern.MATCH))
    if total <= 0:
        return None
    return 1e-6 * total / ctx["trace_rounds"]
