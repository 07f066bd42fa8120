"""Share of the lane sweeps that ran on a lane already converged: every
lane of a coreset group runs as many swap sweeps as its slowest lane.
100 · (1 − Σ ``lane_sweeps_improving`` / Σ ``lane_sweeps_run``) over the
window's ``gather`` spans."""


def read(ctx):
    counts = [s["attrs"] for s in ctx["spans"]
              if s["name"] == "gather" and "lane_sweeps_run" in s["attrs"]]
    run = sum(c["lane_sweeps_run"] for c in counts)
    if not run:
        return None
    return 100.0 * (1.0 - sum(c["lane_sweeps_improving"]
                              for c in counts) / run)
