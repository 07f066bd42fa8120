"""FasterPAM swap sweeps per round: Σ over the window's ``gather`` spans
of their ``swap_sweeps`` attribute (each coreset group's sweep count,
summed over groups) over the window's rounds."""


def read(ctx):
    sweeps = [s["attrs"]["swap_sweeps"] for s in ctx["spans"]
              if s["name"] == "gather" and "swap_sweeps" in s["attrs"]]
    if not sweeps:
        return None
    return sum(sweeps) / ctx["rounds"]
