"""Mean wall time per round of the program's ``cohort_build`` span: the
host pad-and-stack of the whole cohort, every round (fleet round loop)."""


def read(ctx):
    spans = [s["dur"] for s in ctx["spans"] if s["name"] == "cohort_build"]
    if not spans:
        return None
    return 1e3 * sum(spans) / ctx["rounds"]
