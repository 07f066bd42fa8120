"""Host wall time per round of the program's ``stage`` spans: the
host-to-device copies of each group program's data, masks and minibatch
indices (the parameters' lane broadcast, an eager dispatch that can wait
on a full device queue, runs outside the span)."""


def read(ctx):
    spans = [s["dur"] for s in ctx["spans"] if s["name"] == "stage"]
    if not spans:
        return None
    return 1e3 * sum(spans) / ctx["rounds"]
