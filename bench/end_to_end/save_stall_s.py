"""Time the loop spent inside its save calls in the window, over the saves
in it."""


def read(run):
    return sum(s.stall_s for s in run.saves) / len(run.saves) if run.saves else None
