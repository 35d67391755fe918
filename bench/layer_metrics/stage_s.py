"""Staging: a synchronous save's time less the writer's own timer for the
same save (device-to-host copies and packing), per save in the window."""


def read(run):
    return sum(s.stall_s - s.write_s for s in run.saves) / len(run.saves) if run.saves else None
