"""Time from calling a restore to its fields being ready on the device,
summed over the restores in the window and divided by their number."""


def read(run):
    return sum(r.resume_s for r in run.restores) / len(run.restores) if run.restores else None
