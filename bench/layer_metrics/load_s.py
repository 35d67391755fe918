"""Device load: a restore's time less its container read (host transposes
and uploads until the fields are ready), per restore in the window."""


def read(run):
    return sum(r.resume_s - r.read_s for r in run.restores) / len(run.restores) if run.restores else None
