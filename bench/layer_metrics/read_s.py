"""Container read: time inside ``CheckpointManager.restore`` (every dataset
read and its CRC checked), per restore in the window."""


def read(run):
    return sum(r.read_s for r in run.restores) / len(run.restores) if run.restores else None
