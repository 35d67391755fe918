"""Durability: time inside ``os.fsync`` during a save (the commit's two
fsyncs), per save in the window; part of ``write_s``."""


def read(run):
    return sum(s.fsync_s for s in run.saves) / len(run.saves) if run.saves else None
