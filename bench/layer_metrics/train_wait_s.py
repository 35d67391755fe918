"""Async staging, the queue: host time inside the program's ``ckpt.wait``
span (a save joining the write still in flight from the save before), per
save in the traced window."""

from bench import program_spans


def read(run):
    return program_spans.per_save(run, "ckpt.wait")
