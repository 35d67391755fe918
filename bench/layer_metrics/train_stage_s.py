"""Async staging: host time inside the program's ``ckpt.stage`` span
(``AsyncCheckpointer`` copying every leaf of the device state to the host,
a sharded leaf gathered into one buffer), per save in the traced window."""

from bench import program_spans


def read(run):
    return program_spans.per_save(run, "ckpt.stage")
