"""Staging, the topology: host time inside the program's ``sim.topology``
span (``topology_arrays``, a Python loop over every d-grid), per save in the
traced window."""

from bench import program_spans


def read(run):
    return program_spans.per_save(run, "sim.topology")
