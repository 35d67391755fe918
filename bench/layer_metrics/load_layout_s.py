"""Device load, the host layout: host time inside the program's
``sim.layout`` spans (each field's blocked-to-composite transpose), per
restore in the traced window."""

from bench import program_spans


def read(run):
    return program_spans.per_restore(run, "sim.layout")
