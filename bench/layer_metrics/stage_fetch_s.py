"""Staging, the device-to-host copies: host time inside the program's
``sim.fetch`` spans (the step counter, the four fields, the cell types),
per save in the traced window."""

from bench import program_spans


def read(run):
    return program_spans.per_save(run, "sim.fetch")
