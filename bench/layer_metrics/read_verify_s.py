"""Container read, the integrity check: host time inside the program's
``th5.verify`` spans (each dataset's CRC32), summed over the datasets, per
restore in the traced window."""

from bench import program_spans


def read(run):
    return program_spans.per_restore(run, "th5.verify")
