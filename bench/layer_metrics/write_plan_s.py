"""Writer and container, planning: host time inside the program's
``ckpt.plan`` span (each leaf made C-ordered, a copy where it was staged in
another order, and its extent and row plan), per save in the traced
window."""

from bench import program_spans


def read(run):
    return program_spans.per_save(run, "ckpt.plan")
