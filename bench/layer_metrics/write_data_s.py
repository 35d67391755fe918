"""Writer and container, the data: host time inside the program's
``ckpt.write`` span (the pwrites of the state leaves), per save in the
traced window."""

from bench import program_spans


def read(run):
    return program_spans.per_save(run, "ckpt.write")
