"""Writer and container, the seal: host time inside the program's
``ckpt.seal`` span (each leaf read back from the file and its CRC32
computed), per save in the traced window."""

from bench import program_spans


def read(run):
    return program_spans.per_save(run, "ckpt.seal")
