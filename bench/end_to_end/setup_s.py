"""Process start to the first timed step: imports, the state built on the
device, warm-up, and on the first run in a checkout the compiles."""


def read(run):
    return run.setup_s
