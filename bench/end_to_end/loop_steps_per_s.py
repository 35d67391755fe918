"""Steps completed in the window over the window's time, saves and their
stalls included: the job's real pace."""


def read(run):
    return run.steps / run.window_s if run.saves else None
