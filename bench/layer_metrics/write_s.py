"""Writer and container: the writer's own timer (``SaveResult.wall_s``,
first dataset created to the commit's fsyncs), per save in the window."""


def read(run):
    return sum(s.write_s for s in run.saves) / len(run.saves) if run.saves else None
