"""Solver or trainer step: device busy time inside the ``step`` spans of the
traced window, over the steps they ran."""


def read(run):
    spans = run.trace.spans.get("step") if run.trace else None
    return spans.busy_s / run.steps if spans and run.steps else None
