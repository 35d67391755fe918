"""Share of the traced window, whole periods of the cell's loop, in which no
operation ran on the device.  Read for ``device_idle.<split>``, the split
named by the end-to-end metric that the cell reports."""


def read(run):
    return run.trace.idle_pct if run.trace else None
