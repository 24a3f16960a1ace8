"""Share of the traced window in which no op ran on the device."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
