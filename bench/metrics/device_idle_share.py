"""Device: one minus the union of device operation intervals over the
traced window."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
