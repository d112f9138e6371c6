"""Model step: device busy time per serve step, from the trace, over the
executions of the serve-step program inside the window."""


def read(run):
    t = run.trace
    if t is None or not t.steps:
        return None
    return sum(b for _, _, b in t.steps) / len(t.steps) / 1e6
