"""Front-end: mean share of slots holding a request during the window's
steps (telemetry ``step`` events, plus the requests each step finished)."""
from bench import timeline


def read(run):
    steps = set(timeline.window_steps(run))
    if not steps:
        return None
    busy = [len(timeline.live_lens(run, s)) for s in sorted(steps)]
    return 100.0 * sum(busy) / (len(busy) * run.slots)
