"""Front-end: mean over the window's steps of the host time of a serve
step outside its wait on the device (``step_ms - wait_ms`` of the
program's ``step`` events). A mean, so a stall counts."""
from bench import timeline


def read(run):
    steps = set(timeline.window_steps(run))
    host = [e["step_ms"] - e["wait_ms"] for e in run.events
            if e["event"] == "step" and e["step"] in steps and "wait_ms" in e]
    return sum(host) / len(host) if host else None
