"""KV state: host time of the eager page programs (``pages_ms`` of the
program's ``step`` events: page-table rows set, pages zeroed or copied on
admit, finish and prefix eviction), summed over the window's steps and
divided by their number."""
from bench import timeline


def read(run):
    steps = set(timeline.window_steps(run))
    ms = [e["pages_ms"] for e in run.events
          if e["event"] == "step" and e["step"] in steps and "pages_ms" in e]
    return sum(ms) / len(ms) if ms else None
