"""Front-end: JAX compile time inside the window's steps (``compile_ms``
of the program's ``step`` events, summed). It should read 0; where it
does not, those events' ``compile_sites_ms`` name the span and program
that compiled."""
from bench import timeline


def read(run):
    steps = set(timeline.window_steps(run))
    ms = [e["compile_ms"] for e in run.events
          if e["event"] == "step" and e["step"] in steps
          and "compile_ms" in e]
    return sum(ms) / 1e3 if ms else None
