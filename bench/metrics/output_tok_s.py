"""All output tokens that reached the host in the window, over the
window's seconds."""
from bench import timeline


def read(run):
    return timeline.window_tokens(run) / (run.w1 - run.w0)
