"""Front-end: median over requests due in the window of due time to the
step that admitted it (host clock); one not admitted enters at its wait."""
from bench import timeline


def read(run):
    return timeline.percentile(timeline.queue_waits(run), 50)
