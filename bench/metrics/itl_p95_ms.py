"""95th percentile of every gap between consecutive output tokens that
reached the host in the window, open gaps at the close included."""
from bench import timeline


def read(run):
    p = timeline.percentile(timeline.itl_gaps(run), 95)
    return None if p is None else p * 1e3
