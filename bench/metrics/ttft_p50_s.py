"""Median over requests due in the window of first-token time minus due
time; a request with no first token by the close enters at its wait."""
from bench import timeline


def read(run):
    return timeline.percentile(timeline.ttfts(run), 50)
