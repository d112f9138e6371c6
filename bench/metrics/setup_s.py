"""Process start to window start: build, compile or cache load, warm-up
and the traffic fill."""


def read(run):
    return run.setup_s
