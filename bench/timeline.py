"""What happened in a run, on the host clock, from the harness's records.

The harness records, for every call of the front-end's ``step``, its
start and end on the host clock, and keeps the front-end's telemetry
events (``admit``, ``first_token``, ``finish``, ``step``). From those two
streams this module rebuilds when each request was admitted and when each
of its tokens reached the host: a request that has produced its first
token produces one more in every later step until its ``finish`` event.

Times are seconds on ``time.perf_counter``. The window is ``[w0, w1)``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Run:
    steps: dict                 # step number -> (t0, t1)
    events: list                # the front-end's telemetry events
    due: dict                   # rid -> due time
    w0: float
    w1: float
    slots: int
    allocatable: int            # pages a request can be given
    model: dict                 # the configuration file
    peak: Optional[dict] = None
    setup_s: Optional[float] = None
    trace: Optional[object] = None   # bench.trace_reduce.Summary
    requests: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self.requests = request_table(self.events, self.steps)


def request_table(events: list, steps: dict) -> dict:
    """rid -> {admit, first, finish, n_pages, token_steps}."""
    last = max(steps) if steps else -1
    out: dict = {}
    for e in events:
        kind = e["event"]
        if kind == "admit":
            out.setdefault(e["rid"], {})["admit"] = e["step"]
            out[e["rid"]]["n_pages"] = e["n_pages"]
        elif kind == "first_token":
            out.setdefault(e["rid"], {})["first"] = e["step"]
        elif kind == "finish":
            out.setdefault(e["rid"], {})["finish"] = e["step"]
    for r in out.values():
        if "first" in r:
            end = r.get("finish", last)
            r["token_steps"] = list(range(r["first"], end + 1))
        else:
            r["token_steps"] = []
    return out


def token_times(run: Run, rid) -> list:
    r = run.requests.get(rid)
    if r is None:
        return []
    return [run.steps[s][1] for s in r["token_steps"] if s in run.steps]


def in_window(run: Run, t: float) -> bool:
    return run.w0 <= t < run.w1


def window_rids(run: Run) -> list:
    """Requests due in the window."""
    return sorted(r for r, t in run.due.items() if in_window(run, t))


def percentile(xs, q: float) -> Optional[float]:
    """Linear-interpolated percentile; None for no samples."""
    if len(xs) == 0:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


def ttfts(run: Run) -> list:
    """First-token time minus due time of each request due in the window;
    one with no first token by the window's close enters at its wait."""
    out = []
    for rid in window_rids(run):
        tt = token_times(run, rid)
        first = tt[0] if tt and tt[0] < run.w1 else run.w1
        out.append(first - run.due[rid])
    return out


def itl_gaps(run: Run) -> list:
    """Every gap between consecutive tokens of a request whose later token
    reached the host in the window; a request still decoding at the close
    adds its open gap, so a stall cannot drop out."""
    out = []
    for rid, r in run.requests.items():
        tt = [t for t in token_times(run, rid) if t < run.w1]
        for a, b in zip(tt, tt[1:]):
            if b >= run.w0:
                out.append(b - a)
        done = "finish" in r and run.steps.get(r["finish"], (0, run.w1))[1] \
            < run.w1
        if tt and not done:
            out.append(run.w1 - tt[-1])
    return out


def window_tokens(run: Run) -> int:
    return sum(1 for rid in run.requests for t in token_times(run, rid)
               if in_window(run, t))


def window_steps(run: Run) -> list:
    """Step numbers that started and ended inside the window."""
    return sorted(s for s, (t0, t1) in run.steps.items()
                  if t0 >= run.w0 and t1 < run.w1)


def live_lens(run: Run, step: int) -> list:
    """Tokens each live slot's cache holds after ``step`` writes its own."""
    out = []
    for r in run.requests.values():
        if "admit" not in r or r["admit"] > step:
            continue
        if r.get("finish", step) < step:
            continue
        out.append(step - r["admit"] + 1)
    return out


def queue_waits(run: Run) -> list:
    """Due time to admission of each request due in the window; one not
    admitted by the close enters at its wait."""
    out = []
    for rid in window_rids(run):
        r = run.requests.get(rid, {})
        t = run.steps[r["admit"]][0] if "admit" in r else run.w1
        out.append(min(t, run.w1) - run.due[rid])
    return out
