"""KV state: pages mapped by live requests over the pages a request can
be given, mean over the window's steps (telemetry ``admit`` page counts)."""
from bench import timeline


def read(run):
    steps = timeline.window_steps(run)
    if not steps:
        return None
    shares = []
    for s in steps:
        used = sum(r["n_pages"] for r in run.requests.values()
                   if "admit" in r and r["admit"] <= s
                   and r.get("finish", s) >= s)
        shares.append(used / run.allocatable)
    return 100.0 * sum(shares) / len(shares)
