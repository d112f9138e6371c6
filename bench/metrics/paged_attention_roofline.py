"""Kernels: least time of attention's required work per step, with KV
bytes counted at each slot's live length (``bench.work``), over the
``paged_attention`` device time per serve step in the trace."""
from bench import timeline, work


def read(run):
    t = run.trace
    ns = t.kernel_ns.get("paged_attention") if t else None
    steps = timeline.window_steps(run)
    if not ns or not t.steps or not steps or run.peak is None:
        return None
    need = sum(work.step_attention_least_s(
        run.model, timeline.live_lens(run, s), run.peak) for s in steps)
    return 100.0 * (need / len(steps)) / (ns / 1e9 / len(t.steps))
