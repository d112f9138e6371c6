"""Kernels: least time of the decode-at-use matmuls' required work per
step (``bench.work``: stored int8 weights, live rows in and out), over
the ``ecc_qmatmul`` device time per serve step in the trace."""
from bench import timeline, work


def read(run):
    t = run.trace
    ns = t.kernel_ns.get("ecc_qmatmul") if t else None
    steps = timeline.window_steps(run)
    if not ns or not t.steps or not steps or run.peak is None:
        return None
    need = sum(work.step_qmatmul_least_s(
        run.model, len(timeline.live_lens(run, s)), run.peak) for s in steps)
    return 100.0 * (need / len(steps)) / (ns / 1e9 / len(t.steps))
