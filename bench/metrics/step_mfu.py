"""Model step: model FLOPs of the tokens the window's steps processed
(``bench.work.step_flops``), over the steps' summed host-clock time times
the chip's peak bf16 FLOP/s."""
from bench import timeline, work


def read(run):
    steps = timeline.window_steps(run)
    if not steps or run.peak is None:
        return None
    flops = sum(work.step_flops(run.model, timeline.live_lens(run, s))
                for s in steps)
    wall = sum(run.steps[s][1] - run.steps[s][0] for s in steps)
    return 100.0 * flops / (wall * run.peak["bf16_flops"])
