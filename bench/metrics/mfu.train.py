"""Whole training step's share of the chip's peak: the operations one step
needs from shapes (``TrainRun.step_flops``: the target's forward for its
taps, the drafter's forward and backward over the valid expanded
positions), times the train-step programs that ran in the traced window,
over window seconds times chips times peak FLOP/s."""


def read(run):
    if run.trace is None:
        return None
    _, n = run.trace.kind_time("train_step")
    t0, t1 = run.trace_window
    return 100.0 * n * run.step_flops() / (
        (t1 - t0) * run.device["count"] * run.peak.flops)
