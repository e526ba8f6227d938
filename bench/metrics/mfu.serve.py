"""Whole serving step's share of the chip's peak: the target model's
forward operations for the prompt tokens admitted and the output tokens
committed inside the traced window (``bench/flops``), over window seconds
times chips times peak FLOP/s."""


def read(run):
    if run.trace is None:
        return None
    t0, t1 = run.trace_window
    return 100.0 * run.useful_target_flops(t0, t1) / (
        (t1 - t0) * run.device["count"] * run.peak.flops)
