"""Device milliseconds of the train-step program per step, from the trace
(its ``XLA Modules`` events on the first chip)."""


def read(run):
    if run.trace is None:
        return None
    sec, n = run.trace.kind_time("train_step")
    return 1e3 * sec / n if n else None
