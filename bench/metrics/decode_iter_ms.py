"""Device milliseconds of the engine's decode-step program per iteration,
from the trace (the program's ``XLA Modules`` events on the first chip)."""


def read(run):
    if run.trace is None:
        return None
    sec, n = run.trace.kind_time("decode_step")
    return 1e3 * sec / n if n else None
