"""Share of its roofline that one decode iteration reaches: the least time
the chip could take for the operations and bytes the iteration needs
(``bench/flops.decode_iteration`` over each dispatch's active slots and
their committed lengths, the larger of operations over peak FLOP/s and
bytes over HBM bandwidth), over the step program's device time, both
averaged over the iterations of the traced window."""


def read(run):
    if run.trace is None:
        return None
    sec, n = run.trace.kind_time("decode_step")
    bound = run.decode_bound_s(*run.trace_window)
    if not n or bound is None:
        return None
    return 100.0 * bound / (sec / n)
