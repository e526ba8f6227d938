"""Device milliseconds of page growth per decode-step call: the programs
``Engine.ensure_capacity`` runs when a slot crosses a page boundary (the
pool blank ``_blank_row_impl`` and the block-table row setter
``_set_table_row_impl``) in the traced window, over the decode-step calls
there."""
from bench import spans as S


def read(run):
    sp = S.of_run(run)
    n = sp.calls("decode_step") if sp is not None else 0
    if not n:
        return None
    return 1e3 * sp.program_s(S.GROWTH) / n
