"""Device milliseconds per decode-step call of the paged view's copies:
the first chip's ops under the ``gather`` or ``scatter`` scope of
``_paged_step_impl`` (``cache_ops.gather_state`` / ``scatter_state``), as
the union of their intervals in the traced window, over the decode-step
calls there. 0 where the scopes hold no op: a target with no paged leaf
passes both through. Ops the compiler made of the copies but named after
another root (a fusion takes its root's ``op_name``) fall outside."""
from bench import spans as S


def read(run):
    sp = S.of_run(run)
    n = sp.calls("decode_step") if sp is not None else 0
    if not n:
        return None
    return 1e3 * sp.scoped_s(("_paged_step_impl",), ("gather", "scatter")) / n
