"""Device milliseconds per train-step call of the optimizer update: the
first chip's ops under the ``update`` scope of the train step (AdamW with
its gradient clipping, ``optim/adamw.py``, and ``apply_updates``), as the
union of their intervals in the traced window, over the train-step calls
there."""
from bench import spans as S


def read(run):
    sp = S.of_run(run)
    n = sp.calls("train_step") if sp is not None else 0
    if not n:
        return None
    return 1e3 * sp.scoped_s(("step",), ("update",)) / n
