"""Host milliseconds the training loop spends between steps: the mean over
the steps in the traced window of the time from the end of
``train.readback`` (the trainer's read of a step's metrics) to the start of
the next ``train.step`` (its dispatch). It holds the next batch's build
(``train.batch``) and its transfer (``train.put``)."""
from bench import spans as S


def read(run):
    sp = S.of_run(run)
    if sp is None:
        return None
    gaps = sp.stretches_s("train.readback", "train.step")
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
