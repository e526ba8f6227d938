"""Host milliseconds the serving loop spends between the device's results
and its next step: the mean, over the scheduler loop's passes in the traced
window, of the time from the end of ``serve.readback`` (the harvest's
readback) to the start of the next ``serve.dispatch``, less the time inside
``serve.prefill`` (admission prefills, read by ``prefill_ms_per_ktok``).
It holds the harvest's bookkeeping, delivery to clients, the event loop's
turn, admission and page growth."""
from bench import spans as S


def read(run):
    sp = S.of_run(run)
    if sp is None:
        return None
    gaps = sp.stretches_s("serve.readback", "serve.dispatch",
                          less=("serve.prefill",))
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
