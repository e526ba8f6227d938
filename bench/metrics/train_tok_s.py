"""Batch x sequence tokens of the training steps completed inside the
window, over its seconds (host clock; each step ends when the trainer has
read its metrics back)."""


def read(run):
    w0, w1 = run.window
    return run.steps * run.tokens_per_step() / (w1 - w0)
