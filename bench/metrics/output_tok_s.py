"""Output tokens delivered to clients inside the window, over its seconds
(host clock; each token stamped when its stream yields it)."""


def read(run):
    w0, w1 = run.window
    return run.tokens_between(w0, w1) / (w1 - w0)
