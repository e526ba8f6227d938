"""Set-up seconds: process start to the end of warm-up, weights, engine or
trainer, and every compilation (or compile-cache read) included."""


def read(run):
    return run.setup_s
