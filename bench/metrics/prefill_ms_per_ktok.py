"""Device milliseconds of the admission prefill programs per 1000 prompt
tokens of the requests admitted inside the traced window."""


def read(run):
    if run.trace is None:
        return None
    sec, n = run.trace.kind_time("prefill")
    tokens = run.prompt_tokens_admitted(*run.trace_window)
    return 1e6 * sec / tokens if n and tokens else None
