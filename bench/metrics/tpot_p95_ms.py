"""p95 over requests of the time per output token inside the window:
(last token - first token) / (tokens - 1), over each request's tokens
that arrived inside the window, for every request with at least two."""
from bench.stats import percentile


def read(run):
    w0, w1 = run.window
    per = []
    for r in run.records:
        t = [x for x in r.times if w0 <= x < w1]
        if len(t) > 1:
            per.append((t[-1] - t[0]) / (len(t) - 1))
    return 1e3 * percentile(per, 95) if per else None
