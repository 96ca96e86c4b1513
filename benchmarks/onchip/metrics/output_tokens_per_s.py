"""Output tokens of all requests stamped inside the window, over the
window's length."""
from harness.stats import rate


def read(run):
    n = sum(1 for r in run.records for t in r.stamps if run.in_window(t))
    return rate(n, run.seconds)
