"""Generated tokens of every request completed in the window, over the
whole window (host clock)."""
from bench import stats


def read(run, result):
    w = result["window"]
    return stats.rate(w["tokens"], w["seconds"])
