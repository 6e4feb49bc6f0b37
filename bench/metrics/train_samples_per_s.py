"""Samples of every step completed in the window, over the whole window:
first dispatch to the completion of the last step (host clock)."""
from bench import stats


def read(run, result):
    w = result["window"]
    return stats.rate(w["samples"], w["seconds"])
