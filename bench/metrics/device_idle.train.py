"""1 - (union of op intervals on a device) / traced window, mean over the
devices, in %."""
from bench import readers


def read(run, result):
    return readers.idle_percent(result)
