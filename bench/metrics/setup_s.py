"""Process start to the start of the window (host clock)."""


def read(run, result):
    return result["setup_s"]
