"""Per step, the device time in which a collective of the compiled step
runs and no other op does, mean over the devices (traced window)."""
from bench import devtrace, readers


def read(run, result):
    per_dev = readers.step_runs(result)
    coll = result["ctx"].get("collective_ops")
    if per_dev is None or not coll:
        return None
    ms = [devtrace.exposed_ns(dev, runs, coll) / len(runs) / 1e6
          for dev, runs in per_dev]
    return sum(ms) / len(ms)
