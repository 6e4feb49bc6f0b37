"""Per execution of the compiled step in the traced window, the device
time of the ops whose HLO ``op_name`` lies under the named scope
``train.optimizer`` (AdamW and ``apply_updates``), mean over the
executions and devices, in ms.  A fusion counts by its own ``op_name``."""
from bench import devtrace, progtrace, readers


def read(run, result):
    pt = progtrace.of(result)
    per_dev = readers.step_runs(result)
    if pt is None or per_dev is None:
        return None
    ms = []
    for dev, runs in per_dev:
        hlo = pt["hlo"].get(runs[0][0])
        names = progtrace.op_names(hlo) if hlo else {}
        ms.append(progtrace.scope_ms_per_run(devtrace.ops_within(dev, runs),
                                             names, "train.optimizer",
                                             len(runs)))
    return None if None in ms else sum(ms) / len(ms)
