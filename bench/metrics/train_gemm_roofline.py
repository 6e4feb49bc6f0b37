"""The layer GEMMs' share of their roofline: the step's algorithmic GEMM
FLOPs at peak over the device time of the ops that implement them (the
fusions of this run's compiled step that hold a dot or convolution, or a
Pallas call), summed over the step executions in the traced window."""
from bench import devtrace, readers


def read(run, result):
    per_dev = readers.step_runs(result)
    gemms = result["ctx"].get("gemm_ops")
    if per_dev is None or not gemms:
        return None
    flops = result["window"]["flops_per_step"] / run["n_chips"]
    least = busy = 0.0
    for dev, runs in per_dev:
        ops = devtrace.ops_within(dev, runs, gemms)
        least += len(runs) * flops / readers.peak(run)["bf16_flops"]
        busy += sum(e - s for _, s, e in ops) / 1e9
    return 100.0 * least / busy if busy > 0 else None
