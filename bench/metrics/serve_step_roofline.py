"""A decode step's least time over its device time per call.  Least time
is the larger of FLOPs / peak and bytes / HBM bandwidth
(``flops.lm_decode_step``), averaged over the positions a round steps
through (every round runs each once); device time is the mean execution
of the ``serve_step`` program in the traced window."""
from bench import flops, readers


def read(run, result):
    per_dev = readers.step_runs(result, match="serve_step")
    if per_dev is None:
        return None
    t, m = run["traffic"], run["config"]["model"]
    pk = readers.peak(run)
    n = t["prompt_len"] + t["new_tokens"] - 1
    steps = [flops.lm_decode_step(m, t["clients"], pos) for pos in range(n)]
    least = sum(flops.least_seconds(s["flops"], s["bytes"], pk)
                for s in steps) / n
    runs = [r for _, rs in per_dev for r in rs]
    device = sum(e - s for _, s, e in runs) / len(runs) / 1e9
    return 100.0 * least / device
