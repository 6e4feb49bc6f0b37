"""A whole decode step's share of its roofline, for a model of latent
attention and dropless expert layers: its least time (the larger of FLOPs
/ peak and bytes / HBM bandwidth, ``flops_mla_moe.decode_step``: latent
attention, expert layers, the leading dense layers and the head),
averaged over the positions a round's decode steps run at, over the mean
device time of a ``serve_step`` run in the traced window, in %."""
from bench import flops, flops_mla_moe, readers


def read(run, result):
    m = run["config"]["model"]
    per_dev = readers.step_runs(result, match="serve_step")
    if per_dev is None or not m.get("kv_lora_rank"):
        return None
    t, pk = run["traffic"], readers.peak(run)
    positions = flops_mla_moe.decode_positions(t)
    least = sum(flops.least_seconds(s["flops"], s["bytes"], pk)
                for s in (flops_mla_moe.decode_step(m, t["clients"], pos)
                          for pos in positions)) / len(positions)
    runs = [r for _, rs in per_dev for r in rs]
    device = sum(e - s for _, s, e in runs) / len(runs) / 1e9
    return 100.0 * least / device
