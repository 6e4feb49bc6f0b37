"""Absorbed decode attention's share of its roofline: its least time (the
larger of FLOPs / peak and bytes / HBM bandwidth, ``flops_mla_moe
.mla_decode``: the weights and the live latent cache rows), averaged over
the positions a round's decode steps run at, over the device time per
``serve_step`` run of the ops under the named scope ``mla``, in %."""
from bench import flops, flops_mla_moe, readers


def read(run, result):
    ms = flops_mla_moe.scope_ms_per_step(result, "mla")
    if not ms:
        return None
    t, m = run["traffic"], run["config"]["model"]
    pk = readers.peak(run)
    positions = flops_mla_moe.decode_positions(t)
    steps = [flops_mla_moe.mla_decode(m, t["clients"], pos)
             for pos in positions]
    least = sum(flops.least_seconds(s["flops"], s["bytes"], pk)
                for s in steps) / len(steps)
    return 100.0 * least / (ms / 1e3)
