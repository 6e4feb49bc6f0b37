"""Generated tokens/s x 2 x parameters FLOP over the chips' bf16 peak, %."""
from bench import flops, readers, stats


def read(run, result):
    w = result["window"]
    n = flops.lm_param_counts(run["config"]["model"])["total"]
    achieved = stats.rate(w["tokens"], w["seconds"]) * 2.0 * n
    return 100.0 * achieved / (run["n_chips"] * readers.peak(run)["bf16_flops"])
