"""Training FLOP/s over the chips' peak: completed steps times the step's
algorithmic FLOPs (``flops.mlp_train_flops``) over the window, in % of
chips x bf16 peak."""
from bench import readers


def read(run, result):
    w = result["window"]
    achieved = w["steps"] * w["flops_per_step"] / w["seconds"]
    return 100.0 * achieved / (run["n_chips"] * readers.peak(run)["bf16_flops"])
