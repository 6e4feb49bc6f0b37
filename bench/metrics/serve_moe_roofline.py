"""The dropless expert layers' share of their roofline at decode: their
least time (the larger of FLOPs / peak and bytes / HBM bandwidth,
``flops_mla_moe.moe_decode``: router, held and shared experts' weights,
and the routed pairs' GEMMs) over the device time per ``serve_step`` run
of the ops under the named scope ``moe``, in %."""
from bench import flops, flops_mla_moe, readers


def read(run, result):
    ms = flops_mla_moe.scope_ms_per_step(
        result, "moe", flops_mla_moe.GROUPED_GEMM)
    if not ms:
        return None
    s = flops_mla_moe.moe_decode(run["config"]["model"],
                                 run["traffic"]["clients"])
    least = flops.least_seconds(s["flops"], s["bytes"], readers.peak(run))
    return 100.0 * least / (ms / 1e3)
