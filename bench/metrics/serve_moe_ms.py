"""Per run of the ``serve_step`` program in the traced window, the device
time of the ops whose HLO ``op_name`` lies under the named scope ``moe``
(routing, the sort, the held experts' grouped GEMMs, the combine and the
shared experts), mean over the runs, in ms.  A fusion counts by its own
``op_name``; the kernels the compiler builds for the grouped GEMMs carry
none of the program's scopes and count here (``GROUPED_GEMM``)."""
from bench import flops_mla_moe


def read(run, result):
    return flops_mla_moe.scope_ms_per_step(
        result, "moe", flops_mla_moe.GROUPED_GEMM)
