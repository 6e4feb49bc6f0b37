"""For a model of latent attention and dropless expert layers: the
positions a round computes (each request's prompt, prefilled, and its new
tokens but the last, decoded) per second over the window, x 2 x the
parameters a token multiplies on this chip (``flops_mla_moe
.active_params``), over the chips' bf16 peak, in %.  Attention over the
context counts nothing, as in the serve cells' ``serve_mfu``."""
from bench import flops_mla_moe, readers, stats


def read(run, result):
    m, t = run["config"]["model"], run["traffic"]
    if not m.get("kv_lora_rank"):
        return None
    w = result["window"]
    G = t["new_tokens"]
    positions = (stats.rate(w["tokens"], w["seconds"])
                 * (t["prompt_len"] + G - 1) / G)
    achieved = positions * 2.0 * flops_mla_moe.active_params(m)
    return 100.0 * achieved / (run["n_chips"] * readers.peak(run)["bf16_flops"])
