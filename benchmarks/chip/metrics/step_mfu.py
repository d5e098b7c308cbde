"""Device, whole step: the model FLOPs that the window's tokens require (each
prefill's prompt and each decode token: 2 x the active matmul parameters, plus
attention at the token's context; for MoE only the routed and shared experts a
token runs through), over the window times the chip's peak bf16 FLOP/s."""
from chip import counts


def read(run):
    w, cfg = run["window"], run["cfg"]
    flops = sum(counts.prefill_flops(cfg, p["prompt"]) for p in w["prefills"])
    flops += sum(counts.decode_flops(cfg, t["contexts"]) for t in w["ticks"])
    if flops <= 0:
        return None
    return 100.0 * flops / (w["window_s"] * run["peaks"]["bf16_flops"] * run["chips"])
