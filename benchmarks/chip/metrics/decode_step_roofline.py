"""Model step: the least time of the window's decode steps over their device
time.  The least time of a step is the larger of its bytes (every weight once,
plus the keys and values of each active row's live positions) over the peak
HBM bandwidth and its model FLOPs over the peak; the device time is the trace
time of the ``_decode_n_impl`` programs.  Dense models only: an MoE step's
bytes depend on which experts were routed, which the program does not count."""
from chip import counts, trace


def read(run):
    if run["trace"] is None or "n_routed_experts" in run["cfg"]:
        return None
    secs = trace.program_seconds(run["trace"], "jit__decode_n_impl")
    if secs <= 0:
        return None
    cfg, pk = run["cfg"], run["peaks"]
    least = 0.0
    for t in run["window"]["ticks"]:
        if not t["tokens"]:
            continue
        per_step = counts.weight_bytes(cfg) * t["n"] + counts.kv_bytes_per_token(cfg) * sum(
            t["contexts"])
        least += max(per_step / pk["hbm_bw"],
                     counts.decode_flops(cfg, t["contexts"]) / pk["bf16_flops"])
    return 100.0 * least / secs
