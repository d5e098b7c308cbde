"""Sampler: reading the (max_batch, vocab) fp32 logits once at the peak HBM
bandwidth, over ``sampler_ms``: the least time of top-p over those rows,
whatever implements it."""
import statistics

from chip import counts


def read(run):
    d = run.get("sampler_s")
    if not d:
        return None
    least = counts.sampler_bytes(run["max_batch"], run["logits_width"]) / run["peaks"]["hbm_bw"]
    return 100.0 * least / statistics.median(d)
