"""Sampler: device time of one call of the engine's ``_sample_rows``, jitted
alone on seeded fp32 logits of shape (max_batch, vocab); the median of the
calls the traced run makes after its window."""
import statistics


def read(run):
    d = run.get("sampler_s")
    if not d:
        return None
    return 1e3 * statistics.median(d)
