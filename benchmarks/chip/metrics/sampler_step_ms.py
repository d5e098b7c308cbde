"""Sampler: device time, in the window, of the decode program's ops under the
program's ``sampler`` scope (``ContinuousEngine._sample_rows``: top-p, or the
greedy argmax) per decode step: the sampler inside the step, where
``sampler_ms`` times it alone.  Ops are joined to scopes in ``scopes.py``."""


def read(run):
    return run.get("scopes", {}).get("jit__decode_n_impl", {}).get("sampler")
