"""Model step: the decode program's op time in the window per decode step,
less ``attention_step_ms``, ``mlp_step_ms`` and ``sampler_step_ms``: the head
(final norm and unembedding), the embedding, the layer loop's slicing of the
stacked weights and caches, and copies the compiler adds."""


def read(run):
    by = run.get("scopes", {}).get("jit__decode_n_impl")
    if not by:
        return None
    return by["total"] - by["attention"] - by["mlp"] - by["sampler"]
