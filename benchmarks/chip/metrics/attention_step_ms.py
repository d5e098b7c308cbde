"""Model step: device time, in the window, of the decode program's ops under
the program's ``attention`` scope (from ``norm1`` through the residual add:
projections, q/k norms, RoPE, the paged gather, page writes, the output
projection) per decode step.  Ops are joined to scopes in ``scopes.py``."""


def read(run):
    return run.get("scopes", {}).get("jit__decode_n_impl", {}).get("attention")
