"""Model step: device time, in the window, of the decode program's ops under
the program's ``mlp`` scope (from ``norm2`` through the residual add; for MoE
the router, the mask-scan dispatch, the routed and the shared experts) per
decode step.  Ops are joined to scopes in ``scopes.py``."""


def read(run):
    return run.get("scopes", {}).get("jit__decode_n_impl", {}).get("mlp")
