"""Device trace: mean device time of one ``prefix_seed`` program
(``jit_seed_prefix``: a cached prefix's keys and values copied into a scratch
stripe)."""

from benchmark import trace


def read(ctx):
    s = trace.module_mean_s(ctx["trace"], "jit_seed_prefix")
    return None if s is None else 1e3 * s
