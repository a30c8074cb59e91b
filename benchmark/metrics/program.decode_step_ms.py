"""Device trace: mean device time of one execution of the engine's decode
module (``jit_decode_fn``; ``jit_decode_multi`` where ``decode_steps`` > 1 is
not in any cell yet and would need dividing by its steps)."""

from benchmark import trace


def read(ctx):
    s = trace.module_mean_s(ctx["trace"], "jit_decode_fn")
    return None if s is None else 1e3 * s
