"""Device trace: device time of the train step's XLA module, per step."""

from benchmark import trace


def read(ctx):
    s = trace.module_mean_s(ctx["trace"], "jit_step_fn")
    return None if s is None else 1e3 * s
