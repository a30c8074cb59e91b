"""Device trace: milliseconds of a train step under the scopes ``optimizer``
(clip, Adam update, apply) and ``grad_norm``."""

from benchmark import scopes


def read(ctx):
    return scopes.direction_ms(ctx, "jit_step_fn", "optimizer")
