"""Device trace: milliseconds of a train step in operations under ``jvp``
only (the forward pass as first computed), by ``op_name``."""

from benchmark import scopes


def read(ctx):
    return scopes.direction_ms(ctx, "jit_step_fn", "forward")
