"""Device trace: milliseconds of a train step in operations under
``transpose(jvp(..))`` or remat's recomputation, by ``op_name``."""

from benchmark import scopes


def read(ctx):
    return scopes.direction_ms(ctx, "jit_step_fn", "backward")
