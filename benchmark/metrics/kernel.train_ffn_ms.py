"""Device trace: milliseconds of a train step under the scope ``ffn`` /
``moe_ffn``, forward, recomputed and backward together."""

from benchmark import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "jit_step_fn", ("ffn", "moe_ffn"))
