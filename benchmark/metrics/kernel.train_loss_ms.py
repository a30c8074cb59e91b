"""Device trace: milliseconds of a train step under the scopes ``loss`` (the
fused cross-entropy, which holds the output head's matmul) and ``lm_head``,
forward, recomputed and backward together."""

from benchmark import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "jit_step_fn", ("loss", "lm_head"))
