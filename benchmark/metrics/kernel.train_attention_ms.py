"""Device trace: milliseconds of a train step under the scope ``attn_core``
(the attention kernel), forward, recomputed and backward together. A time,
not a roofline share: a share needs the benchmark's count of operations with
recomputation in or out."""

from benchmark import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "jit_step_fn", ("attn_core",))
