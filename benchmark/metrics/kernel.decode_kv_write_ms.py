"""Device trace: milliseconds of a decode step under the scope ``kv_write``
(the new keys and values scattered into the cache, every layer)."""

from benchmark import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "jit_decode_fn", ("kv_write",))
