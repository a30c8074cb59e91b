"""Device trace: milliseconds of a decode step under the scope ``sampling``
(top-k, temperature and the categorical draw over the batch's logits)."""

from benchmark import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "jit_decode_fn", ("sampling",))
