"""Device trace: of ``program.carried_step_own_ms``, the milliseconds a launch
that ``benchmark/scopes.py scope_of`` books to ``kv_write``: the rows' new
keys and values scattered into the pool's cache, every layer. Its twin in
``jit_decode_fn`` is ``kernel.decode_kv_write_ms``. None against a program
without the part ``beside``."""

from benchmark import carried


def read(ctx):
    return carried.own_ms(ctx, "kv_write")
