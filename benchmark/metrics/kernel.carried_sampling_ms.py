"""Device trace: of ``program.carried_step_own_ms``, the milliseconds a launch
that ``benchmark/scopes.py scope_of`` books to ``sampling``: the rows' sampler
(``sample_riders``: candidates, draw and the merge by ``live``). Its twin in
``jit_decode_fn`` is ``kernel.decode_sampling_ms``. None against a program
without the part ``beside``."""

from benchmark import carried


def read(ctx):
    return carried.own_ms(ctx, "sampling")
