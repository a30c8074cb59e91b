"""Device trace: of ``program.carried_step_own_ms``, the milliseconds a launch
that ``benchmark/scopes.py scope_of`` books to ``attn_core``: the rows' read
of their cache: the decode kernel between each row's bounds (or the einsum
over the stripe where it does not engage), a state-space or delta-rule layer's
step (``ssm_step``, ``kda_step``) with its convolution. Its twin in
``jit_decode_fn`` is the decode program's time under the same scope. None
against a program without the part ``beside``."""

from benchmark import carried


def read(ctx):
    return carried.own_ms(ctx, "attn_core")
