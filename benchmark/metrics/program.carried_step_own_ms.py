"""Device trace: what a carried decode step's rows run alone, milliseconds a
launch: device time under a ``beside`` scope part in the window's executions
of ``jit_chunk_mid`` and ``jit_chunk_final``, over the executions that hold
any such operation (``benchmark/carried.py``): the rows' cache write, decode
kernel or state step and sampler, and in a middle chunk the head and a last
feed-forward that are theirs alone. The matrix products the rows share with
the chunk's tokens are not in it, so a carried step costs this and more rows
in those. Dead launches (``engine.carried_step_dead_share.window``) run the
same operations and are among the executions. None against a program without
the part."""

from benchmark import carried


def read(ctx):
    return carried.own_ms(ctx)
