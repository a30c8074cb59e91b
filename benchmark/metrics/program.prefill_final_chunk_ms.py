"""Device trace: mean device time of one execution of the engine's final
prompt-chunk module (``jit_chunk_final``: the chunk's layers over the scratch
stripe, the head, the first token's sample and the stripe's copy into its
slot)."""

from benchmark import trace


def read(ctx):
    s = trace.module_mean_s(ctx["trace"], "jit_chunk_final")
    return None if s is None else 1e3 * s
