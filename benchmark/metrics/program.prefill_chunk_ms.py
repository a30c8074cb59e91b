"""Device trace: mean device time of one execution of the engine's middle
prompt-chunk module (``jit_chunk_mid``: ``prefill_chunk`` tokens, no head)."""

from benchmark import trace


def read(ctx):
    s = trace.module_mean_s(ctx["trace"], "jit_chunk_mid")
    return None if s is None else 1e3 * s
