"""Program counter: bytes the prefix store holds at the window's close in a
pool that stores prefixes as snapshots (each entry a slot's state leaves and
the attention layers' keys and values at one of a few lengths), as the engine
adds them up (``get_stats()["prefix_cache_bytes"]``)."""

from benchmark import ssm_gqa_dense


def read(ctx):
    return ssm_gqa_dense.store_bytes(ctx)
