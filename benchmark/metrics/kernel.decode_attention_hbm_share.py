"""Bytes of the keys and values of the tokens live in the active slots (the
engine's ``live_tokens``, as sampled inside the replica over the window: what
attention over the cache must read, not the dead stripe it reads today) over
the chip's peak HBM bandwidth, over the device time of a decode step under
the scope ``attn_core``."""

from benchmark import peaks, scopes


def read(ctx):
    ms = scopes.per_step_ms(ctx, "jit_decode_fn", ("attn_core",))
    busy = [s for s in ctx["samples"] if s["active_slots"]]
    if not ms or not busy:
        return None
    live = sum(s["live_tokens"] for s in busy) / len(busy)
    needed = live * peaks.kv_bytes_per_token(ctx["config"])
    return 100.0 * needed / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / (1e-3 * ms)
