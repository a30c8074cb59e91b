"""Bytes a decode step of a ``moe_latent`` model must read (attention, norms,
dense feed-forward and head weights once, router, shared experts and the
touched experts of each expert layer, and the latents and shared rotated keys
of the live tokens, every layer) over the chip's peak HBM bandwidth, over the
device time of a decode step, percent."""

from benchmark import moe_latent, moe_window, peaks, trace
from benchmark.families import moe_latent as family


def read(ctx):
    step_s = trace.module_mean_s(ctx["trace"], "jit_decode_fn")
    touched = moe_window.touched_per_layer(ctx, "decode")
    tokens = moe_latent.latent_tokens_per_step(ctx)
    if step_s is None or touched is None or tokens is None:
        return None
    c = ctx["config"]
    needed = family.decode_weight_bytes(c, touched) + (
        tokens * family.layer_rows(c)["all"] * family.latent_bytes_per_token_layer(c))
    return 100.0 * needed / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / step_s
