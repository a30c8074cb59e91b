"""The latents and shared rotated keys the layers of one decode step need
(the engine's ``decode_kv_tokens_latent`` over ``decode_steps``: over active
slots, the length; 576 numbers a token and layer) over the chip's peak HBM
bandwidth, over the step's device time under ``attn_core/latent`` (the
absorbed queries and the decode kernel), percent. Counts the need: the kernel
reads whole blocks and the key's 128-lane row."""

from benchmark import moe_latent, moe_window, peaks
from benchmark.families import moe_latent as family


def read(ctx):
    ms = moe_window.inner_ms(ctx, "jit_decode_fn", "attn_core", "latent")
    tokens = moe_latent.latent_tokens_per_step(ctx)
    if not ms or tokens is None:
        return None
    c = ctx["config"]
    needed = tokens * family.layer_rows(c)["all"] * family.latent_bytes_per_token_layer(c)
    return 100.0 * needed / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / (1e-3 * ms)
