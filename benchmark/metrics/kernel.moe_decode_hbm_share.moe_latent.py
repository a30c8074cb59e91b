"""``kernel.moe_decode_hbm_share`` with family ``moe_latent``'s counts: bytes
the expert layers of one decode step must read (router, its bias and the
shared experts of each layer, and the weights of the experts that got a
token: the engine's ``moe_experts_touched`` over ``moe_layer_steps`` of the
decode program) over the chip's peak HBM bandwidth, over the step's device
time under ``moe_ffn``, percent."""

from benchmark import moe_window, peaks
from benchmark.families import moe_latent as family


def read(ctx):
    ms = moe_window.inner_ms(ctx, "jit_decode_fn", "moe_ffn")
    touched = moe_window.touched_per_layer(ctx, "decode")
    if not ms or touched is None:
        return None
    c = ctx["config"]
    layers = family.layer_rows(c)["sparse"]
    needed = family.moe_needed_bytes(c, layers, layers * touched)
    return 100.0 * needed / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / (1e-3 * ms)
