"""Bytes a decode step of a ``moe_window_gqa`` model must read (attention,
norms, dense feed-forward and head weights once, router, shared expert and
the touched experts of each expert layer, and the keys and values its layers
need: the window's for sliding layers, the length's for full ones) over the
chip's peak HBM bandwidth, over the device time of a decode step, percent."""

from benchmark import moe_window, peaks, trace
from benchmark.families import moe_window_gqa as family


def read(ctx):
    step_s = trace.module_mean_s(ctx["trace"], "jit_decode_fn")
    touched = moe_window.touched_per_layer(ctx, "decode")
    window = moe_window.kv_tokens_per_step(ctx, "window")
    whole = moe_window.kv_tokens_per_step(ctx, "global")
    if step_s is None or touched is None or window is None or whole is None:
        return None
    c = ctx["config"]
    rows = family.layer_rows(c)
    needed = family.decode_weight_bytes(c, touched) + family.kv_bytes_per_token_layer(c) * (
        window * rows["sliding"] + whole * rows["full"])
    return 100.0 * needed / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / step_s
