"""Keys and values the sliding-window layers of one decode step need (the
engine's ``decode_kv_tokens_window``: over active slots, min(length, window))
over the chip's peak HBM bandwidth, over the step's device time under
``attn_core/window``, percent."""

from benchmark import moe_window


def read(ctx):
    return moe_window.attention_share(ctx, "sliding", "window", "window")
