"""Keys and values the full-attention layers of one decode step need (the
engine's ``decode_kv_tokens_global``: over active slots, the length) over the
chip's peak HBM bandwidth, over the step's device time under
``attn_core/global``, percent. The program reads the whole stripe."""

from benchmark import moe_window


def read(ctx):
    return moe_window.attention_share(ctx, "full", "global", "global")
