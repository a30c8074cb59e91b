"""Bytes of the live tokens' keys and values of every layer (2 heads of 128
each a token: ``family.kv_bytes_per_token``; the engine's
``decode_kv_tokens_global`` over ``decode_steps``) over the chip's peak HBM
bandwidth, over one decode step's device time under ``attn_core`` (the decode
kernel over the compressed stripes), percent; on the traced window's own
counts."""

from benchmark import cca_moe

read = cca_moe.on_window(cca_moe.attention_decode_share)
