"""Bytes the one gated attention layer of a decode step must move (its query,
key, value, gate and output projections once, and the keys and values of the
live tokens: the engine's ``decode_kv_tokens_global`` over ``decode_steps``;
``family.attention_decode_bytes``) over the chip's peak HBM bandwidth, over
the step's device time under ``attn_qkv``, ``attn_core`` and ``attn_out``
outside ``kda_mixer``, percent; on the traced window's own counts."""

from benchmark import kda_moe

read = kda_moe.on_window(kda_moe.attention_decode_share)
