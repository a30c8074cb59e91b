"""Bytes of the windows' rotated keys and latents (``min(live, window)`` x 2,176
bytes a row and sliding layer; the engine's ``decode_kv_tokens_window`` over
``decode_steps``) over the chip's peak HBM bandwidth, over one decode step's
device time under ``latent_window`` (the decode kernel between the window's
bounds), percent; on the traced window's own counts."""

from benchmark import sparse_latent

read = sparse_latent.on_window(sparse_latent.attention_part_share("window", "latent_window"))
