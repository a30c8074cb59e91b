"""Bytes of the selected positions' rotated keys and latents (``min(live,
index_topk)`` x 1,152 bytes a row and full layer; the engine's
``index_positions_selected`` over ``decode_steps``) over the chip's peak HBM
bandwidth, over one decode step's device time under ``latent_sparse`` (the
gather of the chosen rows and the attention over them), percent; on the traced
window's own counts."""

from benchmark import sparse_latent

read = sparse_latent.on_window(sparse_latent.attention_part_share("sparse", "latent_sparse"))
