"""Bytes of the live positions' index keys (256 bytes a position, row and full
layer; the engine's ``index_positions_scored`` over ``decode_steps``) over the
chip's peak HBM bandwidth, over one decode step's device time under
``attn_index`` (the indexer's three projections and its scores over a slot's
stripe), percent; on the traced window's own counts."""

from benchmark import sparse_latent

read = sparse_latent.on_window(sparse_latent.attention_part_share("index", "attn_index"))
