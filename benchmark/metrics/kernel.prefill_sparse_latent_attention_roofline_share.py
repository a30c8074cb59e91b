"""Roofline share of a mean final prompt chunk's attention over the positions its
queries selected, the full layers (``jit_chunk_final`` under ``latent_sparse``):
``benchmark/sparse_latent.py final_chunk_sparse_share``. The indexer's scores
and the choice are under ``attn_index`` and ``attn_select`` and counted
apart."""

from benchmark import sparse_latent

read = sparse_latent.final_chunk_sparse_share
