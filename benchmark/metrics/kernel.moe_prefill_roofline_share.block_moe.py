"""Roofline share of the expert layers of one final prompt chunk
(``jit_chunk_final``) of a ``block_moe`` model: the larger of the operations
the chunk's real tokens need (router and eight experts a token:
``family.moe_needed_flops``) over the chip's peak bf16 rate and of the bytes
the layers must read (router, the experts that got a token) over its peak HBM
bandwidth, over the chunk's device time under ``moe_ffn``, percent."""

from benchmark import block_moe

read = block_moe.moe_prefill_share
