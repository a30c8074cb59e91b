"""Roofline share of the chunked scan of one final prompt chunk
(``jit_chunk_final``): the larger of the recurrence's operations at the
chunk's real tokens (inside a 256-token scan chunk the causal pairs, one
group's scores counted once for all 64 heads, and a token's part of the state
once in and once out: ``family.ssm_scan_flops``) over the chip's peak bf16
rate and its bytes (``family.ssm_scan_bytes``) over its peak HBM bandwidth,
all 36 mamba layers, over the chunk's device time under ``ssm_scan``, percent;
on the traced window's own counts (a window's final chunks are prompt tails
behind a seeded state)."""

from benchmark import ssm_gqa_dense

read = ssm_gqa_dense.on_window(ssm_gqa_dense.ssm_scan_share)
