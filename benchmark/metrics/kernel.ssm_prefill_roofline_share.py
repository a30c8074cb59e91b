"""Roofline share of the chunked scan of one final prompt chunk
(``jit_chunk_final``): the larger of the recurrence's operations at the
chunk's real tokens (inside a 128-token scan chunk the causal pairs, from
chunk to chunk the state once in and once out: ``family.ssm_scan_flops``) over
the chip's peak bf16 rate and its bytes (a token's x, B, C and step in, y out,
the row's state read and written) over its peak HBM bandwidth, all five
state-space blocks, over the chunk's device time under ``ssm_scan``, percent.
Real tokens from the engine's ``prefill_query_tokens`` over ``prefill_chunks``
of the final chunks, not the bucket's padding."""

from benchmark import peaks, ssm_latent_moe
from benchmark.families import ssm_latent_moe as family


def read(ctx):
    ms = ssm_latent_moe.under_ms(ctx, "jit_chunk_final", "ssm_scan")
    tokens = ssm_latent_moe.mean_final_chunk_tokens(ctx)
    if not ms or tokens is None:
        return None
    c, chip = ctx["config"], peaks.peaks(ctx["device_kind"])
    least = family.layer_rows(c)["ssm"] * max(
        family.ssm_scan_flops(c, tokens) / chip["bf16_flops_per_s"],
        family.ssm_scan_bytes(c, tokens, 1) / chip["hbm_bytes_per_s"],
    )
    return 100.0 * least / (1e-3 * ms)
