"""Roofline share of the expert blocks of one final prompt chunk
(``jit_chunk_final``) of an ``ssm_latent_moe`` model: the larger of the
operations the chunk's real tokens need (router, the latent's two
projections, the shared expert, and an expert's two matrices for each of a
token's choices that fell on the experts held here:
``family.moe_needed_flops``) over the chip's peak bf16 rate and the bytes of
what the blocks must read (router, projections, shared expert, the held
experts that got a token) over its peak HBM bandwidth, over the chunk's device
time under ``moe_ffn``, percent.

Real tokens are the engine's ``prefill_query_tokens`` over ``prefill_chunks``
of the final chunks, not the bucket's padding; the held share of their
choices is ``moe_assignments_held`` over ``moe_assignments`` of ``chunk_final``
(about a quarter: the grouped matmul's tiles over the three quarters that
fall on absent experts are time under ``moe_ffn`` and no need); experts
touched are ``chunk_final``'s own. A final chunk feeds the head, so all of the
cut's expert blocks run. Means over the window's final chunks of every
bucket: the larger of two means is at most the mean of the larger, so the
share is not read too high for that."""

from benchmark import moe_window, peaks, ssm_latent_moe
from benchmark.families import ssm_latent_moe as family


def read(ctx):
    ms = moe_window.inner_ms(ctx, "jit_chunk_final", "moe_ffn")
    touched = moe_window.touched_per_layer(ctx, "chunk_final")
    tokens = ssm_latent_moe.mean_final_chunk_tokens(ctx)
    share = ssm_latent_moe.final_chunk_held_share(ctx)
    if not ms or touched is None or tokens is None or share is None:
        return None
    c, chip = ctx["config"], peaks.peaks(ctx["device_kind"])
    layers = family.layer_rows(c)["sparse"]
    held = tokens * c["num_experts_per_tok"] * share
    least_s = max(
        family.moe_needed_flops(c, layers, tokens, held) / chip["bf16_flops_per_s"],
        family.moe_needed_bytes(c, layers, layers * touched) / chip["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (1e-3 * ms)
