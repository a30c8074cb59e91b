"""Roofline share of the attention of one final prompt chunk
(``jit_chunk_final``: a short question behind a seeded document): the larger
of its operations in the cheaper of the two forms the program chooses between
by a chunk's width (absorbed: each query through the key up-projection, then a
score and a context against the latents for every head and attended position;
expanded: every seen position's keys and values from its latent first), over
the chip's peak bf16 rate and the bytes of the cached tokens its queries see
over its peak HBM bandwidth, over the chunk's device time under
``attn_core/latent``, percent. Counted from the engine's
``prefill_query_tokens`` and ``prefill_attended_positions`` of ``chunk_final``:
real tokens and causal pairs only, not the chunk's padding nor the masked part
of its last key block."""

from benchmark import moe_latent, moe_window


def read(ctx):
    ms = moe_window.inner_ms(ctx, "jit_chunk_final", "attn_core", "latent")
    least = moe_latent.final_chunk_attention_least_s(ctx)
    if not ms or least is None:
        return None
    return 100.0 * least / (1e-3 * ms)
