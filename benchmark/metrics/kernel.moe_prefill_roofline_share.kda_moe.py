"""Roofline share of the expert layers of one final prompt chunk
(``jit_chunk_final``) of a ``kda_moe`` model: the larger of the operations the
chunk's real tokens need (router, the shared expert, and an expert's three
matrices for each of a token's choices that fell on the experts held here:
``family.moe_needed_flops``) over the chip's peak bf16 rate and the bytes of
what the layers must read (router, shared expert, the held experts that got a
token) over its peak HBM bandwidth, over the chunk's device time under
``moe_ffn``, percent. Real tokens are the engine's ``prefill_query_tokens``
over ``prefill_chunks`` of the final chunks, not the bucket's padding; the
held share of their choices is ``moe_assignments_held`` over
``moe_assignments`` of ``chunk_final`` (about an eighth: the grouped matmul's
tiles over the seven eighths that fall on absent experts are time under
``moe_ffn`` and no need). Means over the window's final chunks of every
bucket: the larger of two means is at most the mean of the larger, so the
share is not read too high for that."""

from benchmark import kda_moe

read = kda_moe.moe_prefill_share
