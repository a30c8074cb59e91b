"""Roofline share of the chunked delta rule in the prompt-chunk programs
(``jit_chunk_mid`` and ``jit_chunk_final``, weighted by their executions in
the traced window): the larger of the rule's operations at a mean launch's
real tokens (inside a 64-token chunk the pair weights, the solve's
application and the reads; from chunk to chunk the state once in and once
out: ``family.kda_scan_flops``; the inverse itself is the algorithm's and not
counted) over the chip's peak bf16 rate and its bytes (a token's q, k, v, decay
and writing strength in, its output out, a row's state read and written:
``family.kda_scan_bytes``) over its peak HBM bandwidth, all three delta-rule
layers, over the launch's device time under ``kda_scan``, percent. Real tokens
and rows a launch from the engine's ``prefill_query_tokens``,
``prefill_chunks`` and ``prefill_programs``, not the bucket's padding. The
rule's products are float32 at the highest precision (six passes of the
matrix unit each), so a reading near a sixth of 100 is the unit's whole rate."""

from benchmark import kda_moe

read = kda_moe.kda_prefill_share
