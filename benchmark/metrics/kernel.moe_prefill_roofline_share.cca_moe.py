"""Roofline share of the held experts' grouped matmuls of one middle prompt
chunk (``jit_chunk_mid``) of a ``cca_moe`` model at one expert a token: the
larger of the operations the launch's real tokens whose choice is held here
need (an expert's three matrices each) over the chip's peak bf16 rate and of
the touched banks' bytes over its peak HBM bandwidth, over the launch's device
time under ``moe_ffn/experts``, percent. Real tokens are the engine's
``prefill_query_tokens`` over ``prefill_programs`` of the middle chunks; the
held share of their choices ``moe_assignments_held`` over ``moe_assignments``
of ``chunk_mid`` (about a half)."""

from benchmark import cca_moe

read = cca_moe.moe_prefill_share
