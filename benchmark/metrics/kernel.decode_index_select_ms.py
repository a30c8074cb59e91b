"""Device milliseconds of one decode step (``jit_decode_fn``) under
``attn_select``, all full layers: the choice of ``index_topk`` positions a row
out of a stripe's scores (``ops/topk.py``). None against a program without the
scope."""

from benchmark import sparse_latent

read = sparse_latent.select_ms
