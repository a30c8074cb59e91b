"""Bytes of the weights a decode step of an ``ssm_gqa_dense`` model multiplies
by (every layer's mixer, feed-forward and norms, the final norm and the tied
table once as the head: ``family.decode_weight_bytes``, 6.38 GB of the some
10 GB a step moves) over the chip's peak HBM bandwidth, over the device time
of a decode step under the scopes ``attn_qkv``, ``attn_out`` (the mamba
layers' two projections run under them too, inside ``ssm_mixer``), ``ffn`` and
``lm_head``, percent. Those matmuls have 24 rows: they are bound by reading
the weights. ``kernel.decode_matmul_hbm_share`` counts a uniform stack's
projections from other keys of the configuration file."""

from benchmark import ssm_gqa_dense

read = ssm_gqa_dense.decode_matmul_share
