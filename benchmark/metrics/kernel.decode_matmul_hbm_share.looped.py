"""Bytes of the weights a decode step of a ``looped_dense`` model multiplies
by (every layer's projections and feed-forward once a pass, the head once)
over the chip's peak HBM bandwidth, over the device time of a step under
``attn_qkv``, ``attn_out``, ``ffn`` and ``lm_head``, percent."""

from benchmark import looped

read = looped.matmul_share
