"""Bytes a decode step of an ``ssm_gqa_dense`` model must move (every layer's
mixer, feed-forward and norms, the final norm and the tied table once; the
state and convolution tails of every slot that holds a request, read and
written; the keys and values of the live tokens in the four attention layers:
``family.decode_step_bytes``) over the chip's peak HBM bandwidth, over the
device time of a decode step, percent; on the traced window's own counts. The
share of the whole step that bounds every later claim in this cell."""

from benchmark import ssm_gqa_dense

read = ssm_gqa_dense.on_window(ssm_gqa_dense.decode_step_share)
