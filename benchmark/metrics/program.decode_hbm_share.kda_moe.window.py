"""Bytes a decode step of a ``kda_moe`` model must move (the delta-rule and
attention layers' weights, the norms and the head once; router, shared expert
and the touched held experts of every layer; the state and convolution tails
of every slot that holds a request, read and written; the keys and values of
the live tokens in the one attention layer: ``family.decode_step_bytes``) over
the chip's peak HBM bandwidth, over the device time of a decode step, percent;
on the traced window's own counts. The share of the whole step that bounds
every later claim in this cell."""

from benchmark import kda_moe

read = kda_moe.on_window(kda_moe.decode_step_share)
