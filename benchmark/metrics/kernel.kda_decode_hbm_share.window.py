"""Bytes the delta-rule layers of one decode step must move (each layer's
weights once; the float32 state and the convolution tails of every slot that
holds a request, read and written: ``decode_slot_steps`` over ``decode_steps``;
``family.kda_decode_bytes``) over the chip's peak HBM bandwidth, over the
step's device time under ``kda_mixer`` (input projection, decay, convolutions,
``kda_step``, norm, gate and output projection), percent; on the traced
window's own counts. The step's kernel walks every slot of the pool: what it
moves of a free slot is time and no need."""

from benchmark import kda_moe

read = kda_moe.on_window(kda_moe.kda_decode_share)
