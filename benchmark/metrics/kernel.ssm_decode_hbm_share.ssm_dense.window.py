"""Bytes the recurrence of one decode step must move (the float32 state of
every slot that holds a request, in each of the 36 mamba layers, once in and
once out: ``decode_slot_steps`` over ``decode_steps``;
``family.ssm_state_bytes``) over the chip's peak HBM bandwidth, over the
step's device time under ``ssm_step`` (the fused step's kernel and what
prepares its operands), percent; on the traced window's own counts. The
kernel walks every slot of the pool: what it moves of a free slot is time and
no need."""

from benchmark import ssm_gqa_dense

read = ssm_gqa_dense.on_window(ssm_gqa_dense.ssm_step_share)
