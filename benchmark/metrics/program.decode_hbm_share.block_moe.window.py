"""Bytes a block step of a ``block_moe`` model must move (attention, norms and
head once; the router and the touched experts of every layer; the keys and
values of the live slots, once a slot whatever the block holds:
``family.step_needed_bytes``) over the chip's peak HBM bandwidth, over the
device time of a step (``jit_block_step``), percent; on the traced window's
own counts. The share of the whole step that bounds every later claim in this
cell."""

from benchmark import block_moe

read = block_moe.on_window(block_moe.step_share)
