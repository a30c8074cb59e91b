"""The router and the touched experts' banks of every layer of one block step
of a ``block_moe`` model over the chip's peak HBM bandwidth, over the step's
device time under ``moe_ffn``, percent; on the traced window's own counts."""

from benchmark import block_moe

read = block_moe.on_window(block_moe.moe_step_share)
