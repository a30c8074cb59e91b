"""The live slots' keys and values of every layer (2,048 bytes a position and
layer at four key-value heads of 128), once a slot, over the chip's peak HBM
bandwidth, over a block step's device time under ``attn_core/block`` (the
decode kernel with the block's four queries folded beside each key-value
head's query heads), percent; on the traced window's own counts. A read
that streamed a stripe once a query would stand at a quarter of this."""

from benchmark import block_moe

read = block_moe.on_window(block_moe.attention_share)
