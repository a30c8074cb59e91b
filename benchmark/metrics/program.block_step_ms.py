"""Device trace: mean device time of one execution of the step of a model that
generates by blocks (``jit_block_step``: a forward of a block a slot, the
choice of what to unmask, the next block state)."""

from benchmark import block_moe

read = block_moe.step_ms
