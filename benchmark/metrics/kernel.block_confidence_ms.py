"""Device milliseconds of one block step (``jit_block_step``) under
``sampling/confidence``: the candidates, the greedy token and the softmax's
maximum and sum over the vocabulary, for every position of every slot's
block (256 rows of 151,936 in the cell)."""

from benchmark import block_moe

read = block_moe.confidence_ms
