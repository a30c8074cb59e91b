"""Program span: tokens that reached a request over the forwards of live
slots (denoise and commit) in the traced window: 0.8 at four denoising steps a
block of 4, 1.33 at two; where a request ended inside a block, less. The place
an acceptance rate has where tokens are drafted. Summed over the window's
own ``engine.counts`` events (``benchmark/window_counts.py``)."""

from benchmark import block_moe

read = block_moe.on_window(block_moe.tokens_per_forward)
