"""Program span: of the traced window's forwards of live slots, the share that
unmasked nothing and only wrote a clean block's keys and values (a fifth at
four denoising steps, a third at two), percent: what joining the commit to
the next block's first denoise forward would take off. Summed over the
window's own ``engine.counts`` events."""

from benchmark import block_moe

read = block_moe.on_window(block_moe.commit_share)
