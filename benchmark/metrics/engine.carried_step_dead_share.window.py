"""Program span: of the traced window's launches of a chunk program that takes
the pool's decode rows, the share that carried no step: 100 x the three
``decode_steps_dead_in_chunk`` causes over them plus ``decode_steps_in_chunk``,
summed over the window's own ``engine.counts`` events
(``benchmark/carried.py``, ``benchmark/window_counts.py``). Such a launch runs
the rows' kernels, scatter and sampler with no row live, so this share x
``program.carried_step_own_ms`` x the launches is device time nobody reads.
None on a trace whose engine has no such counter, or whose window launched no
such program (a latent pool)."""

from benchmark import carried

read = carried.dead_share
