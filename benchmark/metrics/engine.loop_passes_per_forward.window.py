"""Program counter: layer-stack passes the engine's programs ran
(``loop_stack_passes``) over the forwards that reported them
(``loop_forwards``) in the traced window: the model's ``total_ut_steps`` while
every pass is run; a later change that skips a pass shows here. Summed over
the window's own ``engine.counts`` events (``benchmark/window_counts.py``)."""

from benchmark import looped

read = looped.on_window(looped.passes_per_forward)
