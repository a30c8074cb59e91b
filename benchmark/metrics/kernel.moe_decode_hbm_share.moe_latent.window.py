"""``kernel.moe_decode_hbm_share.moe_latent``
with the traced window's own counts: its formula, called on
``window_counts.windowed(ctx)``, where the counters are the sums of the
``engine.counts`` events that start inside the window. Device time and counts
are then of the same launches: no probe, warm-up or ramp dilutes the reading."""

from benchmark import window_counts

read = window_counts.twin("kernel.moe_decode_hbm_share.moe_latent")
