"""Program span: slots that hold a request in a decode step of the traced
window: ``decode_slot_steps`` over ``decode_steps``, both summed over the
window's own ``engine.counts`` events (``benchmark/window_counts.py``). The
rows whose state, keys and values a step of *that* window moves: what the
window's device time has to be divided by, where ``stats_at_end`` gives a mean
since the engine started that the probe's and the warm-up's steps at one or
two live rows pull down."""

from benchmark import ssm_latent_moe, window_counts


def read(ctx):
    own = window_counts.windowed(ctx)
    return None if own is None else ssm_latent_moe.active_slots_per_step(own)
