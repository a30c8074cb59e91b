"""Program counter: prompt tokens served from the prefix cache
(``prompt_tokens_from_prefix``) over all prompt tokens admitted
(``prompt_tokens``), percent. Cumulative since the engine started, as
``stats_at_end`` has the two: the probe, the warm-up and the documents' own
misses during set-up are in it beside the window (the traffic kind checks the
window alone)."""

from benchmark import moe_latent


def read(ctx):
    return moe_latent.prefix_token_share(ctx)
