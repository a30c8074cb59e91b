"""Program counter: tokens the decode program's steps gave to requests
(``tokens_generated`` less ``first_tokens``, which final prefill chunks
sample) over ``decode_steps`` times the slots, percent. The rest is rows of
dead slots and run-ahead tokens decoded for a request that had finished
(``tokens_discarded``). Cumulative since the engine started: warm-up and
ramp are in it."""

from benchmark import scopes


def read(ctx):
    made, first, steps = (scopes.counter(ctx, k)
                          for k in ("tokens_generated", "first_tokens", "decode_steps"))
    slots = scopes.engine_stats(ctx).get("max_num_seqs")
    if made is None or first is None or not steps or not slots:
        return None
    return 100.0 * (made - first) / (steps * slots)
