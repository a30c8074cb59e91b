"""Program span: median of the engine's ``prefill_s`` histogram (a request
admitted into a slot to its first token on the host), milliseconds.
Cumulative since the engine started: warm-up and ramp requests are in it."""

from benchmark import scopes


def read(ctx):
    return scopes.latency_quantile_ms(ctx, "prefill_s", 0.5)
