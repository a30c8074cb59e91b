"""Program span: median of the engine's ``queue_wait_s`` histogram (a
request submitted to its admission into a slot), milliseconds. Cumulative
since the engine started: warm-up and ramp requests are in it."""

from benchmark import scopes


def read(ctx):
    return scopes.latency_quantile_ms(ctx, "queue_wait_s", 0.5)
