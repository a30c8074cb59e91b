"""Program span: median of the engine's ``token_gap_s`` histogram, per request
(finished - first token) / (tokens - 1), milliseconds: a decode step plus the
prefill chunks interleaved with it. The median, because the few requests in
flight while the profiler stops, or while a program compiles, pull the mean
by a quarter. Cumulative since the engine started: warm-up and ramp requests
are in it."""

from benchmark import scopes


def read(ctx):
    return scopes.latency_quantile_ms(ctx, "token_gap_s", 0.5)
