"""Program span: time the engine's loop thread spent inside the calls that
hand work to the device or take its results back, over the traced window,
percent: ``engine.fetch`` (``np.asarray`` of sampled tokens),
``engine.prefill_chunk``, ``engine.decode_launch`` and ``engine.prefix_seed``
(a launch blocks while the device's queue is full, and its host-side dispatch
cannot be told from that wait from outside the runtime). Read from the same
``.xplane.pb`` as the device's operations. The rest of the window is the
scheduler's own Python. High with a busy chip: the loop keeps the chip fed and
waits for it; low with an idle chip: the chip waits for the host. Waiting that
moves from a fetch to a launch, or back, does not move the reading."""

from benchmark import scopes


def read(ctx):
    return scopes.span_share(ctx, scopes.DEVICE_CALL_SPANS)
