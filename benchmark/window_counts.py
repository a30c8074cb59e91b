"""The traced window's own counts.

The engine grows every counter in one method (``llm/engine.py JaxEngine._count``)
and writes the growth, in the same call, as an instant profiler event
``engine.counts`` whose attributes are the deltas under the counters' own
names (``decode_steps``, ``moe_experts_touched:decode``, ...). In the
``.xplane.pb`` the attributes are the stats of the event itself
(``XEvent.stats``: ``jax.profiler.ProfileData`` shows them as ``(name, whole
number)`` pairs, on the clock ``benchmark/scopes.py`` reads the operations on;
the scope path, by contrast, is a stat of the event's *metadata*). So the
counts of a traced window are the sums of the events that start inside it:
matched launch for launch with the device time of the same window, where
``stats_at_end`` is cumulative since the engine started and holds the probe,
the warm-up and the ramp beside it.

``windowed(ctx)`` is the reader's context with ``stats_at_end["counters"]``
replaced by those sums, shaped as ``_counters_view`` shapes them, so that a
window-own metric is its cumulative twin's formula called on that context
(``twin``): no formula is written twice.

The events are ``engine.*`` host events of no length to speak of (a few
microseconds), so ``scopes.read_xplane`` lists them among its spans. No reader
there counts such a span: ``span_share`` takes the spans it names,
``uncovered_idle`` covers gaps with their union, and an instant inside a
stage's span adds nothing to either. Against a trace that holds no such event
(the parent of the PR that brought them) every function returns None."""

from __future__ import annotations

import functools
import os

from benchmark import common, scopes, trace

COUNTS_EVENT = "engine.counts"


@functools.lru_cache(maxsize=2)
def read_events(path: str) -> tuple:
    """((start in seconds on the trace's clock, {counter name: delta}), ...)
    of every ``engine.counts`` event on any host thread, sorted."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == COUNTS_EVENT:
                    events.append((ev.start_ns * 1e-9, dict(ev.stats)))
    events.sort(key=lambda e: e[0])
    return tuple(events)


def sums(events, lo: float, hi: float) -> "dict | None":
    """Each counter's growth over the events that start in ``[lo, hi]``, or
    None where there is no such event at all."""
    out: dict = {}
    for start, deltas in events:
        if lo <= start <= hi:
            for name, value in deltas.items():
                out[name] = out.get(name, 0) + value
    return out or None


def view(flat: dict, like: "dict | None" = None) -> dict:
    """``flat`` (``requests_failed:decode`` -> n) with each labelled family as
    a dict, as ``JaxEngine._counters_view`` shapes ``get_stats()["counters"]``;
    a counter of ``like`` (the cumulative view) that no event named reads 0."""
    out: dict = {}
    for name, value in (like or {}).items():
        out[name] = dict.fromkeys(value, 0) if isinstance(value, dict) else 0
    for name, value in flat.items():
        family, _, label = name.partition(":")
        if label:
            out.setdefault(family, {})[label] = value
        else:
            out[name] = value
    return out


def window_counts(ctx: dict) -> "dict | None":
    """The counters' growth inside this run's traced window, shaped as the
    cumulative ones are; None without a trace or without the events."""
    parsed = scopes.trace_of(ctx)
    if parsed is None:
        return None
    trace_dir = os.path.join(common.ROOT, ".bench_out", ctx["cell"]["name"], "trace")
    flat = sums(read_events(trace.find_xplane(trace_dir)), *parsed["window"])
    if flat is None:
        return None
    return view(flat, like=scopes.engine_stats(ctx).get("counters"))


def windowed(ctx: dict) -> "dict | None":
    """``ctx`` with ``stats_at_end["counters"]`` replaced by the window's own
    (``max_num_seqs``, ``pools`` and the histograms pass through); the
    caller's context is left as it was. None where ``window_counts`` is."""
    counts = window_counts(ctx)
    if counts is None:
        return None
    stats = dict(scopes.engine_stats(ctx), counters=counts)
    return dict(ctx, extra=dict(ctx["extra"], stats_at_end=stats))


def twin(metric: str):
    """The ``read`` of a window-own metric: the reader of ``metric`` (its
    cumulative twin, ``benchmark/metrics/<metric>.py``) on ``windowed(ctx)``."""
    def read(ctx):
        own = windowed(ctx)
        return None if own is None else common.load_reader(metric)(own)
    return read
