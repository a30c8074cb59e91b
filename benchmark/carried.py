"""What the readers of a carried decode step's metrics share.

A prompt chunk's launch carries the pool's decode step (``llm/engine.py
_advance_admissions``): the pool's rows ride through ``jit_chunk_mid`` or
``jit_chunk_final`` beside the chunk's tokens. The program names what those
rows run alone with one more scope part, ``beside``, *in front of* the name it
has without them (``models/patterned.py _Rows.scope``: ``beside/kv_write``,
``beside/attn_core/window``, ``beside/attn_core/ssm_mixer/ssm_step``,
``beside/sampling``), so ``scopes.scope_of`` and ``moe_window.inner_of`` read
what they read before and the readers here split a chunk module's time by that
part. What multiplies both sets' rows as one matrix (the projections, the
feed-forwards, a final chunk's head) has no such part and stays the chunk's.

A launch of a program that takes the rows runs them whether or not a step
rides. The engine counts the launches that carried none by cause
(``decode_steps_dead_in_chunk:step_carried``, ``:runahead_full``, ``:no_slot``)
where it counts the ones that did (``decode_steps_in_chunk``), in the same
``engine.counts`` events (``benchmark/window_counts.py``).

Against a program with no such part or an engine with no such counter (the
parent of the PR that brought them) every function returns None."""

from __future__ import annotations

import bisect
import functools
import os

from benchmark import common, scopes, trace, window_counts

PART = "beside"
MODULES = ("jit_chunk_mid", "jit_chunk_final")
CARRIED, DEAD = "decode_steps_in_chunk", "decode_steps_dead_in_chunk"


def is_beside(op_name: str) -> bool:
    """Whether an operation's path holds the part ``beside``."""
    return PART in op_name and PART in op_name.split("/")


@functools.lru_cache(maxsize=2)
def _rows_time(path: str) -> "tuple | None":
    """(executions, {scope: seconds}) of the trace at ``path``: the window's
    executions of the chunk modules that hold any operation under ``beside``,
    and those operations' device seconds by the scope ``scopes.scope_of``
    books them to (None: under ``beside`` and no known name)."""
    parsed = scopes.read_xplane(path)
    lo, hi = parsed["window"]
    runs = [(a, b) for a, b, name in parsed["modules"] if name in MODULES and a >= lo and b <= hi]
    starts = [a for a, _ in runs]
    held, seconds = set(), {}
    for start, end, _, op_name in parsed["ops"]:
        if not is_beside(op_name):
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or end > runs[i][1] + 1e-9:
            continue
        held.add(i)
        scope = scopes.scope_of(op_name)
        seconds[scope] = seconds.get(scope, 0.0) + (end - start)
    return (len(held), seconds) if held else None


def rows_time(ctx: dict) -> "tuple | None":
    """``_rows_time`` of this run's trace, or None where there is no trace or
    no operation of it lies under ``beside``."""
    trace_dir = os.path.join(common.ROOT, ".bench_out", ctx["cell"]["name"], "trace")
    try:
        return _rows_time(trace.find_xplane(trace_dir))
    except (FileNotFoundError, OSError):
        return None


def own_ms(ctx: dict, scope: "str | None" = None) -> "float | None":
    """Mean device milliseconds the rows' own operations take in one chunk
    launch that holds any: all of them, or those booked to ``scope``."""
    found = rows_time(ctx)
    if found is None:
        return None
    n, seconds = found
    total = sum(seconds.values()) if scope is None else seconds.get(scope, 0.0)
    return 1e3 * total / n if total else None


def dead_share(ctx: dict) -> "float | None":
    """Of the window's launches of a chunk program that takes the pool's
    rows, the share that carried no step, percent."""
    own = window_counts.window_counts(ctx)
    if own is None or not isinstance(own.get(DEAD), dict):
        return None
    dead = sum(own[DEAD].values())
    launches = dead + own.get(CARRIED, 0)
    return 100.0 * dead / launches if launches else None
