"""Tracing: distributed task spans + app spans + chrome-trace export.

Reference: ``python/ray/util/tracing/tracing_helper.py`` wraps every task and
actor invocation in OpenTelemetry spans with W3C trace-context propagated
through the TaskSpec, so one trace follows a call across driver → GCS →
raylet → worker. Here, without an OTel dependency:

- every submission stamps ``trace_id``/``parent_span_id`` onto the TaskSpec
  (``worker.WorkerAPI`` reads :func:`current_context`), so nested submits and
  actor calls chain causally across processes;
- all three planes emit lifecycle spans into THIS module's bounded
  per-process ring buffer — head (``head.sched``), agent (``agent.lease`` /
  ``agent.dispatch`` / ``agent.actor_create``), worker (``task.exec`` with
  ``task.deserialize``/``task.store_returns`` children). Per-task span ids
  are DETERMINISTIC (``<task_id>:sched`` / ``:agent`` / ``:exec``) so planes
  stitch without shipping ids;
- rings ship to the head piggybacked on existing report traffic (agents'
  ``AgentReportBatch`` tick; worker flushers through the agent intercept) and
  merge in ``util.state.api.timeline()`` / ``/api/timeline``;
- always-on overhead is gated by sampling: every task's HEAD EVENTS stay
  trace-joinable (``task_events`` carries the trace ids), while lifecycle
  spans — head, agent, and worker — are recorded for 1-in-``trace_sample_n``
  tasks (deterministic by task id, so a sampled task gets its WHOLE chain).
  ``trace_sample_n=1`` records everything; ``0`` disables tracing.

App-level :func:`span` parents correctly under the executing task (context
propagation rides a :class:`contextvars.ContextVar`, so spans opened inside
asyncio actors — including across the ``run_in_executor`` hand-off the async
path uses — keep their parents).

One clock with the device: in a process that has already imported JAX,
:func:`span` and :func:`annotate` also enter a
``jax.profiler.TraceAnnotation``, so the same call site lands in the
profiler's ``.xplane.pb`` beside the device operations while a profiler
session runs (and costs a check of one flag while none does). A process that
has no JAX (controller, agents) never imports it from here. :func:`annotate`
is the hot-loop form: profiler only, never the ring; :func:`mark` is an
instant that carries numbers (the engine's ``engine.counts``).
"""

from __future__ import annotations

import contextvars
import itertools
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Callable, Optional

_DEFAULT_BUFFER = 4096

_spans: deque = deque()
_max_spans: Optional[int] = None  # resolved lazily (config/env)
_dropped = 0
_lock = threading.Lock()
_id_counter = itertools.count(1)
# (trace_id, span_id) of the innermost open app span / attached task context.
# A ContextVar (not a threading.local): asyncio tasks copy their context at
# creation, so concurrent coroutines of one async actor keep separate parent
# chains on a single loop thread — a plain thread-local would cross-wire them.
_current: "contextvars.ContextVar[Optional[tuple]]" = contextvars.ContextVar(
    "rtpu_trace_ctx", default=None
)
# Fallback provider for the executing TASK's context (worker_runtime
# registers one reading its _exec_ctx thread-local): app spans opened inside
# a task body parent under the task's exec span even when no enclosing app
# span set the ContextVar.
_context_provider: Optional[Callable[[], Optional[tuple]]] = None
_sample_n_cache: Optional[int] = None


def set_context_provider(fn: Optional[Callable[[], Optional[tuple]]]):
    """Register the task-execution context fallback (worker runtime)."""
    global _context_provider
    _context_provider = fn


# ------------------------------------------------------------ ids & context

# getpid() is a syscall — cache it (and refresh in forked children so two
# processes can't mint colliding ids from one cached pid).
_PID = os.getpid()
try:
    os.register_at_fork(
        after_in_child=lambda: globals().__setitem__("_PID", os.getpid())
    )
except AttributeError:  # platform without register_at_fork
    pass


def new_span_id() -> str:
    """Process-unique span id. ``time_ns`` alone collides for spans started
    in the same nanosecond across threads (and across processes started in
    the same tick); the pid + an atomic per-process counter make the id
    collision-free without an os.urandom syscall per span."""
    return f"{time.time_ns():x}-{_PID:x}-{next(_id_counter):x}"


def new_trace_id() -> str:
    return f"t{time.time_ns():x}{_PID:x}{next(_id_counter):x}"


def current_context() -> Optional[tuple]:
    """(trace_id, span_id) of the innermost open app span, else the
    executing task's exec-span context, else None. This is what the submit
    path stamps onto new TaskSpecs."""
    ctx = _current.get()
    if ctx is not None:
        return ctx
    if _context_provider is not None:
        return _context_provider()
    return None


def attach_context(ctx: Optional[tuple]):
    """Set the current (trace_id, span_id) pair; returns a token for
    :func:`detach_context`. Used by the async execution path (per-coroutine
    contexts) and by code that hops executors: capture with
    ``contextvars.copy_context()`` and run the hand-off under it, or attach
    the pair explicitly on the far side."""
    return _current.set(ctx)


def detach_context(token) -> None:
    _current.reset(token)


# ------------------------------------------------------------------ sampling

def trace_sample_n() -> int:
    """The sampling knob (config ``trace_sample_n`` / env
    ``RAY_TPU_TRACE_SAMPLE_N``): 0 disables tracing, 1 records every task's
    span chain, N records 1-in-N chains (head task_events stay
    trace-joinable for every task either way). Cached per process; tests
    reset via :func:`_reset_sampling`."""
    global _sample_n_cache
    if _sample_n_cache is None:
        env = os.environ.get("RAY_TPU_TRACE_SAMPLE_N")
        if env is not None:
            try:
                _sample_n_cache = max(0, int(env))
            except ValueError:
                _sample_n_cache = 16
        else:
            try:
                from ray_tpu._private.config import get_config

                _sample_n_cache = max(0, int(get_config().trace_sample_n))
            except Exception:  # noqa: BLE001 — env-only processes
                _sample_n_cache = 16
    return _sample_n_cache


def _reset_sampling() -> None:
    global _sample_n_cache, _max_spans
    _sample_n_cache = None
    _max_spans = None


def enabled() -> bool:
    return trace_sample_n() > 0


def sampled(task_id_bin: bytes, n: Optional[int] = None) -> bool:
    """Deterministic per-task sampling decision — every plane computes the
    same verdict from the task id, so a sampled task's chain is complete
    (head+agent+worker) instead of randomly holey."""
    if n is None:
        n = trace_sample_n()
    if n <= 0:
        return False
    if n == 1:
        return True
    # stable across processes (Python's hash() is salted per process)
    return int.from_bytes(task_id_bin[:8] or b"\0", "little") % n == 0


# ---------------------------------------------------------------- recording

def _buffer_cap() -> int:
    global _max_spans
    if _max_spans is None:
        env = os.environ.get("RAY_TPU_TRACE_BUFFER_SIZE")
        if env is not None:
            try:
                _max_spans = max(16, int(env))
            except ValueError:
                _max_spans = _DEFAULT_BUFFER
        else:
            try:
                from ray_tpu._private.config import get_config

                _max_spans = max(16, int(get_config().trace_buffer_size))
            except Exception:  # noqa: BLE001
                _max_spans = _DEFAULT_BUFFER
    return _max_spans


def _append(rec: dict) -> None:
    global _dropped
    cap = _buffer_cap()
    with _lock:
        while len(_spans) >= cap:
            _spans.popleft()
            _dropped += 1
        _spans.append(rec)


def record_span(
    name: str,
    start: float,
    end: float,
    *,
    trace_id: Optional[str] = None,
    span_id: Optional[str] = None,
    parent_id: Optional[str] = None,
    plane: Optional[str] = None,
    task_id: Optional[str] = None,
    node: Optional[str] = None,
    **attributes,
) -> Optional[dict]:
    """Record one finished lifecycle span into the process ring buffer.
    ``start``/``end`` are wall-clock seconds; ids default to fresh ones.
    Returns None without recording when tracing is disabled."""
    if not enabled():
        return None
    rec = {
        "name": name,
        "span_id": span_id or new_span_id(),
        "parent_id": parent_id,
        "trace_id": trace_id,
        "plane": plane,
        "task_id": task_id,
        "node": node,
        "pid": _PID,
        "start": start,
        "end": end,
        "attributes": attributes,
    }
    _append(rec)
    return rec


_NO_ANNOTATION = nullcontext()


def annotate(name: str, **attributes):
    """Profiler-only span for hot loops (per loop pass, per chunk, per
    launch): a ``jax.profiler.TraceAnnotation`` where this process has
    imported JAX, else nothing. Never recorded in the ring, not gated by
    ``trace_sample_n``: a profiler session is its own on switch."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_ANNOTATION
    return jax.profiler.TraceAnnotation(name, **attributes)


def mark(name: str, **attributes) -> None:
    """An instant on the profiler's clock: :func:`annotate` entered and left
    at once, so the event holds ``attributes`` (as its stats in the
    ``.xplane.pb``, each under its own name) and no time. Profiler only,
    never the ring; nothing where no session runs or the process has no JAX."""
    with annotate(name, **attributes):
        pass


@contextmanager
def span(name: str, **attributes):
    """App-level span: parents under the innermost open span, else the
    executing task's exec span, else roots a fresh trace. With tracing
    disabled nothing is recorded (``trace_sample_n=0`` means no recording,
    no buffering, no shipping); the profiler annotation of :func:`annotate`
    is entered either way."""
    if not enabled():
        with annotate(name, **attributes):
            yield
        return
    parent_ctx = current_context()
    trace_id = parent_ctx[0] if parent_ctx else new_trace_id()
    parent_id = parent_ctx[1] if parent_ctx else None
    sid = new_span_id()
    token = _current.set((trace_id, sid))
    start = time.time()
    try:
        with annotate(name, **attributes):
            yield
    finally:
        _current.reset(token)
        record_span(
            name,
            start,
            time.time(),
            trace_id=trace_id,
            span_id=sid,
            parent_id=parent_id,
            plane="app",
            **attributes,
        )


def get_spans() -> list[dict]:
    with _lock:
        return list(_spans)


def drain_spans() -> list[dict]:
    """Pop every buffered span (the ship path: the per-process flusher
    drains the ring and forwards to the head)."""
    with _lock:
        out = list(_spans)
        _spans.clear()
    return out


def requeue_spans(spans: list[dict]) -> None:
    """Put drained spans back (ship failed — retry next tick). Bounded:
    excess beyond the ring cap is counted into ``dropped_spans``."""
    global _dropped
    cap = _buffer_cap()
    with _lock:
        restored = 0
        for rec in reversed(spans):
            if len(_spans) >= cap:
                _dropped += len(spans) - restored
                break
            _spans.appendleft(rec)
            restored += 1


def dropped_spans() -> int:
    with _lock:
        return _dropped


def clear():
    global _dropped
    with _lock:
        _spans.clear()
        _dropped = 0


# ------------------------------------------------------------------- export

def spans_to_chrome(spans: list[dict], pid_of=None) -> list[dict]:
    """Render span records as chrome-trace complete events. ``pid_of(rec)``
    maps a span to a chrome pid (e.g. a node index); default is the
    recording process's pid."""
    out = []
    for s in spans:
        out.append(
            {
                "name": s["name"],
                "cat": s.get("plane") or "span",
                "ph": "X",
                "ts": s["start"] * 1e6,
                "dur": max((s["end"] - s["start"]) * 1e6, 1),
                "pid": pid_of(s) if pid_of is not None else s.get("pid", 0),
                "tid": s.get("pid", 0),
                "args": {
                    "trace_id": s.get("trace_id"),
                    "span_id": s.get("span_id"),
                    "parent_id": s.get("parent_id"),
                    "task_id": s.get("task_id"),
                    "node": s.get("node"),
                    "plane": s.get("plane"),
                    **(s.get("attributes") or {}),
                },
            }
        )
    return out


def export_chrome_trace(path: Optional[str] = None, include_tasks: bool = True) -> list[dict]:
    """The cluster-merged timeline (task events + every plane's spans) as
    one chrome trace, plus any LOCAL spans the merged view doesn't carry
    yet — the head's own ring rides ``timeline()`` already (dedup by
    span_id keeps it single), while a client driver's ring never ships
    and would otherwise vanish from the export."""
    trace: list = []
    if include_tasks:
        try:
            from ray_tpu.util.state.api import timeline

            trace = timeline()
        except Exception:  # noqa: BLE001 — no cluster attached
            trace = []
    seen = {
        e.get("args", {}).get("span_id")
        for e in trace
        if isinstance(e.get("args"), dict)
    }
    trace.extend(
        spans_to_chrome(
            [s for s in get_spans() if s.get("span_id") not in seen]
        )
    )
    if path:
        import json

        with open(path, "w") as f:
            json.dump(trace, f)
    return trace
