"""Worker-side runtime: the task execution loop.

Analog of the reference's worker path: ``worker.main_loop``
(``python/ray/_private/worker.py:964``) → ``CoreWorker.run_task_loop``
(``_raylet.pyx:3050``) → ``CoreWorkerProcess::RunTaskExecutionLoop``
(``core_worker_process.cc:103``). One runtime per worker process (or thread in
thread mode): receives ``ExecuteTask`` messages, deserializes args (reading
large payloads zero-copy out of shared memory), runs the function, and stores
returns — small results inline through the control plane, large results as new
shared-memory segments (``PutInLocalPlasmaStore`` analog,
``core_worker.cc:1565``). Actor instances live in this process for their
lifetime; ordered execution and ``max_concurrency`` mirror the reference's
``ActorSchedulingQueue`` / ``ConcurrencyGroupManager``.
"""

from __future__ import annotations

import asyncio
import functools
import itertools
import os
import queue
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

import cloudpickle

from ray_tpu._private import locktrace
from ray_tpu._private import protocol as P
from ray_tpu._private.ids import ObjectID, WorkerID
from ray_tpu._private.serialization import SerializationContext, SerializedObject
from ray_tpu._private.task_spec import TaskSpec, TaskType
from ray_tpu.exceptions import TaskError

_INLINE_LIMIT_ENV = "RAY_TPU_MAX_INLINE_OBJECT_SIZE"


class ConnEpochBumped(OSError):
    """The controller connection was re-established (client pump re-dial
    after a head restart, or the node agent's ``HeadRestarted`` notice for
    relayed workers) while this request was in flight: its reply died with
    the old head. The retry envelope replays reads and idempotent writes;
    once-only ops surface ``HeadRestartedError``."""


class StreamConsumerGone(Exception):
    """The consumer of a streaming generator freed its ObjectRefGenerator
    while the (backpressured) producer was still running."""

# Per-thread execution context: which actor's task is running on this thread.
# Tasks execute wholly on one thread (worker loop thread, actor pool thread,
# or thread-mode worker thread), so a threading.local is exact — unlike
# process-global state, which is wrong for in-process (thread-mode) actors
# and concurrent actor pools.
_exec_ctx = threading.local()


def current_actor_id() -> Optional[bytes]:
    """Binary ActorID of the actor whose task is executing on this thread."""
    return getattr(_exec_ctx, "actor_id", None)


def current_exec_tenant() -> Optional[str]:
    """Tenant of the task executing on THIS thread (None outside task
    execution). Nested submits inherit it, so a tenant's whole task tree
    bills to one fair-share queue group — the intra-tenant FIFO interleave
    the scheduler preserves is meaningless if children land elsewhere."""
    return getattr(_exec_ctx, "tenant", None)


def current_exec_priority() -> Optional[int]:
    """Priority of the task executing on THIS thread (inherited by nested
    submits the same way as the tenant)."""
    return getattr(_exec_ctx, "priority", None)


# Tracing rides the same execution context: nested submits inherit the
# executing task's (trace_id, exec span id) exactly like tenant/priority,
# so one driver call's whole task tree stitches into one trace.
_tracing_mod = None


def _trace_mod():
    """Lazy tracing import (ray_tpu.util's package __init__ pulls API
    modules — importing it at this module's import time would cycle), plus
    one-time registration of the task-context provider so app spans opened
    inside a task body parent under the task's exec span."""
    global _tracing_mod
    if _tracing_mod is None:
        from ray_tpu.util import tracing

        tracing.set_context_provider(_task_trace_context)
        _tracing_mod = tracing
    return _tracing_mod


def _task_trace_context() -> Optional[tuple]:
    t = getattr(_exec_ctx, "trace_id", None)
    s = getattr(_exec_ctx, "span_id", None)
    return (t, s) if t and s else None


def current_exec_trace() -> Optional[tuple]:
    """(trace_id, exec span id) of the task executing on THIS thread."""
    return _task_trace_context()


def _obs_flush_loop(runtime: "WorkerRuntime") -> None:
    """Periodic observability flusher (module-level like the coalescer's
    loop thread: its only runtime interaction is the flush call, which
    ships through the ordinary controller-request path)."""
    while not runtime._obs_stop.wait(timeout=runtime._obs_interval_s):
        runtime._flush_observability()
    runtime._flush_observability()  # final report before teardown


# Actors hosted in THIS process that are eligible for same-process inline
# execution (sync, max_concurrency=1): actor_id binary -> hosting runtime.
# The inline fast path (WorkerAPI submit) executes eligible calls on the
# caller's thread under the actor's execution lock, with zero thread hops
# (reference shape: core_worker submits to a same-process actor without a
# raylet round trip). Thread mode has many runtimes in one process; process
# mode has one per worker process — both index here.
_inline_hosts: dict[bytes, "WorkerRuntime"] = {}
_inline_hosts_lock = threading.Lock()


def inline_host(actor_bin: bytes) -> Optional["WorkerRuntime"]:
    """The runtime hosting this actor in the calling process, if inline-
    eligible (sync max_concurrency=1) — None otherwise."""
    return _inline_hosts.get(actor_bin)


# Actor methods ("ClassName.method", spec.name) observed performing a
# BLOCKING runtime wait mid-execution: never run these inline. A caller
# thread stuck inside one cannot submit the peer work the method is waiting
# for (collective rendezvous, cross-actor barriers) — the queued paths
# overlap such calls on executor threads, the inline path would serialize
# them into a deadlock. Flagged from the runtime's own blocking primitives
# (collective _run, long get/wait), so the first queued execution marks the
# method before the inline gate ever considers it.
_noinline_methods: set[str] = set()


def note_execution_blocked():
    """Flag the actor method executing on THIS thread (if any) as blocking
    — called from runtime wait primitives (get/wait/collective)."""
    key = getattr(_exec_ctx, "method_key", None)
    if key is not None:
        _noinline_methods.add(key)


def method_blocks(name: str) -> bool:
    return name in _noinline_methods


class InProcessChannel:
    """Duplex in-process channel with the multiprocessing.Connection API
    subset (send/recv/close) — used for thread-mode workers."""

    def __init__(self, inbox: "queue.Queue", outbox: "queue.Queue"):
        self._inbox = inbox
        self._outbox = outbox
        self._closed = False

    @classmethod
    def pair(cls):
        a, b = queue.Queue(), queue.Queue()
        return cls(a, b), cls(b, a)

    def send(self, msg):
        if self._closed:
            raise OSError("channel closed")
        self._outbox.put(msg)

    def recv(self):
        msg = self._inbox.get()
        if msg is _CLOSE:
            raise EOFError
        return msg

    def close(self):
        self._closed = True
        self._inbox.put(_CLOSE)
        self._outbox.put(_CLOSE)


_CLOSE = object()


class _DirectTask:
    """A direct actor call routed through the normal execution machinery;
    the reply goes back on the caller's connection, not to the head."""

    __slots__ = ("spec", "resolved_args", "direct_reply", "req_id")

    def __init__(self, spec, resolved_args, direct_reply, req_id):
        self.spec = spec
        self.resolved_args = resolved_args
        self.direct_reply = direct_reply
        self.req_id = req_id


class _DirectReplyConn:
    """Send-side of one caller's direct connection (serialized sends)."""

    __slots__ = ("conn", "lock")

    def __init__(self, conn):
        self.conn = conn
        self.lock = threading.Lock()

    def send(self, msg):
        with self.lock:
            self.conn.send(msg)


def batch_knobs() -> tuple[float, int]:
    """(window_seconds, max_items) for the client-side submit coalescer.
    Config-backed with env overrides (worker processes inherit only the
    environment). window <= 0 disables coalescing."""
    window_ms: Optional[float] = None
    max_items: Optional[int] = None
    env_w = os.environ.get("RAY_TPU_SUBMIT_BATCH_WINDOW_MS")
    env_m = os.environ.get("RAY_TPU_SUBMIT_BATCH_MAX")
    try:
        if env_w is not None:
            window_ms = float(env_w)
        if env_m is not None:
            max_items = int(env_m)
    except (TypeError, ValueError):
        # a typo'd deployment env must degrade to the defaults, not crash
        # every worker/driver at startup
        window_ms, max_items = None, None
    if window_ms is None or max_items is None:
        try:
            from ray_tpu._private.config import get_config

            cfg = get_config()
            if window_ms is None:
                window_ms = cfg.submit_batch_window_ms
            if max_items is None:
                max_items = cfg.submit_batch_max
        except Exception:  # noqa: BLE001 — env-only processes
            window_ms = 2.0 if window_ms is None else window_ms
            max_items = 256 if max_items is None else max_items
    return max(0.0, window_ms) / 1000.0, max(1, max_items)


class SubmitCoalescer:
    """Client-side control-plane batcher (the tentpole of the batched-wire-
    ops PR): task submissions and fire-and-forget ref traffic queue here and
    ride ONE ``submit_batch`` request per flush instead of one request each.

    Ordering contract: items flush in FIFO order, and every SYNCHRONOUS
    controller interaction (get/wait/any request op) flushes the buffer
    first — so program-order visibility is preserved and ``get()`` never
    waits out the window. Flushes are serialized (``_flush_lock``), so
    batches hit the wire in swap order even when the window thread and a
    sync caller race.

    Reliability: ``flush_fn(items)`` owns delivery + retry. The controller
    applies a batch atomically w.r.t. chaos injection and skips
    already-applied specs, so retrying the identical batch is safe
    (idempotent replay — no lost spec, no double dispatch)."""

    def __init__(self, flush_fn, window_s: float, max_items: int, name: str = "submit-coalescer"):
        self._flush_fn = flush_fn
        self.window_s = window_s
        self.max_items = max_items
        self._name = name
        self._items: list = []
        self._lock = threading.Lock()
        self._flush_lock = threading.Lock()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._thread_lock = threading.Lock()
        self._shutdown = False
        # optional owner-supplied thread starter (() -> started Thread): the
        # owner keeps the flusher thread's target among its OWN methods, so
        # thread-root analyses (locktrace dumps, tpulint shared-state) see it
        self.thread_starter = None

    @property
    def enabled(self) -> bool:
        return self.window_s > 0 and not self._shutdown

    def queue(self, item) -> None:
        """Append one batch item; flushes inline past the size cap
        (submitter backpressure bounds buffer memory)."""
        with self._lock:
            self._items.append(item)
            n = len(self._items)
        self._ensure_thread()
        if n >= self.max_items:
            self.flush()
        else:
            self._wake.set()

    def pending(self) -> int:
        return len(self._items)

    def flush(self) -> None:
        """Drain and deliver everything queued (called from sync paths and
        the window thread; FIFO across concurrent flushers). Always invokes
        ``flush_fn`` — even with zero queued items — because the flush
        function may own side queues of its own (the worker runtime drains
        its GC free queue into the same batch)."""
        with self._flush_lock:
            with self._lock:
                items, self._items = self._items, []
            self._flush_fn(items)

    def _ensure_thread(self):
        if self._thread is not None and self._thread.is_alive():
            return
        with self._thread_lock:
            if self._thread is None or not self._thread.is_alive():
                if self.thread_starter is not None:
                    self._thread = self.thread_starter()
                    return
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name=self._name
                )
                self._thread.start()

    def _loop(self):
        while not self._shutdown:
            # short poll (matching the old free flusher's cadence): GC frees
            # are queued from __del__ paths that can never set the wake
            # event, so the loop must look for them on its own beat
            self._wake.wait(timeout=0.05)
            self._wake.clear()
            if self._shutdown:
                break
            if self.window_s:
                # coalescing beat: submissions arrive in bursts; one extra
                # breath batches the whole burst into a single request
                time.sleep(self.window_s)
            try:
                self.flush()
            except Exception:  # noqa: BLE001 — sync paths re-raise their own
                if not self._shutdown:
                    traceback.print_exc()
        try:
            self.flush()
        except Exception:  # noqa: BLE001
            pass

    def shutdown(self):
        """Final flush, then stop the window thread."""
        self._shutdown = True
        self._wake.set()
        locktrace.join_if_alive(self._thread, timeout=1.0)
        try:
            self.flush()
        except Exception:  # noqa: BLE001 — teardown must not raise
            pass


class WorkerRuntime:
    def __init__(
        self,
        worker_id: WorkerID,
        conn,
        in_process: bool = False,
        authkey: Optional[bytes] = None,
    ):
        self.worker_id = worker_id
        self.conn = conn
        self.in_process = in_process
        self.authkey = authkey
        # set by worker_main when the process cannot provide what it was
        # spawned for; _invoke raises it for every task and actor creation
        self.startup_error: Optional[BaseException] = None
        # direct actor-call listener (started in run() for process workers)
        self._direct_listener = None
        self.direct_address: Optional[str] = None
        self.serialization = SerializationContext()
        self.actors: dict[bytes, Any] = {}  # actor_id binary -> instance
        self.actor_pools: dict[bytes, ThreadPoolExecutor] = {}
        self.actor_loops: dict[bytes, asyncio.AbstractEventLoop] = {}
        # async actors: FIFO admission lock per actor (see _execute_async) —
        # created lazily ON the actor's loop, keyed like actor_loops
        self._async_admission: dict[bytes, asyncio.Lock] = {}
        # max_concurrency=1 sync actors: every execution path (task pool AND
        # inline direct calls) serializes on this per-actor lock, so direct
        # calls can run on the caller-connection reader thread — one fewer
        # context switch per call — without breaking the concurrency contract
        self.actor_exec_locks: dict[bytes, threading.Lock] = {}
        self._get_replies: dict[int, Any] = {}
        self._get_cv = locktrace.register_lock(
            "worker.get_cv", threading.Condition()
        )
        self._req_counter = itertools.count(1)
        self._send_lock = locktrace.register_lock(
            "worker.send_lock", threading.Lock()
        )
        self._put_counter = itertools.count(1)
        self._shm_client = None
        self._shm_client_lock = threading.Lock()
        self._shutdown = False
        self.max_inline = int(os.environ.get(_INLINE_LIMIT_ENV, 100 * 1024))
        # direct-call replies above this ride shared memory instead of the
        # reply frame (single-host only; see _store_returns). Env override
        # mirrors the config field direct_inline_max_bytes.
        try:
            from ray_tpu._private.config import get_config

            _default_dimb = get_config().direct_inline_max_bytes
        except Exception:  # noqa: BLE001 — env-only processes
            _default_dimb = 8 * 1024**2
        self.direct_inline_max = int(
            os.environ.get("RAY_TPU_DIRECT_INLINE_MAX_BYTES", _default_dimb)
        )
        # cross-node transfer accounting (tests assert the zero-re-transfer
        # property through counters, not timing)
        self.transfer_chunks_pulled = 0
        # pull-into-arena kill switch (config.pull_into_arena; env override
        # for workers that inherit only the environment)
        try:
            from ray_tpu._private.config import get_config as _get_config

            _arena_pull = _get_config().pull_into_arena
        except Exception:  # noqa: BLE001 — env-only processes
            _arena_pull = True
        self._arena_pull_enabled = os.environ.get(
            "RAY_TPU_PULL_INTO_ARENA", "1" if _arena_pull else "0"
        ).lower() not in ("0", "false", "no", "off")
        self.current_task_name: Optional[str] = None
        # The reader loop must never block on task execution (tasks make
        # controller calls — get/submit — whose replies arrive on the reader).
        self._task_pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="task-exec")
        # Queued-but-unstarted normal tasks (pipelined dispatches): task_id
        # binary -> Future. The controller may steal these back for idle
        # workers (StealTasks); a Future that cancels cleanly never started.
        # _pf_lock serializes reader inserts against the executor's pop at
        # execution start (a lost race would pin an entry forever).
        self._pending_futures: dict = {}
        self._pf_lock = threading.Lock()
        # worker-side rpc chaos (lazily parsed from env)
        self._chaos_table: Optional[dict] = None
        import random as _random

        self._chaos_rng = _random.Random(
            int.from_bytes(worker_id.binary()[:4], "little")
        )
        # Observability report loop (process workers only; thread-mode
        # runtimes share the driver process's span ring and metrics
        # registry, which the head reads directly): every tick the worker
        # drains its span ring and snapshots its util.metrics registry into
        # ONE report_observability push. On agent nodes the agent
        # intercepts the push locally and piggybacks the node's merged
        # payload on its report-batch tick — zero extra head round trips.
        try:
            from ray_tpu._private.config import get_config as _gc

            _obs_ms = float(
                os.environ.get(
                    "RAY_TPU_METRICS_REPORT_INTERVAL_MS",
                    _gc().metrics_report_interval_ms,
                )
            )
        except Exception:  # noqa: BLE001 — env-only processes
            _obs_ms = 2000.0
        self._obs_interval_s = max(0.05, _obs_ms / 1000.0)
        self._obs_stop = threading.Event()
        self._obs_thread: Optional[threading.Thread] = None
        # client drivers attach to a foreign cluster: reply pump only, no
        # task execution, and never os._exit on disconnect
        self.client_mode = False
        # (target, family, authkey) for client reconnect after head restart
        self.client_target = None
        # bumped on reconnect: in-flight waiters of the old epoch fail fast
        self._conn_epoch = 0
        # async ref-release queue (see queue_free)
        self._free_queue: list = []
        # Client-side submit coalescer (batched wire ops): submissions and
        # add_ref bursts buffer here and ride one submit_batch Request per
        # flush; the flusher also drains _free_queue into the same batch, so
        # a GC burst costs one Request instead of one FreeObjects frame per
        # flush window. Disabled for in-process (thread-mode) runtimes — the
        # driver API owns batching there.
        window_s, max_items = batch_knobs()
        self._coalescer = SubmitCoalescer(
            self._deliver_batch,
            window_s if not in_process else 0.0,
            max_items,
            name=f"submit-coalescer-{worker_id.hex()[:8]}",
        )
        self._coalescer.thread_starter = self._start_coalescer_thread

    # ------------------------------------------------------------- transport

    def _maybe_inject_failure(self, op: str):
        """Worker-side RPC chaos (reference: ``rpc_chaos.h:23`` covers EVERY
        rpc channel, not just GCS ops — this is the worker↔controller and
        plasma analog of the controller's ``testing_rpc_failure``). Config:
        env ``RAY_TPU_WORKER_RPC_FAILURE="op=prob,op=prob"``."""
        spec = os.environ.get("RAY_TPU_WORKER_RPC_FAILURE")
        if not spec:
            return
        if self._chaos_table is None:
            # a typo'd channel/op name silently never injects — fail loud
            # (valid keys: every controller request op + the worker-local
            # object channels; kept code-true by tpulint wire-conformance)
            self._chaos_table = P.parse_worker_chaos_table(spec)
        prob = self._chaos_table.get(op)
        if prob and self._chaos_rng.random() < prob:
            raise OSError(
                f"injected worker rpc failure for {op!r} "
                f"(RAY_TPU_WORKER_RPC_FAILURE)"
            )

    def _send(self, msg):
        with self._send_lock:
            self.conn.send(msg)

    def queue_free(self, object_id) -> None:
        """Asynchronous ref release (called from ObjectRef.__del__ — must
        never touch the connection OR any non-reentrant lock: GC can
        interrupt a thread that is already inside a locked region, and a
        nested acquire would self-deadlock). Append only; the coalescer
        flush drains this queue into its control batch."""
        self._free_queue.append(object_id)

    # ---------------------------------------------- submit coalescer plumbing

    def queue_submit(self, spec, actor_name=None) -> bool:
        """Coalesce a task/actor submission into the control batch (the
        head folds the return-id add_refs into the batch apply). Returns
        False when batching is disabled — the caller takes the synchronous
        submit_task path instead."""
        if not self._coalescer.enabled:
            return False
        self._coalescer.queue(("submit", spec, actor_name))
        return True

    def queue_add_refs(self, object_ids) -> bool:
        """Coalesce an add_ref burst (serialization hooks); replaces the
        old fire-and-forget Request that spawned a drain thread per call."""
        if not self._coalescer.enabled:
            return False
        self._coalescer.queue(("add_ref", list(object_ids)))
        return True

    def flush_submits(self) -> None:
        """Deliver everything coalesced (queued submits, add_refs, frees).
        Every synchronous controller interaction calls this first, so
        program-order visibility survives batching."""
        self._coalescer.flush()

    def _start_coalescer_thread(self):
        """Flusher-thread factory handed to the coalescer: keeping the
        target among THIS class's methods keeps thread-root analyses
        (watchdog dumps, tpulint's shared-state check) aware that the
        runtime runs its own flusher."""
        t = threading.Thread(
            target=self._coalescer_flush_loop, daemon=True,
            name=f"submit-coalescer-{self.worker_id.hex()[:8]}",
        )
        t.start()
        return t

    def _coalescer_flush_loop(self):
        self._coalescer._loop()

    def _drain_free_item(self):
        batch, self._free_queue = self._free_queue, []
        return ("free", batch) if batch else None

    def _deliver_batch(self, items: list) -> None:
        """Ship one coalesced control batch (runs under the coalescer's
        flush lock, so batches hit the wire in FIFO order). Pure-free
        batches ride the classic fire-and-forget FreeObjects frame; any
        batch carrying submits/add_refs goes as ONE submit_batch Request,
        retried on failure — the head's apply is replay-idempotent, so a
        lost batch is re-sent verbatim with no double-dispatch."""
        free_item = self._drain_free_item()
        if free_item is not None:
            items = items + [free_item]
        if not items:
            return
        if all(it[0] == "free" for it in items):
            oids = [oid for it in items for oid in it[1]]
            try:
                self._send(P.FreeObjects(oids))
            except (OSError, EOFError):
                pass  # conn gone: the head reaps this worker's refs on death
            return
        last_err: Optional[BaseException] = None
        for attempt in range(20):
            if self._shutdown and attempt > 0:
                return
            try:
                self.call_controller("submit_batch", items, _skip_flush=True)
                return
            except (OSError, EOFError, TimeoutError, RuntimeError) as e:
                # client-side injected chaos (OSError pre-send), an injected
                # controller failure (error reply -> RuntimeError), or a
                # transport hiccup: replay the identical batch
                last_err = e
                time.sleep(min(0.02 * (attempt + 1), 0.2))
        raise OSError(f"submit_batch delivery failed after retries: {last_err}")

    def shutdown(self):
        """Deterministic teardown: stop the coalescer (its shutdown flushes
        the final batch) — the final free batch must hit the wire before
        the process exits — and join the observability flusher (its exit
        path ships the final span/metric report while the conn is still
        plausibly alive)."""
        self._shutdown = True
        self._obs_stop.set()
        locktrace.join_if_alive(self._obs_thread, timeout=1.0)
        if not self.in_process:
            self._coalescer.shutdown()
        else:
            self._coalescer._shutdown = True

    # ------------------------------------------------ observability shipping

    def _flush_observability(self):
        """Ship this process's span ring + metrics snapshot to the head (or
        to the node agent's intercept). Metrics are cumulative snapshots —
        a lost report is covered by the next one and a replay diffs to zero
        at the head — so only spans need requeueing on failure."""
        from ray_tpu.util import metrics as metrics_mod

        t = _trace_mod()
        spans = t.drain_spans()
        snap = metrics_mod.snapshot()
        if not spans and not snap:
            return
        entry = {
            "reporter": f"w-{self.worker_id.hex()[:12]}-{os.getpid()}",
            "pid": os.getpid(),
            "spans": spans,
            "dropped_spans": t.dropped_spans(),
            "metrics": snap,
        }
        try:
            self.call_controller(
                "report_observability", (None, [entry]), _skip_flush=True
            )
        except Exception:  # noqa: BLE001 — retried on the next tick
            t.requeue_spans(spans)

    # compat shim for older call sites/tests: flush everything queued
    def _flush_frees(self) -> bool:
        try:
            self._coalescer.flush()
            return True
        except (OSError, EOFError):
            return False

    def register_driver(self):
        """Synchronous client-driver registration: MUST be on the wire before
        any API request, or the controller's handshake closes the conn."""
        self._send(P.RegisterDriver(self.worker_id, os.getpid()))

    def run(self):
        # Register with the controller, then serve the task loop.
        if not self.in_process:
            # thread-mode workers never send FreeObjects (the driver API is
            # the global one and frees flow through it) — a flusher thread
            # per in-process worker is pure thread-count overhead at the
            # 1000-actor envelope scale
            self._coalescer._ensure_thread()
        if self.client_mode:
            # client driver: this loop only pumps replies; no tasks arrive
            # (registration already sent synchronously by _connect_client)
            self._client_loop()
            return
        if self.in_process:
            # Thread mode: the driver's API is already the global one; share
            # its serialization context so ref tracking stays consistent.
            from ray_tpu._private import worker as worker_mod

            if worker_mod.is_initialized():
                self.serialization = worker_mod.global_worker().serialization
        else:
            self._install_worker_api()
            self._start_direct_server()
            # per-process observability flusher (thread mode shares the
            # driver's ring/registry — the head reads them in-process)
            self._obs_thread = threading.Thread(
                target=_obs_flush_loop, args=(self,), daemon=True,
                name=f"obs-flush-{self.worker_id.hex()[:8]}",
            )
            self._obs_thread.start()
        self._send(
            P.RegisterWorker(
                self.worker_id, os.getpid(), direct_address=self.direct_address
            )
        )
        while not self._shutdown:
            try:
                msg = self.conn.recv()
            except (EOFError, OSError):
                break
            if isinstance(msg, P.ExecuteTask):
                self._route_task(msg)
            elif isinstance(msg, (P.GetReply, P.PutAck, P.Reply)):
                self._handle_reply(msg)
            elif isinstance(msg, P.StealTasks):
                self._handle_steal(msg)
            elif isinstance(msg, P.DumpStacks):
                try:
                    self._send(P.StacksReply(msg.req_id, self._dump_stacks()))
                except (OSError, EOFError):
                    pass
            elif isinstance(msg, P.HeadRestarted):
                # the agent re-registered with a RESTARTED head: every
                # in-flight controller call relayed through it lost its
                # reply — bump the epoch so blocked waiters unblock and
                # the per-op retry envelope decides (replay vs surface)
                with self._get_cv:
                    self._conn_epoch += 1
                    self._get_cv.notify_all()
            elif isinstance(msg, P.KillActor):
                break
            elif isinstance(msg, P.Shutdown):
                break
        self._shutdown = True
        self._drop_inline_hosts()
        self.shutdown()  # joins the free flusher + final flush (see above)
        if not self.in_process:
            os._exit(0)
        # thread-mode worker retiring (e.g. KillActor): close the channel so
        # the controller's reader thread sees EOF and exits — otherwise every
        # killed actor leaks a blocked reader thread and a 1000-actor
        # create/kill cycle strangles the host
        try:
            self.conn.close()
        except Exception:  # noqa: BLE001
            pass

    # ------------------------------------------------- direct actor calls

    def _start_direct_server(self):
        """Listen for worker-to-worker actor calls (reference: the core
        worker's gRPC server handling PushTask directly from callers,
        ``core_worker.cc`` HandlePushTask — no raylet/GCS on the path).
        Binds 0.0.0.0 when the node advertises an IP (agent hosts, so
        cross-host callers can reach it); loopback otherwise."""
        if self.authkey is None:
            return
        from multiprocessing.connection import Listener

        host = os.environ.get("RAY_TPU_NODE_IP")
        try:
            self._direct_listener = Listener(
                ("0.0.0.0" if host else "127.0.0.1", 0), authkey=self.authkey
            )
        except OSError:
            return  # no direct transport; calls fall back to the head
        port = self._direct_listener.address[1]
        self.direct_address = f"{host or '127.0.0.1'}:{port}"
        threading.Thread(
            target=self._direct_accept_loop, daemon=True, name="direct-accept"
        ).start()

    def _direct_accept_loop(self):
        while not self._shutdown:
            try:
                conn = self._direct_listener.accept()
            except (OSError, EOFError):
                if self._shutdown:
                    return
                continue
            except Exception:  # noqa: BLE001 — failed auth handshake
                continue
            threading.Thread(
                target=self._direct_conn_loop,
                args=(conn,),
                daemon=True,
                name="direct-conn",
            ).start()

    def _direct_conn_loop(self, conn):
        """One caller's connection: FIFO per caller — messages are routed
        to the actor's execution queue in arrival order, so a single
        caller's calls execute in submission order (caller-side seq)."""
        reply = _DirectReplyConn(conn)
        while not self._shutdown:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break
            except (TypeError, ValueError):
                break  # recv raced a close() — handle already None
            if isinstance(msg, P.DirectActorCall):
                task = _DirectTask(msg.spec, msg.resolved_args, reply, msg.req_id)
                abin = (
                    msg.spec.actor_id.binary()
                    if msg.spec.actor_id is not None
                    else None
                )
                if abin is not None and abin not in self.actors:
                    # stale endpoint (actor restarted elsewhere / not yet
                    # created here): tell the caller to re-resolve instead
                    # of raising an opaque KeyError from the task body
                    try:
                        reply.send(P.DirectCallReply(msg.req_id, "stale"))
                    except (OSError, EOFError):
                        break
                    continue
                lock = self.actor_exec_locks.get(abin)
                if lock is not None:
                    # sync maxc=1 actor: run inline on this reader thread
                    # (per-caller FIFO holds — this thread drains the conn in
                    # order; the lock serializes against other callers and
                    # the head-dispatch pool)
                    with lock:
                        self._execute_task(task)
                else:
                    self._route_task(task)
        try:
            conn.close()
        except OSError:
            pass

    def _dump_stacks(self) -> str:
        """Every thread's Python stack, annotated with the running task —
        the py-spy/dashboard-profiling analog (reference:
        ``dashboard/modules/reporter/reporter_agent.py`` on-demand stack
        traces), served in-process so no ptrace capability is needed."""
        import sys
        import traceback

        names = {t.ident: t.name for t in threading.enumerate()}
        parts = [
            f"pid={os.getpid()} task={self.current_task_name!r} "
            f"worker={self.worker_id.hex()[:12]}"
        ]
        for tid, frame in sorted(sys._current_frames().items()):
            parts.append(
                f"\n--- thread {names.get(tid, '?')} (ident {tid}) ---\n"
                + "".join(traceback.format_stack(frame))
            )
        return "".join(parts)

    def _handle_reply(self, msg) -> None:
        with self._get_cv:
            if isinstance(msg, P.GetReply):
                self._get_replies[msg.req_id] = msg.results
            elif isinstance(msg, P.PutAck):
                self._get_replies[msg.req_id] = True
            else:
                self._get_replies[msg.req_id] = msg
            self._get_cv.notify_all()

    def _client_loop(self):
        """Reply pump for client-driver mode. On connection loss the pump
        re-dials the head (restart grace window): pending calls fail fast
        with an error reply so callers can retry against the restored
        cluster (reference: ray client reconnect grace period)."""
        while not self._shutdown:
            try:
                msg = self.conn.recv()
            except (EOFError, OSError):
                if self._shutdown or not self._client_reconnect():
                    break
                continue
            except TypeError:
                # recv on a handle another thread just close()d (detach/
                # shutdown) dies with TypeError (handle is None) — same as
                # EOF (see _DirectConn._read_loop)
                if self._shutdown or not self._client_reconnect():
                    break
                continue
            if isinstance(msg, (P.GetReply, P.PutAck, P.Reply)):
                self._handle_reply(msg)
            elif isinstance(msg, P.Shutdown):
                break
        self._shutdown = True
        with self._get_cv:
            self._get_cv.notify_all()

    def _client_reconnect(self, window_s: float = 30.0) -> bool:
        if self.client_target is None:
            return False
        from multiprocessing.connection import Client

        # fail all in-flight calls: their replies died with the old conn
        # (epoch bump wakes _await_reply waiters, who raise and let callers
        # retry against the restored head)
        with self._get_cv:
            self._conn_epoch += 1
            self._get_cv.notify_all()
        target, family, authkey = self.client_target
        deadline = time.monotonic() + window_s
        while time.monotonic() < deadline and not self._shutdown:
            try:
                conn = Client(target, family=family, authkey=authkey)
                # swap + register atomically: another thread's request must
                # not become the new connection's first message (the head
                # closes conns whose first message isn't a Register*)
                with self._send_lock:
                    self.conn = conn
                    conn.send(P.RegisterDriver(self.worker_id, os.getpid()))
                # bump AGAIN after the swap: a request sent DURING the dial
                # window captured the entry bump's epoch but went into the
                # dead socket — without this second bump its waiter would
                # sit out its full timeout on a reply that can never come
                # (the spuriously-kicked requests that raced the swap onto
                # the live conn just replay through the retry envelope)
                with self._get_cv:
                    self._conn_epoch += 1
                    self._get_cv.notify_all()
                return True
            except (OSError, EOFError, ConnectionError):
                time.sleep(1.0)
        return False

    def _route_task(self, msg: P.ExecuteTask):
        spec = msg.spec
        try:
            if spec.task_type == TaskType.ACTOR_TASK:
                # concurrency is a property of the ACTOR (set at creation),
                # not of the method-call spec — route through the actor's pool
                pool = self.actor_pools.get(spec.actor_id.binary())
                if pool is not None:
                    pool.submit(self._execute_task, msg)
                    return
                # async-ness is likewise an actor property; method-call
                # specs don't carry is_async_actor
                loop = self.actor_loops.get(spec.actor_id.binary())
                if loop is not None:
                    asyncio.run_coroutine_threadsafe(self._execute_async(msg), loop)
                    return
            if spec.task_type == TaskType.NORMAL_TASK:
                tid = spec.task_id.binary()
                with self._pf_lock:
                    self._pending_futures[tid] = None  # placeholder pre-submit
                try:
                    fut = self._task_pool.submit(self._execute_task, msg)
                except RuntimeError:
                    with self._pf_lock:
                        self._pending_futures.pop(tid, None)
                    raise
                with self._pf_lock:
                    # skip if the executor already started (and popped) it
                    if tid in self._pending_futures:
                        self._pending_futures[tid] = fut
            elif self.in_process:
                # thread-mode actor execution runs INLINE on this worker's
                # own loop thread: ordering is the channel's FIFO, blocking
                # get()s go straight to the in-process controller (replies
                # never ride this channel), and the 1000-actor envelope
                # drops a ThreadPoolExecutor thread per actor. Normal tasks
                # keep the pool — work stealing needs their queued futures.
                self._execute_task(msg)
            else:
                self._task_pool.submit(self._execute_task, msg)
        except RuntimeError:
            # pool shut down: this worker is going away; the controller
            # reschedules the task when the death is observed
            pass

    def _handle_steal(self, msg: "P.StealTasks"):
        """Give back up to ``count`` queued tasks, newest first (they would
        run last anyway). Runs on the reader thread — the same thread that
        populates _pending_futures — so iteration is race-free; only the
        executor thread's pop (at execution start) can interleave, and
        Future.cancel() arbitrates that atomically."""
        stolen = []
        with self._pf_lock:
            for tid in list(reversed(self._pending_futures.keys())):
                if len(stolen) >= msg.count:
                    break
                fut = self._pending_futures.get(tid)
                if fut is not None and fut.cancel():
                    self._pending_futures.pop(tid, None)
                    stolen.append(tid)
        try:
            self._send(P.TasksStolen(stolen))
        except (OSError, EOFError):
            pass

    # -------------------------------------------------------- object plane

    # ----------------------- client-transparent head-restart retry envelope

    def _head_retry_window_s(self) -> float:
        try:
            from ray_tpu._private.config import get_config

            return float(
                os.environ.get(
                    "RAY_TPU_HEAD_RETRY_TIMEOUT_S",
                    get_config().head_retry_timeout_s,
                )
            )
        except Exception:  # noqa: BLE001 — env-only processes
            return 60.0

    def _retry_recoverable(self, exc: BaseException) -> bool:
        """Is this connection failure one a retry can outlive? An epoch
        bump means a reconnect ALREADY happened (client pump re-dial, or
        the agent's HeadRestarted notice for relayed workers). A raw send/
        EOF failure is recoverable only in client mode, where the reply
        pump keeps re-dialing — a head-local worker's dead socket never
        comes back (the head respawns workers, not the reverse)."""
        if isinstance(exc, ConnEpochBumped):
            return True
        return self.client_mode

    def _head_retry(self, op: str, fn, *, idempotency: Optional[str] = None):
        """Run one send+await closure, replaying it across head restarts
        per its idempotency class (bounded exponential backoff + jitter
        inside the configured window): reads replay freely, idempotent
        writes replay under their original request ids' semantics (the
        head dedups), and once-only ops surface a typed
        ``HeadRestartedError`` instead of guessing."""
        cls = idempotency or P.op_idempotency(op)
        deadline = None
        attempt = 0
        while True:
            try:
                return fn()
            except (ConnEpochBumped, OSError, EOFError) as e:
                if isinstance(e, TimeoutError):
                    raise  # a caller deadline, not a transport loss
                if self._shutdown or not self._retry_recoverable(e):
                    raise
                if cls == "once":
                    from ray_tpu.exceptions import HeadRestartedError

                    raise HeadRestartedError(op, str(e)) from e
                now = time.monotonic()
                if deadline is None:
                    deadline = now + self._head_retry_window_s()
                if now >= deadline:
                    raise
                import random as _random

                delay = min(0.05 * (2 ** min(attempt, 6)), 2.0)
                time.sleep(delay * (0.5 + _random.random()))
                attempt += 1

    def get_objects(self, object_ids: list[ObjectID], timeout=None) -> list:
        """Returns [(SerializedObject, kind)] parallel to object_ids."""
        # injection FIRST (a failed request leaves the coalescer untouched),
        # then flush: pending coalesced submits must be on the wire before a
        # synchronous read (program-order visibility across the window)
        self._maybe_inject_failure("get_objects")
        self._coalescer.flush()

        def attempt():
            req_id = next(self._req_counter)
            epoch = self._conn_epoch
            self._send(P.GetObjects(req_id, object_ids))
            return self._await_reply(req_id, timeout, epoch=epoch)

        # pure read: a get() in flight across a head crash blocks through
        # recovery and re-asks the restored head instead of erroring
        results = self._head_retry("get_objects", attempt, idempotency="read")
        return [
            (self._materialize(kind, payload, object_id=oid), kind)
            for oid, kind, payload in results
        ]

    def _await_reply(self, req_id: int, timeout=None, epoch=None):
        """``epoch`` must be the _conn_epoch captured BEFORE the request was
        sent — capturing at wait time would miss a reconnect that lands
        between send and wait, leaving the waiter blocked forever."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._get_cv:
            if epoch is None:
                epoch = self._conn_epoch
            while req_id not in self._get_replies:
                if self._shutdown:
                    raise OSError("worker shutting down")
                if self._conn_epoch != epoch:
                    # head connection was lost and re-dialed: this request's
                    # reply died with the old connection
                    raise ConnEpochBumped(
                        "connection to head lost (reconnected)"
                    )
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise TimeoutError("controller reply timed out")
                self._get_cv.wait(timeout=remaining if remaining is not None else 1.0)
            return self._get_replies.pop(req_id)

    def call_controller(self, op: str, payload=None, fire_and_forget: bool = False, _skip_flush: bool = False):
        self._maybe_inject_failure(op)
        if not _skip_flush:
            # any synchronous controller interaction flushes the submit
            # coalescer first — ordering and get()/cancel/kill visibility
            # are preserved across the batching window (_skip_flush marks
            # the coalescer's own delivery call; flushing there would
            # re-enter the flush lock)
            self._coalescer.flush()
        if fire_and_forget:
            req_id = next(self._req_counter)
            epoch = self._conn_epoch
            self._send(P.Request(req_id, op, payload))

            # Still consume the reply asynchronously to keep the table clean.
            def drain():
                try:
                    self._await_reply(req_id, epoch=epoch)
                except (OSError, TimeoutError):
                    pass

            threading.Thread(target=drain, daemon=True).start()
            return None

        def attempt():
            req_id = next(self._req_counter)
            epoch = self._conn_epoch
            self._send(P.Request(req_id, op, payload))
            return self._await_reply(req_id, epoch=epoch)

        # head-restart envelope: reads and idempotent writes replay across
        # the crash (the restored head dedups replayed submits by task id /
        # sealed returns); once-only ops surface HeadRestartedError
        reply = self._head_retry(op, attempt)
        if reply.error is not None:
            raise RuntimeError(f"controller call {op} failed: {reply.error}")
        return reply.payload

    def _materialize(self, kind, payload, object_id=None) -> SerializedObject:
        from ray_tpu._native.plasma import NativePlasmaError
        from ray_tpu._private.object_store import (
            ObjectRelocatedError,
            parse_arena_location,
        )

        local_arena = os.environ.get("RAY_TPU_ARENA")
        for _ in range(5):
            if kind in ("inline", "error"):
                return SerializedObject.from_buffer(payload)
            if kind == "spilled":
                path, size = payload
                try:
                    with open(path, "rb") as f:
                        return SerializedObject.from_buffer(f.read())
                except OSError:
                    # spill file lives on the head's filesystem — a cross-host
                    # client pulls it through the chunk protocol instead
                    if object_id is None:
                        raise
                    return SerializedObject.from_buffer(
                        self._pull_object(object_id, size)
                    )
            shm_name, size = payload
            loc = parse_arena_location(shm_name)
            pullable = loc is not None and loc[2] is not None
            if pullable and local_arena and loc[0] != local_arena:
                # object lives in ANOTHER node's arena. Preferred path:
                # materialize it into THIS node's arena (one node-level
                # transfer; subsequent local readers mmap it — reference:
                # pulls land in the local plasma store, pull_manager.h:49).
                entry = self._pull_via_arena(ObjectID(loc[2]), size)
                if entry is not None:
                    kind, payload = entry
                    continue  # re-materialize from the (local) entry
                # fallback: private windowed pull into this process
                return SerializedObject.from_buffer(
                    self._pull_object(ObjectID(loc[2]), size)
                )
            try:
                self._maybe_inject_failure("plasma_read")
                return self._plasma().read(shm_name, size)
            except (FileNotFoundError, OSError, NativePlasmaError):
                # the segment/arena isn't attachable from this process — a
                # cross-host client driver. Fall back to the pull protocol.
                if not pullable:
                    raise
                return SerializedObject.from_buffer(
                    self._pull_object(ObjectID(loc[2]), size)
                )
            except ObjectRelocatedError:
                # the arena block was spilled/recycled while we read —
                # re-resolve through the controller (entry now points at the
                # spill file or a fresh location)
                if loc is None or loc[2] is None:
                    raise
                req_id = next(self._req_counter)
                epoch = self._conn_epoch
                self._send(P.GetObjects(req_id, [ObjectID(loc[2])]))
                results = self._await_reply(req_id, 30.0, epoch=epoch)
                _, kind, payload = results[0]
        raise ObjectRelocatedError(f"object kept relocating: {payload!r}")

    def _transfer_knobs(self) -> tuple[int, int]:
        """(chunk_bytes, window) for chunked pull/push streams."""
        try:
            from ray_tpu._private.config import get_config

            cfg = get_config()
            return (
                max(64 * 1024, cfg.object_transfer_chunk_bytes),
                max(1, cfg.object_transfer_window),
            )
        except Exception:  # noqa: BLE001 — env-only processes
            return 4 * 1024**2, 8

    def _pull_via_arena(self, object_id: ObjectID, size: int):
        """Ask the node authority (agent, or the controller for head-side
        nodes) to materialize a remote object into THIS node's arena and
        return the fresh local ``(kind, payload)`` entry — or None when the
        node has no arena-pull support (the caller direct-pulls instead).
        The node-level single-flight lives server-side, so concurrent
        readers of one object on one node coalesce into a single
        transfer."""
        if not getattr(self, "_arena_pull_enabled", True):
            return None
        try:
            entry = self._call_controller_inproc_safe(
                "pull_into_arena", (object_id, size)
            )
        except (RuntimeError, TimeoutError, OSError):
            return None
        if entry is None:
            return None
        kind, payload = entry
        if kind == "plasma":
            # never loop on a still-remote location (a directory race):
            # only a LOCAL materialization is an answer
            from ray_tpu._private.object_store import parse_arena_location

            loc = parse_arena_location(payload[0])
            if loc is None or loc[0] != os.environ.get("RAY_TPU_ARENA"):
                return None
        return entry

    def _await_chunk_replies(self, inflight: dict, deadline) -> tuple[int, Any]:
        """Block until ANY req_id in ``inflight`` (req_id -> send epoch) has
        a reply; returns (req_id, reply-or-None). None means the reply died
        with a reconnected head connection — the caller re-sends that
        chunk. Waits are bounded and re-check liveness."""
        with self._get_cv:
            while True:
                for rid, epoch in inflight.items():
                    if rid in self._get_replies:
                        return rid, self._get_replies.pop(rid)
                    if self._conn_epoch != epoch:
                        return rid, None
                if self._shutdown:
                    raise OSError("worker shutting down")
                if deadline is not None and time.monotonic() >= deadline:
                    raise TimeoutError("chunk transfer timed out")
                self._get_cv.wait(timeout=1.0)

    def _pump_chunk_window(
        self, chunks: list, send_chunk, on_reply, window: int,
        timeout: Optional[float] = None, max_attempts: int = 5,
    ):
        """Shared engine for windowed chunk transfer over the control
        connection (pull AND push ride it). ``chunks`` are opaque work
        items; ``send_chunk(item) -> req_id`` fires one request (recording
        its epoch via ``_conn_epoch``); ``on_reply(item, reply)`` consumes a
        success reply. Keeps ``window`` requests in flight with per-chunk
        retry — one dropped chunk costs one retransmit, not the whole
        object (reference: the chunk retry loop in
        PullManager/ObjectBufferPool)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        pending = list(reversed(chunks))  # pop() pulls in order
        inflight: dict[int, Any] = {}  # req_id -> (item, attempt, epoch)
        backoff_until = 0.0
        while pending or inflight:
            while pending and len(inflight) < window:
                item = pending.pop()
                epoch = self._conn_epoch
                req_id = send_chunk(item)
                inflight[req_id] = (item, 1, epoch)
            rid, reply = self._await_chunk_replies(
                {r: v[2] for r, v in inflight.items()}, deadline
            )
            item, attempt, _epoch = inflight.pop(rid)
            err = getattr(reply, "error", None) if reply is not None else "connection lost"
            if reply is None or err is not None:
                if attempt >= max_attempts:
                    raise RuntimeError(
                        f"chunk transfer failed after {attempt} attempts: {err}"
                    )
                # pace retries without stalling the rest of the window
                now = time.monotonic()
                if now < backoff_until:
                    time.sleep(backoff_until - now)
                backoff_until = time.monotonic() + 0.05 * attempt
                epoch = self._conn_epoch
                req_id = send_chunk(item)
                inflight[req_id] = (item, attempt + 1, epoch)
                continue
            extra = on_reply(item, reply)
            if extra is not None:
                pending.append(extra)

    def _pull_object(
        self,
        object_id: ObjectID,
        size: int,
        chunk_bytes: Optional[int] = None,
        window: Optional[int] = None,
    ) -> bytearray:
        """Windowed chunked pull over the control connection: up to
        ``object_transfer_window`` chunk requests in flight, each chunk
        written straight into ONE preallocated buffer (no grow-and-copy
        ``bytearray`` + final ``bytes()`` double peak — it matters at
        multi-GB objects)."""
        cfg_chunk, cfg_window = self._transfer_knobs()
        chunk_bytes = chunk_bytes or cfg_chunk
        window = window or cfg_window
        buf = bytearray(size)
        mv = memoryview(buf)

        def send_chunk(item) -> int:
            offset, length = item
            self._maybe_inject_failure("pull_object_chunk")
            req_id = next(self._req_counter)
            self._send(
                P.Request(
                    req_id, "pull_object_chunk", (object_id, offset, length)
                )
            )
            return req_id

        def on_reply(item, reply):
            offset, length = item
            _total, data = reply.payload
            if not data:
                raise RuntimeError(
                    f"empty chunk at offset {offset}/{size} for {object_id.hex()}"
                )
            mv[offset : offset + len(data)] = data
            self.transfer_chunks_pulled += 1
            if len(data) < length:
                # server capped the chunk at ITS transfer config: re-request
                # the remainder as a fresh window item
                return (offset + len(data), length - len(data))
            return None

        chunks = [
            (off, min(chunk_bytes, size - off))
            for off in range(0, size, chunk_bytes)
        ]
        self._pump_chunk_window(chunks, send_chunk, on_reply, window)
        return buf

    def _plasma(self):
        if self._shm_client is None:
            from ray_tpu._private.object_store import PlasmaClient

            # raced from every get/put thread on first use; the losing
            # thread's client would leak its shm mapping
            with self._shm_client_lock:
                if self._shm_client is None:
                    self._shm_client = PlasmaClient()
        return self._shm_client

    def _inproc_controller(self):
        """Thread mode only: the controller object lives in this process.
        Blocking MID-TASK ops (stream-item seals, backpressure polls) must
        use it directly instead of the worker channel: inline actor tasks
        run ON the channel's run loop, so a channel round trip issued from
        inside one can never receive its reply — the loop that would pump
        the ack is the thread waiting for it (the test_streaming
        actor-method hang the conftest watchdog used to eat 300 s on)."""
        if not self.in_process:
            return None
        from ray_tpu._private import worker as worker_mod

        if worker_mod.is_initialized():
            return getattr(worker_mod.global_worker(), "controller", None)
        return None

    def _call_controller_inproc_safe(self, op: str, payload=None):
        """``call_controller``, but routed through the in-process dispatch
        when this worker IS the channel pump (thread mode): a channel round
        trip issued from an inline task mid-execution can never receive its
        own reply (the pump is the blocked thread)."""
        if self._inproc_controller() is not None:
            from ray_tpu._private import worker as worker_mod

            return worker_mod.global_worker().controller_call(op, payload)
        return self.call_controller(op, payload)

    def put_serialized(self, object_id: ObjectID, sobj: SerializedObject):
        self._maybe_inject_failure("put_object")
        ctrl = self._inproc_controller()
        if ctrl is not None:
            if sobj.total_bytes() <= self.max_inline:
                ctrl.seal_object(object_id, "inline", sobj.to_bytes())
            else:
                ctrl.seal_object(
                    object_id, "plasma", self._write_shm(object_id, sobj)
                )
            return
        # the put's own add_ref may still sit in the coalescer: it has to
        # reach the head before the seal does, or the head finds an object
        # nobody holds and frees it where it is sealed
        self._coalescer.flush()
        if (
            sobj.total_bytes() > self.max_inline
            and self.client_mode
            and not os.environ.get("RAY_TPU_ARENA")
        ):
            # client driver (possibly on another host — no attachable
            # arena): push the bytes through the control channel in chunks
            # (inverse of the pull protocol; reference: PushManager,
            # push_manager.h:27). The controller seals into the head store.
            self._push_object(object_id, sobj.to_bytes())
            return
        if sobj.total_bytes() <= self.max_inline:
            kind, put_payload = "inline", sobj.to_bytes()
        else:
            kind, put_payload = "plasma", self._write_shm(object_id, sobj)

        def attempt():
            req_id = next(self._req_counter)
            epoch = self._conn_epoch
            self._send(P.PutObject(req_id, object_id, kind, put_payload))
            return self._await_reply(req_id, epoch=epoch)

        # sealing the same (oid, payload) twice is idempotent head-side
        self._head_retry("put_object", attempt, idempotency="idempotent")

    def put_entry(self, object_id: ObjectID, kind: str, payload: bytes):
        """Seal a pre-serialized entry with an explicit kind ("inline" or
        "error") into the head's store — used when promoting a direct-call
        result that escapes to another process (kind must survive: an
        "error" promoted as "inline" would stop propagating)."""

        def attempt():
            req_id = next(self._req_counter)
            epoch = self._conn_epoch
            self._send(P.PutObject(req_id, object_id, kind, payload))
            return self._await_reply(req_id, epoch=epoch)

        self._head_retry("put_object", attempt, idempotency="idempotent")

    def _push_object(
        self,
        object_id: ObjectID,
        data: bytes,
        chunk_bytes: Optional[int] = None,
        window: Optional[int] = None,
    ) -> None:
        """Windowed chunked push with per-chunk retry (mirror of
        ``_pull_object`` — same in-flight window over the control
        connection; chunk writes are idempotent server-side, so a retried
        chunk is safe)."""
        cfg_chunk, cfg_window = self._transfer_knobs()
        chunk_bytes = chunk_bytes or cfg_chunk
        window = window or cfg_window
        total = len(data)
        mv = memoryview(data)

        def send_chunk(offset) -> int:
            self._maybe_inject_failure("push_object_chunk")
            req_id = next(self._req_counter)
            chunk = bytes(mv[offset : offset + chunk_bytes])
            self._send(
                P.Request(
                    req_id, "push_object_chunk", (object_id, offset, total, chunk)
                )
            )
            return req_id

        def on_reply(offset, reply):
            return None

        self._pump_chunk_window(
            list(range(0, total, chunk_bytes)), send_chunk, on_reply, window
        )

    def _write_shm(self, object_id: ObjectID, sobj: SerializedObject):
        if os.environ.get("RAY_TPU_ARENA"):
            data = sobj.to_bytes()
            # native arena: allocate via the store authority, write through
            # this process's mapping (plasma create/seal protocol).
            # inproc-safe: an inline actor task sealing a large stream item
            # must not issue a channel round trip from the pump thread
            name = self._call_controller_inproc_safe(
                "shm_create", (object_id, len(data))
            )
            if isinstance(name, tuple) and name[0] == "exists":
                # duplicate put — the sealed object stands; skip the write
                return name[1], name[2]
            self._plasma().write_arena(name, data)
            return name, len(data)
        return self._write_plain_shm(object_id, sobj)

    def _write_plain_shm(self, object_id: ObjectID, sobj: SerializedObject):
        """Write into a standalone SharedMemory segment (never the arena —
        direct-call results bypass the store authority entirely; lifecycle
        belongs to whoever seals or releases the object)."""
        data = sobj.to_bytes()
        from multiprocessing import shared_memory

        name = f"rt_{object_id.hex()[:20]}_{os.getpid() & 0xFFFF:x}"
        seg = shared_memory.SharedMemory(create=True, size=max(len(data), 1), name=name)
        try:
            seg.buf[: len(data)] = data
            # Hand lifecycle ownership to the consumer (controller or direct
            # caller): stop this process's resource tracker from unlinking
            # the segment at exit.
            try:
                from multiprocessing import resource_tracker

                resource_tracker.unregister(seg._name, "shared_memory")  # type: ignore[attr-defined]
            except Exception:
                pass
        except BaseException:
            # nobody will ever consume the segment: reclaim it now, or the
            # spill leaks RSS until process exit (the PR 4 leak shape)
            seg.close()
            try:
                seg.unlink()
            except OSError:
                pass
            raise
        seg.close()
        return name, len(data)

    # -------------------------------------------------------------- execution

    def _deserialize_args(self, spec: TaskSpec, resolved_args: list):
        """Decode the (args, kwargs) template + resolved top-level refs.

        ``resolved_args[0]`` is the serialized template; the rest are the
        resolved payloads of top-level ObjectRef args, in marker order
        (see WorkerAPI._encode_args).
        """
        from ray_tpu._private.worker import _marker_state

        # spec.args keeps the ("ref", oid) entries in marker order, so each
        # resolved payload can carry its object id — required for the pull
        # fallback when this worker is on another host than the payload.
        ref_ids = [a[1] for a in spec.args if a[0] == "ref"]
        ref_values = []
        for (kind, payload), oid in zip(resolved_args[1:], ref_ids):
            sobj = self._materialize(kind, payload, object_id=oid)
            value = self.serialization.deserialize(sobj)
            if kind == "error":
                if isinstance(value, TaskError):
                    raise value.as_instanceof_cause()
                raise value
            ref_values.append(value)
        _marker_state.values = ref_values
        try:
            template = self.serialization.deserialize(
                SerializedObject.from_buffer(resolved_args[0][1])
            )
        finally:
            _marker_state.values = None
        args, kwargs = template
        return list(args), dict(kwargs)

    def _drop_inline_hosts(self):
        """Retire this runtime's actors from the inline-host registry (run
        on loop exit: KillActor / Shutdown / connection loss). Only entries
        still pointing at THIS runtime are removed — a restarted incarnation
        on another runtime must not be evicted by the old one's teardown."""
        with _inline_hosts_lock:
            for key in list(self.actors):
                if _inline_hosts.get(key) is self:
                    del _inline_hosts[key]

    def execute_inline(self, spec: TaskSpec, resolved_args: list):
        """Zero-hop fast path: run an eligible sync actor call ON the
        calling thread under the actor's execution lock, returning the
        TaskDone-shaped results list. The worker loop, the per-actor
        executor, and the controller reply round trip are all bypassed.

        Returns None when the call must fall back to the slow path: the
        actor is gone from this runtime, or its lock is held by another
        thread — blocking a nominally non-blocking ``.remote()`` behind a
        busy actor would serialize callers the queued paths let overlap.
        A reentrant self-call (the calling thread IS the actor) re-enters
        the RLock and runs nested instead of deadlocking.
        """
        abin = spec.actor_id.binary()
        lock = self.actor_exec_locks.get(abin)
        if lock is None or not lock.acquire(blocking=False):
            return None
        prev_name = self.current_task_name
        prev_actor = getattr(_exec_ctx, "actor_id", None)
        prev_mkey = getattr(_exec_ctx, "method_key", None)
        prev_tenant = getattr(_exec_ctx, "tenant", None)
        prev_prio = getattr(_exec_ctx, "priority", None)
        prev_trace = getattr(_exec_ctx, "trace_id", None)
        prev_span = getattr(_exec_ctx, "span_id", None)
        traced = self._trace_gate(spec)
        t_wall = time.time()
        failed = False
        try:
            if abin not in self.actors:
                traced = False
                return None
            try:
                args, kwargs = self._deserialize_args(spec, resolved_args)
                value = self._invoke(spec, args, kwargs)
                return self._store_returns(spec, value, inline_only=True)
            except (KeyboardInterrupt, SystemExit):
                # unlike the queued paths (executor threads never receive
                # signals), inline runs on the signal-delivery thread: a
                # Ctrl-C must terminate the driver, not become a result
                raise
            except BaseException as e:  # noqa: BLE001 — becomes the call's error result
                failed = True
                return self._store_error(spec, e)
        finally:
            # restore the OUTER execution context: a nested inline call from
            # an actor method must not leave the callee's identity behind
            self.current_task_name = prev_name
            _exec_ctx.actor_id = prev_actor
            _exec_ctx.method_key = prev_mkey
            _exec_ctx.tenant = prev_tenant
            _exec_ctx.priority = prev_prio
            _exec_ctx.trace_id = prev_trace
            _exec_ctx.span_id = prev_span
            lock.release()
            if traced:
                self._record_exec_spans(
                    spec, t_wall, None, None, time.time(), failed
                )

    def _trace_gate(self, spec: TaskSpec) -> bool:
        """Record this task's worker-plane spans? Sampled deterministically
        by task id, so every plane of a sampled task agrees."""
        t = _trace_mod()
        return (
            getattr(spec, "trace_id", None) is not None
            and t.sampled(spec.task_id.binary())
        )

    def _record_exec_spans(
        self, spec: TaskSpec, t0: float, t_deser: Optional[float],
        t_ret: Optional[float], t_end: float, failed: bool,
    ):
        """The worker plane's lifecycle spans: one ``task.exec`` umbrella
        (the id nested submits parent under) with deserialize/store-returns
        children. Parent = whichever plane dispatched us (the head's sched
        span or the agent's lease span, via ``spec.sched_span_id``; direct
        worker-to-worker calls chain straight to the caller's span)."""
        t = _trace_mod()
        tid_hex = spec.task_id.hex()
        trace_id = getattr(spec, "trace_id", None)
        parent = getattr(spec, "sched_span_id", None) or getattr(
            spec, "parent_span_id", None
        )
        eid = f"{tid_hex}:exec"
        t.record_span(
            "task.exec", t0, t_end, trace_id=trace_id, span_id=eid,
            parent_id=parent, plane="worker", task_id=tid_hex,
            task=spec.name, failed=failed,
        )
        if t_deser is not None:
            t.record_span(
                "task.deserialize", t0, t_deser, trace_id=trace_id,
                span_id=f"{tid_hex}:deser", parent_id=eid, plane="worker",
                task_id=tid_hex,
            )
        if t_ret is not None and t_end >= t_ret:
            t.record_span(
                "task.store_returns", t_ret, t_end, trace_id=trace_id,
                span_id=f"{tid_hex}:store", parent_id=eid, plane="worker",
                task_id=tid_hex,
            )

    def _execute_task(self, msg: P.ExecuteTask):
        spec = msg.spec
        direct = getattr(msg, "direct_reply", None)
        # running now — no longer stealable
        with self._pf_lock:
            self._pending_futures.pop(spec.task_id.binary(), None)
        start = time.monotonic()
        traced = self._trace_gate(spec)
        t_wall = time.time()
        t_deser = t_ret = None
        # head-dispatched calls to a sync maxc=1 actor serialize against
        # inline direct calls (the inline path already holds the lock)
        lock = None
        if (
            direct is None
            and spec.task_type == TaskType.ACTOR_TASK
            and spec.actor_id is not None
        ):
            lock = self.actor_exec_locks.get(spec.actor_id.binary())
        if lock is not None:
            lock.acquire()
        results = []
        failed = False
        try:
            args, kwargs = self._deserialize_args(spec, msg.resolved_args)
            t_deser = time.time()
            value = self._invoke(spec, args, kwargs)
            t_ret = time.time()
            results = self._store_returns(spec, value, inline_only=direct is not None)
        except BaseException as e:  # noqa: BLE001 — task errors must not kill the worker
            failed = True
            results = self._store_error(spec, e)
        finally:
            if lock is not None:
                lock.release()
        if traced:
            self._record_exec_spans(
                spec, t_wall, t_deser, t_ret, time.time(), failed
            )
        exec_ms = (time.monotonic() - start) * 1e3
        if direct is not None:
            # result rides the caller's connection; the head sees nothing
            try:
                direct.send(P.DirectCallReply(msg.req_id, results))
            except (OSError, EOFError):
                pass  # caller gone; nothing to deliver to
            return
        actor_id = spec.actor_id if spec.task_type != TaskType.NORMAL_TASK else None
        self._send(P.TaskDone(spec.task_id, results, actor_id=actor_id, exec_ms=exec_ms))

    async def _execute_async(self, msg: P.ExecuteTask):
        spec = msg.spec
        direct = getattr(msg, "direct_reply", None)
        start = time.monotonic()
        traced = self._trace_gate(spec)
        t_wall = time.time()
        t_deser = t_ret = None
        failed = False
        loop = asyncio.get_running_loop()
        # Trace context for the async plane: a ContextVar set inside THIS
        # coroutine (each run_coroutine_threadsafe task copied its context
        # at creation, so concurrent calls don't cross-wire parents). App
        # spans opened in the method body — or inside the executor-run
        # deserialize/store segments below, which run under a copy of this
        # context — parent under the task's exec span.
        t = _trace_mod()
        trace_id = getattr(spec, "trace_id", None)
        token = t.attach_context(
            (trace_id, f"{spec.task_id.hex()}:exec") if trace_id else None
        )
        try:
            key = spec.actor_id.binary()
            adm = self._async_admission.get(key)
            if adm is None:
                adm = self._async_admission.setdefault(key, asyncio.Lock())
            # Arg materialization can retry-sleep on store contention; on the
            # event loop that stalls every other coroutine of this actor —
            # route it through the default executor. The admission lock keeps
            # the pre-executor semantics intact: asyncio.Lock wakes waiters
            # FIFO, so tasks still START in submission order and plain-def
            # methods still run atomically in that order; only the await of
            # an async method body (below, outside the lock) overlaps.
            async with adm:
                import contextvars as _cv

                _ctx = _cv.copy_context()
                args, kwargs = await loop.run_in_executor(
                    None,
                    _ctx.run,
                    self._deserialize_args, spec, msg.resolved_args,
                )
                t_deser = time.time()
                instance = self.actors[key]
                if spec.method_name == "__rtpu_call__":
                    value = args[0](instance, *args[1:], **kwargs)
                else:
                    method = getattr(instance, spec.method_name)
                    value = method(*args, **kwargs)
            if asyncio.iscoroutine(value):
                value = await value
            t_ret = time.time()
            if spec.num_returns == "streaming" and hasattr(value, "__anext__"):
                results = await self._stream_returns_async(spec, value)
            else:
                # same store-contention retry shape as the args pull above
                import contextvars as _cv

                _ctx = _cv.copy_context()
                results = await loop.run_in_executor(
                    None,
                    _ctx.run,
                    functools.partial(
                        self._store_returns, spec, value,
                        inline_only=direct is not None,
                    ),
                )
        except BaseException as e:  # noqa: BLE001
            failed = True
            results = self._store_error(spec, e)
        finally:
            t.detach_context(token)
        if traced:
            self._record_exec_spans(
                spec, t_wall, t_deser, t_ret, time.time(), failed
            )
        exec_ms = (time.monotonic() - start) * 1e3
        if direct is not None:
            try:
                direct.send(P.DirectCallReply(msg.req_id, results))
            except (OSError, EOFError):
                pass
            return
        self._send(P.TaskDone(spec.task_id, results, actor_id=spec.actor_id, exec_ms=exec_ms))

    def _invoke(self, spec: TaskSpec, args, kwargs):
        if self.startup_error is not None and spec.task_type != TaskType.ACTOR_TASK:
            # this process could not provide what it was spawned for (a TPU
            # grant without the chips): nothing new may start on it
            raise self.startup_error
        self.current_task_name = spec.name
        # nested submits from this task inherit its tenant + priority
        _exec_ctx.tenant = getattr(spec, "tenant", None)
        _exec_ctx.priority = getattr(spec, "priority", None)
        # ... and its trace context: children parent under THIS task's exec
        # span (deterministic id — every plane derives the same one)
        _trace_mod()  # registers the context provider on first execution
        _exec_ctx.trace_id = getattr(spec, "trace_id", None)
        _exec_ctx.span_id = (
            f"{spec.task_id.hex()}:exec" if _exec_ctx.trace_id else None
        )
        _exec_ctx.actor_id = (
            spec.actor_id.binary()
            if spec.task_type != TaskType.NORMAL_TASK and spec.actor_id
            else None
        )
        # blocking-wait attribution (note_execution_blocked): only actor
        # METHODS are inline candidates, so only they carry a key
        _exec_ctx.method_key = (
            spec.name if spec.task_type == TaskType.ACTOR_TASK else None
        )
        if spec.task_type == TaskType.NORMAL_TASK:
            fn = cloudpickle.loads(spec.function_blob)
            return fn(*args, **kwargs)
        if spec.task_type == TaskType.ACTOR_CREATION_TASK:
            cls = cloudpickle.loads(spec.function_blob)
            instance = cls(*args, **kwargs)
            key = spec.actor_id.binary()
            self.actors[key] = instance
            if spec.max_concurrency > 1:
                self.actor_pools[key] = ThreadPoolExecutor(
                    max_workers=spec.max_concurrency, thread_name_prefix="actor"
                )
            if spec.is_async_actor:
                loop = asyncio.new_event_loop()
                self.actor_loops[key] = loop
                threading.Thread(target=loop.run_forever, daemon=True, name="actor-loop").start()
            elif spec.max_concurrency <= 1:
                # enables inline direct-call execution (see _direct_conn_loop)
                # and the same-process inline fast path (execute_inline).
                # RLock, not Lock: a reentrant self-call (an actor method
                # calling its own handle) runs nested on the same thread
                # instead of deadlocking on its own execution lock.
                self.actor_exec_locks[key] = locktrace.register_lock(
                    f"worker.actor_exec[{spec.actor_id.hex()[:8]}]",
                    threading.RLock(),
                )
                with _inline_hosts_lock:
                    _inline_hosts[key] = self
            return None
        # ACTOR_TASK
        instance = self.actors[spec.actor_id.binary()]
        if spec.method_name == "__rtpu_call__":
            # run an arbitrary function against the actor instance
            # (reference: ``__ray_call__``, used by compiled-graph loops)
            fn = args[0]
            return fn(instance, *args[1:], **kwargs)
        method = getattr(instance, spec.method_name)
        return method(*args, **kwargs)

    def _store_returns(self, spec: TaskSpec, value, inline_only: bool = False) -> list:
        return_ids = spec.return_ids()
        if spec.num_returns == "streaming":
            return self._stream_returns(spec, value)
        if spec.num_returns == 1:
            values = [value]
        else:
            values = list(value)
            if len(values) != spec.num_returns:
                raise ValueError(
                    f"task {spec.name} declared num_returns={spec.num_returns} "
                    f"but returned {len(values)} values"
                )
        results = []
        for oid, v in zip(return_ids, values):
            sobj = self.serialization.serialize(v)
            if inline_only:
                # direct-call / inline-path results are CALLER-owned — the
                # head's store never sees them. Small ones ride the reply
                # frame; past direct_inline_max the bytes go through a plain
                # shared-memory segment the caller maps zero-copy (same-host
                # only — a cross-host caller could not attach it, so agent
                # hosts keep everything in-frame)
                if (
                    sobj.total_bytes() > self.direct_inline_max
                    and not self.in_process
                    and not os.environ.get("RAY_TPU_NODE_IP")
                ):
                    name, size = self._write_plain_shm(oid, sobj)
                    results.append((oid, "plasma", (name, size)))
                else:
                    results.append((oid, "inline", sobj.to_bytes()))
            elif sobj.total_bytes() <= self.max_inline:
                results.append((oid, "inline", sobj.to_bytes()))
            else:
                name, size = self._write_shm(oid, sobj)
                results.append((oid, "plasma", (name, size)))
        return results

    def _stream_returns(self, spec: TaskSpec, value) -> list:
        """Execute a streaming-generator task: seal each yielded item into the
        store as it is produced (item i → return index i+1), then report the
        completion record (total count) at index 0 via the final TaskDone.

        Reference: the streaming-generator execution path in
        ``_raylet.pyx`` (``execute_streaming_generator_sync``) — items are
        reported to the owner eagerly, not batched at task end.
        """
        if not hasattr(value, "__next__"):
            raise TypeError(
                f"streaming task {spec.name} must return a generator, "
                f"got {type(value).__name__}"
            )
        count = 0
        try:
            for item in value:
                count += 1
                oid = ObjectID.for_return(spec.task_id, count)
                self.put_serialized(oid, self.serialization.serialize(item))
                self._stream_backpressure(spec, count)
        except BaseException as e:  # noqa: BLE001 — surface at the fail point
            count = self._seal_stream_error(spec, count, e)
        return self._stream_completion(spec, count)

    def _seal_stream_error(self, spec: TaskSpec, count: int, exc) -> int:
        """Seal a mid-stream error as the FINAL stream item: consumers drain
        every good item, raise on this one, then see StopIteration. The
        completion record still resolves to the count — only external
        failures (worker crash, cancel) surface through it."""
        count += 1
        payload = self._store_error(spec, exc)[0][2]
        oid = ObjectID.for_return(spec.task_id, count)
        ctrl = self._inproc_controller()
        if ctrl is not None:
            ctrl.seal_object(oid, "error", payload)
            return count
        req_id = next(self._req_counter)
        epoch = self._conn_epoch
        self._send(P.PutObject(req_id, oid, "error", payload))
        self._await_reply(req_id, epoch=epoch)
        return count

    def _stream_completion(self, spec: TaskSpec, count: int) -> list:
        gen_id = ObjectID.for_return(spec.task_id, 0)
        sobj = self.serialization.serialize(count)
        return [(gen_id, "inline", sobj.to_bytes())]

    def _stream_backpressure(self, spec: TaskSpec, produced: int):
        """Block while produced - consumed >= the backpressure threshold."""
        if not spec.generator_backpressure:
            return
        delay = 0.002
        while True:
            # same no-channel rule as put_serialized: an inline actor task
            # polling over the channel would deadlock its own pump
            consumed = self._call_controller_inproc_safe(
                "stream_consumed_get", spec.task_id
            )
            if consumed < 0:
                # the consumer freed the generator: stop producing rather
                # than poll a dead stream forever
                raise StreamConsumerGone(
                    f"stream consumer for {spec.name} is gone"
                )
            if produced - consumed < spec.generator_backpressure:
                return
            # backoff: a long-stalled consumer must not saturate the shared
            # control channel with poll RPCs
            time.sleep(delay)
            delay = min(delay * 1.6, 0.1)

    async def _stream_returns_async(self, spec: TaskSpec, agen) -> list:
        """Async-actor variant of ``_stream_returns`` for async generators."""
        count = 0
        loop = asyncio.get_running_loop()
        try:
            async for item in agen:
                count += 1
                oid = ObjectID.for_return(spec.task_id, count)
                sobj = self.serialization.serialize(item)
                await loop.run_in_executor(None, self.put_serialized, oid, sobj)
                if spec.generator_backpressure:
                    await loop.run_in_executor(
                        None, self._stream_backpressure, spec, count
                    )
        except BaseException as e:  # noqa: BLE001
            count = await loop.run_in_executor(
                None, self._seal_stream_error, spec, count, e
            )
        return self._stream_completion(spec, count)

    def _store_error(self, spec: TaskSpec, exc: BaseException) -> list:
        if isinstance(exc, TaskError):
            err = exc
        else:
            tb = "".join(traceback.format_exception(type(exc), exc, exc.__traceback__))
            err = TaskError(spec.name, exc, remote_tb=tb)
        try:
            sobj = self.serialization.serialize(err)
        except Exception:
            # Unpicklable cause: degrade to a string-only error.
            fallback = TaskError(spec.name, RuntimeError(repr(exc)), remote_tb=err.remote_tb)
            sobj = self.serialization.serialize(fallback)
        return [(oid, "error", sobj.to_bytes()) for oid in spec.return_ids()]

    # ---------------------------------------------------------- in-task API

    def _install_worker_api(self):
        """Give user code running in this worker access to get/put/remote."""
        from ray_tpu._private import worker as worker_mod

        worker_mod._set_worker_runtime(self)
