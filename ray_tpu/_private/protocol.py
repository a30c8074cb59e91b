"""Wire protocol between the controller process and worker processes.

The reference's control plane is gRPC (``src/ray/rpc/``); here the single-host
control plane is length-delimited pickled messages over
``multiprocessing.connection`` (AF_UNIX) — the same lease-then-push shape
(scheduler pushes ``ExecuteTask`` to a leased worker; data plane bypasses the
controller via shared memory). A gRPC/C++ transport can replace this without
changing message semantics.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

from ray_tpu._private.ids import ActorID, ObjectID, TaskID, WorkerID
from ray_tpu._private.task_spec import TaskSpec


class ChunkPullError(RuntimeError):
    """The owner reported it cannot serve the object (not resident)."""


class ChunkConnPool:
    """Pooled, authenticated connections to chunk listeners (agents' data
    plane). Up to ``max_conns_per_peer`` connections per peer address so a
    windowed pull can keep several chunk round trips in flight to one
    source (reference: ObjectBufferPool keeps many chunks of a transfer in
    flight, ``object_buffer_pool.h``); a transport error drops that
    connection and retries on a fresh one (per-chunk retry, matching the
    worker-side pull loop). Connects happen OUTSIDE the pool lock, so one
    unreachable peer (SYN-retry stall) cannot block pulls to healthy
    peers."""

    def __init__(self, authkey: bytes, max_conns_per_peer: int = 8):
        import threading

        self._authkey = authkey
        self._max_per_peer = max(1, max_conns_per_peer)
        # address -> {"idle": [conn, ...], "total": checked-out + idle}
        self._peers: dict[str, dict] = {}
        self._cv = threading.Condition(threading.Lock())

    def _dial(self, address: str, timeout: float = 10.0):
        """Authenticated data connection with BOUNDED dial + handshake.

        ``multiprocessing.connection.Client`` blocks forever in the auth
        challenge when a half-open peer (SYN-proxied address, dying host)
        accepts the TCP connection but never answers — hanging the chunk
        thread and with it the whole pull. Here the connect and every
        handshake syscall carry an OS-level deadline (``SO_RCVTIMEO`` /
        ``SO_SNDTIMEO``), so a dead source surfaces as OSError and the
        fetcher fails over to another replica or the head. The per-syscall
        deadline stays on the bulk phase too: it bounds stall, not
        throughput (each 64 KiB read just has to make progress)."""
        import socket as _socket
        import struct as _struct
        from multiprocessing.connection import (
            Connection,
            answer_challenge,
            deliver_challenge,
        )

        host, _, port = address.rpartition(":")
        sock = _socket.create_connection((host, int(port)), timeout=timeout)
        try:
            sock.setblocking(True)
            tv = _struct.pack("ll", int(timeout), int((timeout % 1) * 1e6))
            sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVTIMEO, tv)
            sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_SNDTIMEO, tv)
            conn = Connection(sock.detach())
        except BaseException:
            sock.close()
            raise
        try:
            answer_challenge(conn, self._authkey)
            deliver_challenge(conn, self._authkey)
        except BaseException:
            conn.close()
            raise
        return conn

    def _checkout(self, address: str, timeout: float = 60.0):
        import time as _time

        deadline = _time.monotonic() + timeout
        with self._cv:
            while True:
                entry = self._peers.get(address)
                if entry is None:
                    entry = {"idle": [], "total": 0}
                    self._peers[address] = entry
                if entry["idle"]:
                    return entry["idle"].pop()
                if entry["total"] < self._max_per_peer:
                    entry["total"] += 1
                    break
                # every checked-out conn is checked back in via a finally
                # in pull_chunk, so this wait is bounded by a chunk round
                # trip; the re-check guards against a dropped peer
                if not self._cv.wait(timeout=min(1.0, max(0.0, deadline - _time.monotonic()))):
                    if _time.monotonic() >= deadline:
                        raise OSError(f"no free data connection to {address}")
        try:
            return self._dial(address)
        except BaseException:
            # the reserved slot must be released, or the peer's pool shrinks
            # permanently with every failed dial
            with self._cv:
                entry = self._peers.get(address)
                if entry is not None and entry["total"] > 0:
                    entry["total"] -= 1
                self._cv.notify_all()
            raise

    def _checkin(self, address: str, conn, broken: bool = False):
        with self._cv:
            entry = self._peers.get(address)
            if broken or entry is None:
                if entry is not None and entry["total"] > 0:
                    entry["total"] -= 1
                self._cv.notify_all()
            else:
                entry["idle"].append(conn)
                self._cv.notify_all()
                return
        try:
            conn.close()
        except OSError:
            pass

    def drop(self, address: str):
        """Forget pooled connections to a dead/stale peer. In-flight
        checkouts fail on their own and release their slots at checkin."""
        with self._cv:
            entry = self._peers.get(address)
            if entry is None:
                return
            idle, entry["idle"] = entry["idle"], []
            entry["total"] = max(0, entry["total"] - len(idle))
            if entry["total"] == 0:
                self._peers.pop(address, None)
            self._cv.notify_all()
        for conn in idle:
            try:
                conn.close()
            except OSError:
                pass

    def pull_chunk(
        self, address: str, oid_bytes: bytes, offset: int, length: int,
        retries: int = 3,
    ):
        """Returns (total_size, chunk_bytes). Raises ChunkPullError when the
        owner does not have the object; OSError after transport retries."""
        import time as _time

        last_err: Optional[BaseException] = None
        for attempt in range(retries):
            try:
                conn = self._checkout(address)
            except (OSError, ConnectionError) as e:
                last_err = e
                _time.sleep(0.05 * (attempt + 1))
                continue
            ok = False
            try:
                conn.send(("chunk", oid_bytes, offset, length))
                result = conn.recv()
                ok = True
            except (OSError, EOFError, ConnectionError) as e:
                last_err = e
            finally:
                self._checkin(address, conn, broken=not ok)
            if not ok:
                _time.sleep(0.05 * (attempt + 1))
                continue
            if isinstance(result, tuple) and result and result[0] == "error":
                raise ChunkPullError(result[1])
            return result
        raise last_err  # type: ignore[misc]

    def close(self):
        with self._cv:
            conns = [c for e in self._peers.values() for c in e["idle"]]
            self._peers.clear()
            self._cv.notify_all()
        for conn in conns:
            try:
                conn.close()
            except OSError:
                pass


def _buffer_sink(buf):
    """Chunk sink writing into a preallocated buffer; disjoint-range writes
    are thread-safe (each chunk owns its slice)."""
    mv = memoryview(buf)

    def sink(offset: int, data):
        mv[offset : offset + len(data)] = data

    return sink


def pull_windowed(fetch, sink, size: int, chunk_bytes: int, window: int):
    """Pull ``[0, size)`` in ``chunk_bytes`` pieces keeping up to ``window``
    chunk fetches in flight, writing each completed chunk through
    ``sink(offset, bytes)``.

    ``fetch(offset, length) -> (total_size, bytes)`` owns per-chunk retry /
    source failover and may return SHORT chunks (a server caps lengths at
    its own chunk config) — the remainder is re-requested. The first chunk
    error propagates after the in-flight window drains (workers are joined
    before return; a failed transfer leaks no thread)."""
    import threading

    def pull_one(off: int):
        ln = min(chunk_bytes, size - off)
        got = 0
        while got < ln:
            _, data = fetch(off + got, ln - got)
            if not data:
                raise ChunkPullError(f"empty chunk at {off + got}/{size}")
            sink(off + got, data)
            got += len(data)

    offsets = list(range(0, size, chunk_bytes))
    if window <= 1 or len(offsets) <= 1:
        for off in offsets:
            pull_one(off)
        return

    it = iter(offsets)
    lock = threading.Lock()
    errors: list = []

    def worker():
        while True:
            with lock:
                if errors:
                    return
                off = next(it, None)
            if off is None:
                return
            try:
                pull_one(off)
            except BaseException as e:  # noqa: BLE001 — re-raised by caller
                with lock:
                    errors.append(e)
                return

    threads = [
        threading.Thread(target=worker, daemon=True, name="chunk-pull")
        for _ in range(min(window, len(offsets)))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


class ReplicaFetcher:
    """Per-chunk fetch over a replica set with load spreading + failover
    (reference: the PullManager picks among known locations,
    ``pull_manager.h:49``; ownership directory supplies the set).

    Thread-safe: chunk fetches spread round-robin from a random start
    across ``sources``; a source that fails is dropped for the REST of the
    pull (and reported through ``on_source_fail`` so callers can invalidate
    their location caches). When every source is gone, ``fallback(offset,
    length)`` — typically the head relay — serves the chunk; with no
    fallback the pull fails."""

    def __init__(
        self, pool: "ChunkConnPool", oid_bytes: bytes, sources,
        fallback=None, on_source_fail=None,
    ):
        import itertools as _it
        import random as _random
        import threading

        self._pool = pool
        self._oid = oid_bytes
        self._sources = list(sources)
        self._rr = _it.count(
            _random.randrange(len(self._sources)) if self._sources else 0
        )
        self._lock = threading.Lock()
        self._fallback = fallback
        self._on_fail = on_source_fail
        self.peer_chunks = 0
        self.fallback_chunks = 0

    def __call__(self, offset: int, length: int):
        while True:
            with self._lock:
                srcs = list(self._sources)
            if not srcs:
                break
            addr = srcs[next(self._rr) % len(srcs)]
            try:
                result = self._pool.pull_chunk(addr, self._oid, offset, length)
            except (ChunkPullError, OSError, EOFError, ConnectionError) as e:
                with self._lock:
                    if addr in self._sources:
                        self._sources.remove(addr)
                if self._on_fail is not None:
                    self._on_fail(addr, e)
                continue
            with self._lock:
                self.peer_chunks += 1
            return result
        if self._fallback is None:
            raise ChunkPullError(
                f"no live source for chunk at offset {offset}"
            )
        result = self._fallback(offset, length)
        with self._lock:
            self.fallback_chunks += 1
        return result


def token_to_authkey(token: str) -> bytes:
    """Derive the control-plane authkey from a shared cluster token."""
    import hashlib

    return hashlib.sha256(b"rtpu-cluster:" + token.encode()).digest()[:16]


def routable_host() -> str:
    """Best-effort externally-routable IP of this host. The UDP-connect
    trick sends no packets; the kernel just resolves the egress interface."""
    import socket

    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        return "127.0.0.1"


# ---- op catalog -----------------------------------------------------------
#
# The string-keyed request surface (``Request.op``). These sets are the
# RUNTIME half of the wire contract: the controller and worker validate
# chaos-injection config keys against them at parse time (a typo'd op name
# would otherwise never inject and every fault-injection test relying on it
# passes vacuously). The STATIC half is tpulint's ``wire-conformance``
# family, which extracts the real dispatch branches and send sites from the
# AST and fails the lint gate when these literals drift from the code —
# see ``ray_tpu/devtools/lint/wire.py`` and ``docs/PROTOCOL.md``.

# Every op `Controller._dispatch_request` handles.
CONTROLLER_OPS = frozenset(
    {
        "actor_creation_failed",
        "actor_creation_stats",
        "actor_direct_endpoint",
        "actor_placed",
        "actor_placed_batch",
        "actor_state",
        "add_node",
        "add_ref",
        "autoscaler_state",
        "available_resources",
        "cancel",
        "cluster_metrics",
        "cluster_resources",
        "debug_worker_msg_count",
        "drain_node",
        "drain_status",
        "get_named_actor",
        "head_arena",
        "kill_actor",
        "kv_del",
        "kv_get",
        "kv_keys",
        "kv_put",
        "list_actors",
        "list_objects",
        "list_placement_groups",
        "list_tasks",
        "list_workers",
        "log_get",
        "log_list",
        "log_tail_buffer",
        "node_preempt_notice",
        "nodes",
        "object_locations",
        "pg_create",
        "pg_ready",
        "pg_remove",
        "pg_table",
        "proxy_stats",
        "pubsub_poll",
        "pubsub_publish",
        "pull_into_arena",
        "pull_object_chunk",
        "push_object_chunk",
        "reconcile_report",
        "recovery_stats",
        "register_replica",
        "remove_node",
        "report_agent_spill",
        "report_observability",
        "report_proxy_stats",
        "set_tenant_quota",
        "shm_create",
        "stream_abandoned",
        "stream_consumed_get",
        "stream_consumed_report",
        "submit_batch",
        "submit_task",
        "task_events",
        "tasks_pending",
        "tenant_stats",
        "testing_lose_object",
        "transfer_stats",
        "unregister_replica",
        "wait",
        "worker_stacks",
    }
)

# Ops a node agent intercepts for its local workers (node-local data plane,
# plus the observability push — the agent buffers its workers' span/metric
# reports and piggybacks the node's merged payload on its report tick).
# Must stay a subset of CONTROLLER_OPS: head-side workers have no agent, so
# an agent-only op would work on agent nodes and break on the head node.
AGENT_LOCAL_OPS = frozenset(
    {
        "pull_into_arena",
        "pull_object_chunk",
        "report_observability",
        "shm_create",
        "transfer_stats",
    }
)

# Worker-side chaos channel names that are not request ops (the plasma /
# object-channel analogs injected by RAY_TPU_WORKER_RPC_FAILURE).
WORKER_CHANNEL_OPS = frozenset({"get_objects", "plasma_read", "put_object"})

def parse_worker_chaos_table(spec: str) -> dict:
    """Parse ``RAY_TPU_WORKER_RPC_FAILURE`` (``"op=prob,op=prob"``),
    validating keys against the op catalog — a typo'd channel/op name
    silently never injects, so every chaos test relying on it would pass
    vacuously. Shared by the worker runtime and the node agent (the
    agent's own controller calls — the lease report channel — ride the
    same table)."""
    table: dict = {}
    for part in spec.split(","):
        name, _, prob = part.partition("=")
        table[name.strip()] = float(prob)
    unknown = set(table) - CONTROLLER_OPS - WORKER_CHANNEL_OPS
    if unknown:
        raise ValueError(
            f"RAY_TPU_WORKER_RPC_FAILURE names unknown op(s) "
            f"{sorted(unknown)} (see docs/PROTOCOL.md)"
        )
    return table


# Controller→agent PUSH messages (typed dataclasses, not Request ops) with a
# chaos-injection channel: `RAY_testing_rpc_failure` keys naming one of these
# fail the SEND (the grant never reaches the agent), exercising the
# retry/re-place path without a receiver-side hook. Kept separate from
# CONTROLLER_OPS so the wire-conformance declared-set check (which mirrors
# the `_dispatch_request` branch ladder) stays exact.
#
# "lease_batch" covers the batched grant push (``LeaseBatch``): an injected
# failure drops the WHOLE batch before the wire, and the scheduler requeues
# every lease it carried — exercising idempotent re-grant of a lost batch.
# "agent_reconcile" covers the recovery ask (``AgentReconcile``): an injected
# failure drops the push before the wire, exercising the head's single
# bounded re-ask (see Controller._recovery_monitor).
# "replicate_objects" covers the preempt-evacuation push
# (``ReplicateObjects``): an injected failure drops the replicate ask before
# the wire — the drain loop's pull-to-head fallback (``_migrate_node_objects``)
# still re-homes the sole-copy objects, exercising the degraded path.
AGENT_PUSH_OPS = frozenset(
    {"agent_reconcile", "lease_actor", "lease_batch", "replicate_objects"}
)


# Controller-internal chaos channels that are neither request ops nor agent
# pushes: "wal_write" fails the next write-ahead-journal flush, exercising
# the loud degrade to snapshot-only durability (rtpu_wal_errors counter,
# never a silent hole in the log).
INTERNAL_CHAOS_OPS = frozenset({"wal_write"})


# ---- per-op idempotency classes (client-transparent head reconnect) -------
#
# The retry envelope around controller calls (worker_runtime.call_controller
# / DriverAPI.controller_call) consults these when a call is interrupted by
# a head restart: READ ops replay freely, IDEMPOTENT writes replay safely
# (the head dedups — replayed submit_batch/submit_task skip specs already
# pending or sealed; seals/frees/kv writes converge), and everything else
# surfaces a typed ``HeadRestartedError`` instead of guessing.

READ_ONLY_OPS = frozenset(
    {
        "actor_creation_stats",
        "actor_direct_endpoint",
        "actor_state",
        "autoscaler_state",
        "available_resources",
        "cluster_metrics",
        "cluster_resources",
        "debug_worker_msg_count",
        "drain_status",
        "get_named_actor",
        "head_arena",
        "kv_get",
        "kv_keys",
        "list_actors",
        "list_objects",
        "list_placement_groups",
        "list_tasks",
        "list_workers",
        "log_get",
        "log_list",
        "log_tail_buffer",
        "nodes",
        "object_locations",
        "pg_ready",
        "pg_table",
        "proxy_stats",
        "pubsub_poll",
        "pull_object_chunk",
        "recovery_stats",
        "stream_consumed_get",
        "task_events",
        "tasks_pending",
        "tenant_stats",
        "transfer_stats",
        "wait",
        "worker_stacks",
    }
)

IDEMPOTENT_OPS = frozenset(
    {
        "cancel",
        "drain_node",
        "kill_actor",
        "kv_del",
        "kv_put",
        "node_preempt_notice",
        "pull_into_arena",
        "push_object_chunk",
        "reconcile_report",
        "register_replica",
        "remove_node",
        "report_agent_spill",
        "report_observability",
        "report_proxy_stats",
        "set_tenant_quota",
        "stream_consumed_report",
        "submit_batch",
        "submit_task",
        "unregister_replica",
    }
)

# Everything else in CONTROLLER_OPS replays unsafely: add_ref (a replay
# double-counts), pg_create (a replay reserves a second group), shm_create
# (a replay allocates a second segment), pubsub_publish (duplicate events),
# stream_abandoned (an at-most-once signal), testing hooks.


def op_idempotency(op: str) -> str:
    """'read' | 'idempotent' | 'once' for a controller request op (worker
    channel names — get_objects/put_object — classify as reads/idempotent
    at their call sites)."""
    if op in READ_ONLY_OPS:
        return "read"
    if op in IDEMPOTENT_OPS:
        return "idempotent"
    return "once"


# ---- worker -> controller ----

@dataclasses.dataclass
class RegisterWorker:
    worker_id: WorkerID
    pid: int
    # "host:port" of this worker's direct actor-call listener (None for
    # thread-mode/in-process workers). Callers push actor calls straight to
    # this address, bypassing the head (reference: the direct PushTask
    # transport, src/ray/core_worker/transport/actor_task_submitter.h).
    direct_address: Optional[str] = None


@dataclasses.dataclass
class RegisterDriver:
    """A CLIENT driver attaching to a running cluster (``ray://`` analog,
    reference: ``python/ray/util/client/``). Drivers get the full object/
    task/actor API over the same channel but are never schedulable."""

    driver_id: WorkerID
    pid: int


@dataclasses.dataclass
class TaskDone:
    task_id: TaskID
    # list of (object_id, kind, payload): kind in {"inline", "plasma", "error"}
    # inline/error payload = flattened SerializedObject bytes;
    # plasma payload = (shm_name, size)
    results: list
    actor_id: Optional[ActorID] = None
    # Execution info for observability (task events; reference:
    # task_event_buffer.h).
    exec_ms: float = 0.0


@dataclasses.dataclass
class GetObjects:
    req_id: int
    object_ids: list


@dataclasses.dataclass
class PutObject:
    req_id: int
    object_id: ObjectID
    # Either inline bytes or a plasma (shm_name, size) the worker created.
    kind: str
    payload: Any


@dataclasses.dataclass
class WorkerError:
    message: str
    task_id: Optional[TaskID] = None


@dataclasses.dataclass
class Request:
    """Generic worker→controller RPC (submit_task, register_actor, kv ops,
    placement-group ops, state queries, ref counting...)."""

    req_id: int
    op: str
    payload: Any


@dataclasses.dataclass
class Reply:
    req_id: int
    payload: Any
    error: Optional[str] = None


@dataclasses.dataclass
class FreeObjects:
    object_ids: list


@dataclasses.dataclass
class StacksReply:
    """Worker → controller: formatted thread stacks (on-demand profiling,
    reference: ``dashboard/modules/reporter`` py-spy integration)."""

    req_id: int
    text: str


# ---- controller -> worker ----

@dataclasses.dataclass
class DumpStacks:
    """Controller → worker: dump every thread's Python stack."""

    req_id: int


@dataclasses.dataclass
class ExecuteTask:
    spec: TaskSpec
    # Resolved args: parallel to spec.args; refs replaced by ("inline", bytes)
    # or ("plasma", (shm_name, size)).
    resolved_args: list


@dataclasses.dataclass
class GetReply:
    req_id: int
    # list of (object_id, kind, payload) — kind in {"inline","plasma","error"}
    results: list


@dataclasses.dataclass
class PutAck:
    req_id: int


@dataclasses.dataclass
class KillActor:
    actor_id: ActorID


@dataclasses.dataclass
class StealTasks:
    """Controller → worker: return up to ``count`` not-yet-started pipelined
    tasks so they can be re-dispatched to an idle worker (reference: the
    work-stealing companion of max_tasks_in_flight_per_worker pipelining in
    the direct task submitter)."""

    count: int


@dataclasses.dataclass
class TasksStolen:
    """Worker → controller: task ids whose queued futures were successfully
    cancelled (never started); the controller re-enqueues them."""

    task_ids: list  # of bytes (TaskID.binary())


@dataclasses.dataclass
class Shutdown:
    pass


# ---- caller <-> actor worker (direct transport; the head is NOT on this
# path — reference: ActorTaskSubmitter pushes calls worker-to-worker over
# gRPC without a raylet/GCS hop, actor_task_submitter.h) ----

@dataclasses.dataclass
class DirectActorCall:
    """Caller → actor worker: execute this actor task and reply on THIS
    connection. ``resolved_args`` carries the template plus caller-resolved
    ref payloads (same shape as ExecuteTask.resolved_args); ordering is the
    connection's FIFO order (caller-side sequencing)."""

    req_id: int
    spec: TaskSpec
    resolved_args: list


@dataclasses.dataclass
class DirectCallReply:
    """Actor worker → caller: results of a DirectActorCall. Always inline
    or error payloads — the result rides the direct connection, never the
    head's store (kind in {"inline", "error"})."""

    req_id: int
    results: list  # [(object_id, kind, payload_bytes)]


# ---- node agent <-> controller (real multi-host worker plane; reference:
# the raylet's NodeManager gRPC surface, src/ray/raylet/node_manager.h:124,
# and `ray start --address=<head>`, python/ray/scripts/scripts.py:226) ----

@dataclasses.dataclass
class RegisterAgent:
    """Agent → controller: a REAL node joining the cluster. The agent owns
    its host's worker pool and plasma arena; objects it seals are served to
    peers over its ``data_address`` chunk listener (reference:
    ObjectManager, object_manager.h:119)."""

    node_id: Any  # NodeID
    resources: dict
    labels: dict
    arena_name: Optional[str]
    data_address: Optional[str]  # "host:port" peers pull chunks from
    pid: int = 0
    hostname: str = ""
    # True on a reconnect attempt that PRESERVED local state (workers,
    # arena, held leases) hoping the head restarted and wants to reconcile
    # (reference: raylet resubscribe after NotifyGCSRestart). The head
    # answers with AgentAck.resume_verdict.
    resume: bool = False


@dataclasses.dataclass
class AgentAck:
    """Controller → agent: registration accepted (or, for a resume
    attempt, refused — see ``resume_verdict``)."""

    node_id_hex: str
    head_data_address: Optional[str] = None
    # Resume protocol: "fresh" (normal registration), "reconcile" (the head
    # is RECOVERING and accepts the preserved state — an AgentReconcile ask
    # follows on this connection), or "reset" (preserved state refused: the
    # head never died, or the recovery window closed and journaled leases
    # were already re-placed — the agent must tear down local state and
    # re-register fresh, exactly-once execution depends on it).
    resume_verdict: str = "fresh"


@dataclasses.dataclass
class AgentReconcile:
    """Controller → agent: the restarted head asks for this node's truth
    (reference: raylet resubscribe/reconciliation after a GCS restart).
    The agent answers with the ``reconcile_report`` request op carrying its
    held task/creation leases, alive workers and actors (with pids as
    incarnations), recently-completed done reports the crashed head may
    never have journaled, and its arena object inventory."""

    deadline_s: float
    # bumps on the head's bounded re-ask so a duplicate report is
    # distinguishable in logs (application is idempotent either way)
    ask_seq: int = 1


@dataclasses.dataclass
class HeadRestarted:
    """Agent → local worker: the head connection was lost and re-established
    against a restarted controller. In-flight controller calls relayed
    through the agent lost their replies — the worker bumps its connection
    epoch so blocked waiters unblock and the per-op retry envelope decides
    (replay reads/idempotent writes, surface HeadRestartedError otherwise)."""

    epoch: int = 0


@dataclasses.dataclass
class SpawnWorker:
    """Controller → agent: start one worker process on the agent's host
    (remote half of WorkerPool::StartWorkerProcess, worker_pool.h:283)."""

    worker_id: WorkerID
    env_vars: dict
    tpu_chips: int
    fingerprint: tuple
    # runtime-env payloads shipped by value: [(kind, name, zip_bytes)] where
    # kind in {"working_dir", "py_module"} (reference: working_dir packaging
    # via GCS KV upload, _private/runtime_env/packaging.py)
    packages: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class KillWorker:
    """Controller → agent: hard-kill a worker process (ray.kill path)."""

    worker_id: WorkerID


@dataclasses.dataclass
class LeaseTask:
    """Controller → agent: run this normal task on YOUR worker pool — the
    second level of two-level scheduling. The head picked the node and holds
    the resource charge; the agent owns worker pop/spawn/queueing locally
    (reference: ClusterTaskManager assigns a node, the raylet's
    LocalTaskManager dispatches, cluster_task_manager.h:44,
    local_task_manager.h:60)."""

    spec: Any  # TaskSpec
    resolved_args: list
    tpu_chips: int
    env_vars: dict


@dataclasses.dataclass
class LeaseActor:
    """Controller → agent: a CREATION LEASE — the head picked this node for
    the actor and charged its resources at grant; the agent owns the entire
    local lifecycle from here (worker pool-pop or fresh spawn, runtime-env
    build, creation-task dispatch, readiness/registration handshake,
    direct-call listener advertisement) and reports back with the
    ``actor_placed`` / ``actor_creation_failed`` request ops (reference:
    GcsActorScheduler leasing creation to the raylet end-to-end,
    ``gcs_actor_scheduler.cc:55``)."""

    spec: Any  # TaskSpec (ACTOR_CREATION_TASK)
    resolved_args: list
    tpu_chips: int
    env_vars: dict
    fingerprint: tuple
    # runtime-env payloads shipped by value, same shape as SpawnWorker's
    packages: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class LeaseBatch:
    """Controller → agent: N lease grants (``LeaseTask``/``LeaseActor``) in
    ONE push — the scheduler's per-round outbox coalesces every grant bound
    for the same agent instead of paying one wire frame per lease
    (reference: the raylet pipelines lease traffic while the GCS owns
    durable state, PAPER.md L4/L5). Order within the batch is the
    scheduler's dispatch order; the agent unpacks FIFO, so per-agent grant
    ordering is exactly what N single pushes gave."""

    leases: list  # of LeaseTask | LeaseActor


@dataclasses.dataclass
class AgentTaskDone:
    """Agent → controller: a leased task finished (results already sealed
    into the agent's arena where plasma-sized)."""

    task_id: Any  # TaskID
    results: list  # [(object_id, kind, payload)]
    exec_ms: float = 0.0


@dataclasses.dataclass
class AgentReportBatch:
    """Agent → controller: N per-task completion reports coalesced per
    flush tick (``AgentTaskDone`` entries, FIFO). A steady-state agent
    completing hundreds of short leases per second pays one wire frame per
    tick instead of one per task; the head processes entries in order, and
    each completion may immediately re-arm the finishing node with the next
    queued same-(tenant, shape) spec (agent lease caching — see
    ``Controller._maybe_rearm_locked``).

    ``observability`` piggybacks the node's due span/metric report on the
    same tick (a list of per-reporter entries, the exact shape the
    ``report_observability`` request op carries) — the observability plane
    adds ZERO wire frames on the hot path. None when nothing is due."""

    items: list  # of AgentTaskDone
    observability: Any = None  # list of reporter entries, or None


@dataclasses.dataclass
class TaskSpilled:
    """Agent → controller: leased tasks this agent is handing back — local
    overload or a dead worker. The head re-places them elsewhere (reference:
    scheduler spillback, hybrid_scheduling_policy.h:50)."""

    task_ids: list  # of bytes (TaskID.binary())
    reason: str = "overload"  # or "worker_died"


@dataclasses.dataclass
class ToWorker:
    """Controller → agent envelope: deliver ``msg`` to a local worker."""

    worker_id: WorkerID
    msg: Any


@dataclasses.dataclass
class FromWorker:
    """Agent → controller envelope: ``msg`` originated from a local worker."""

    worker_id: WorkerID
    msg: Any


@dataclasses.dataclass
class WorkerDied:
    """Agent → controller: a local worker's connection/process died."""

    worker_id: WorkerID
    reason: str


@dataclasses.dataclass
class DrainAgent:
    """Controller → agent: quiesce for graceful node release (reference:
    ``NodeManager::HandleDrainRaylet``, ``src/ray/raylet/node_manager.cc:1989``).
    The agent must reject new leases (spill them back with reason
    "draining"), let running/queued leased work finish within the deadline,
    flush captured worker logs, and reply with ``AgentDrained``."""

    deadline_s: float
    reason: str = ""


@dataclasses.dataclass
class ReplicateObjects:
    """Controller → agent: proactively pull these objects into YOUR arena
    and register as a replica (the preempt-notice evacuation path — a
    terminating node's sole-copy objects re-home onto surviving nodes
    BEFORE the arena dies, so readers promote a replica instead of paying
    lineage re-execution). Each entry is ``(object_id, size)``; the agent
    pulls via its normal single-flight pull-into-arena machinery, so a
    concurrent reader's pull coalesces with the evacuation."""

    objects: list  # [(ObjectID, size_bytes)]


@dataclasses.dataclass
class AgentDrained:
    """Agent → controller: the quiesce handshake completed — no leased task
    is running or queued locally and worker logs were flushed. ``remaining``
    reports tasks still in flight when the quiesce deadline lapsed (0 on a
    clean drain)."""

    node_id: Any  # NodeID
    remaining: int = 0


@dataclasses.dataclass
class Heartbeat:
    """Agent → controller: periodic liveness + load (reference: the GCS
    health-check service, gcs_health_check_manager.h)."""

    node_id: Any  # NodeID
    load: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class WorkerLogLines:
    """Agent → controller: new stdout/stderr lines captured from a local
    worker's log files (the remote half of the log monitor; reference:
    ``log_monitor.py`` publishing tailed lines to the driver)."""

    worker_id_hex: str
    source: str  # "out" | "err"
    lines: list


@dataclasses.dataclass
class FetchLogs:
    """Controller → agent: read the tail of a (possibly dead) worker's
    captured log file."""

    req_id: int
    worker_id_hex: str
    source: str
    tail_bytes: int


@dataclasses.dataclass
class LogsReply:
    """Agent → controller: FetchLogs response."""

    req_id: int
    text: str


@dataclasses.dataclass
class FreeLocal:
    """Controller → agent: drop these objects from the agent's arena (the
    owner-driven free path of the distributed ref counter)."""

    object_ids: list
