"""The controller: single-host control plane (GCS + raylet analog).

Runs inside the driver process as a set of threads. Responsibilities mirror
the reference's head-node stack:

- cluster membership + resource accounting       ≈ GcsNodeManager/GcsResourceManager
  (``src/ray/gcs/gcs_server/gcs_server.cc:219``)
- task queueing + scheduling policies            ≈ ClusterTaskManager/LocalTaskManager
  (``src/ray/raylet/scheduling/cluster_task_manager.h:44``)
- worker process pool with on-demand spawn       ≈ WorkerPool (``src/ray/raylet/worker_pool.h:283``)
- actor directory + restart                      ≈ GcsActorManager (``gcs_actor_manager.cc:398``)
- object directory + dependency management       ≈ OwnershipObjectDirectory + DependencyManager
- reference counting + freeing                   ≈ ReferenceCounter (``reference_count.h:73``)
- internal KV                                    ≈ GCS internal KV

Data plane (object payloads) bypasses the controller: workers write to the
shared-memory plasma store and only locations travel through here — the same
split the reference makes between raylet control RPCs and plasma.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import itertools
import tempfile
import threading
import time
import traceback
from collections import OrderedDict, defaultdict, deque
from concurrent.futures import ThreadPoolExecutor
from multiprocessing.connection import Listener
from typing import Any, Optional

from ray_tpu._private import locktrace
from ray_tpu._private import protocol as P
from ray_tpu._private import tenants as tenants_mod
from ray_tpu._private.config import Config
from ray_tpu._private.ids import (
    ActorID,
    NodeID,
    ObjectID,
    PlacementGroupID,
    TaskID,
    WorkerID,
)
from ray_tpu._private.object_store import MemoryStore, PlasmaClient, PlasmaStore
from ray_tpu._private.serialization import SerializationContext, SerializedObject
from ray_tpu._private.task_spec import TaskSpec, TaskType
from ray_tpu.tpu.accelerator import ChipPool, chip_worker_env, chips_requested
from ray_tpu.exceptions import (
    ActorDiedError,
    ObjectLostError,
    PlacementGroupSchedulingError,
    TaskError,
    WorkerCrashedError,
)

logger = logging.getLogger(__name__)

# ---- dispatch shard tables -------------------------------------------------
#
# Which subsystem shard handles each request op (see
# ``Controller._dispatch_request``). The union MUST equal
# ``protocol.CONTROLLER_OPS`` — asserted at controller init; the lint gate's
# wire-conformance family separately keeps CONTROLLER_OPS in sync with the
# shard ladders themselves.

TASK_SHARD_OPS = frozenset({
    "submit_task", "submit_batch", "cancel", "tasks_pending", "task_events",
    "list_tasks", "debug_worker_msg_count",
})
ACTOR_SHARD_OPS = frozenset({
    "actor_direct_endpoint", "get_named_actor", "actor_state", "kill_actor",
    "list_actors", "actor_placed", "actor_placed_batch",
    "actor_creation_failed", "actor_creation_stats",
})
OBJECT_SHARD_OPS = frozenset({
    "add_ref", "wait", "shm_create", "push_object_chunk",
    "pull_object_chunk", "pull_into_arena", "object_locations",
    "register_replica", "unregister_replica", "transfer_stats",
    "report_agent_spill", "testing_lose_object", "stream_consumed_report",
    "stream_abandoned", "stream_consumed_get", "list_objects", "head_arena",
})
NODE_SHARD_OPS = frozenset({
    "add_node", "remove_node", "drain_node", "drain_status", "nodes",
    "cluster_resources", "available_resources", "autoscaler_state",
    "list_workers", "pg_create", "pg_ready", "pg_remove", "pg_table",
    "list_placement_groups", "reconcile_report", "set_tenant_quota",
    "tenant_stats", "node_preempt_notice",
})
KV_SHARD_OPS = frozenset({"kv_put", "kv_get", "kv_del", "kv_keys"})
OBSERVE_SHARD_OPS = frozenset({
    "cluster_metrics", "log_get", "log_list", "log_tail_buffer",
    "proxy_stats", "pubsub_poll", "pubsub_publish", "recovery_stats",
    "report_observability", "report_proxy_stats", "worker_stacks",
})


class NodeState:
    def __init__(self, node_id: NodeID, resources: dict[str, float], labels=None):
        self.node_id = node_id
        self.total = dict(resources)
        self.available = dict(resources)
        self.labels = labels or {}
        self.alive = True
        # Graceful drain (reference: NodeManager::HandleDrainRaylet,
        # node_manager.cc:1989): a DRAINING node accepts no new leases,
        # placements, or placement-group bundles; running work finishes
        # within the drain deadline, restartable actors migrate off, and
        # resident objects are pulled to the head before release.
        self.draining = False
        self.drain_reason: Optional[str] = None
        self.drain_deadline = 0.0
        # Termination notice received (spot/maintenance reclaim announced):
        # a preempt drain additionally re-replicates sole-copy arena
        # objects to surviving nodes, and the autoscaler treats the node
        # as already-dead for replacement purposes (launches a substitute
        # immediately instead of waiting out heartbeat loss).
        self.preempting = False
        # Set for REAL remote nodes (agent-backed); None for the head node
        # and fake test nodes (reference: raylet vs. cluster_utils nodes).
        self.agent: Optional["AgentHandle"] = None
        self.last_heartbeat = time.monotonic()
        # Worker-pool discipline (see Config.worker_pool_soft_limit): pooled
        # task workers alive + starting on this node, and when a task last
        # finished here (a recent completion means the pool is churning and
        # will free a worker shortly — growing it would spawn-storm).
        self.task_workers = 0
        self.starting_workers = 0
        # chips spoken for by worker spawns still in flight here: a spawn
        # for a TPU grant may wait for the previous holder's exit, and a
        # second speculative one would only wait behind it
        self.starting_chips = 0
        self.last_task_done_t = 0.0
        # Normal tasks leased to this node's agent for LOCAL dispatch
        # (two-level scheduling): task_id binary -> PendingTask. The head
        # holds the resource charge; the agent owns worker pop/queueing.
        self.leased: dict[bytes, "PendingTask"] = {}
        # Actor CREATION leases granted to this node's agent (reference:
        # GcsActorScheduler leasing creation to the raylet,
        # gcs_actor_scheduler.cc:55): creation task_id binary ->
        # PendingTask. Resources are charged at grant; the agent owns the
        # whole local lifecycle (spawn, handshake, creation dispatch) and
        # reports back via the actor_placed / actor_creation_failed ops.
        # A node dying mid-lease requeues these WITHOUT charging the
        # actor's restart budget (see remove_node).
        self.actor_leases: dict[bytes, "PendingTask"] = {}

    @property
    def schedulable(self) -> bool:
        """May the scheduler place NEW work here? One predicate for every
        scheduler site — a node state added here (drain today, cordon
        tomorrow) applies everywhere at once."""
        return self.alive and not self.draining

    def fits(self, demand: dict[str, float]) -> bool:
        return all(self.available.get(k, 0.0) + 1e-9 >= v for k, v in demand.items())

    def allocate(self, demand: dict[str, float]):
        for k, v in demand.items():
            self.available[k] = self.available.get(k, 0.0) - v

    def release(self, demand: dict[str, float]):
        for k, v in demand.items():
            self.available[k] = self.available.get(k, 0.0) + v

    def utilization(self) -> float:
        fracs = [
            1.0 - self.available.get(k, 0.0) / t
            for k, t in self.total.items()
            if t > 0
        ]
        return max(fracs) if fracs else 0.0


class WorkerHandle:
    def __init__(self, worker_id: WorkerID, node_id: NodeID, proc=None, conn=None):
        self.worker_id = worker_id
        self.node_id = node_id
        self.proc = proc
        self.conn = conn
        self.registered = threading.Event()
        self.running: dict[TaskID, "PendingTask"] = {}
        self.actor_id: Optional[ActorID] = None
        self.dead = False
        self.last_idle_t = time.monotonic()
        self.send_lock = threading.Lock()
        # Environment fingerprint this worker was spawned with (TPU
        # visibility, runtime_env vars); only matching tasks may reuse it.
        self.fingerprint = (False, ())
        # True while this worker is counted in its node's task_workers pool
        # gauge — flipped exactly once each way so retirement paths can't
        # double- or miss-decrement (pool-cap accounting).
        self.pooled_counted = False
        # Active worker lease: (shape_key, NodeState, pg_bundle, demand).
        # The lease — not each task — holds the node/bundle resource charge;
        # same-shape normal tasks pipeline behind the running one up to
        # Config.max_tasks_in_flight_per_worker (reference: the per-
        # SchedulingKey leased-worker pipeline, normal_task_submitter.h:79).
        self.lease = None
        # one outstanding StealTasks request at a time per worker
        self.steal_pending = False
        # spawned and scheduled by a node agent's local dispatcher — the
        # head tracks identity only (never pools or dispatches onto it)
        self.agent_owned = False
        self.is_driver = False  # client drivers are never scheduling targets
        # "host:port" of the worker's direct actor-call listener (callers
        # push actor calls here, bypassing the head entirely)
        self.direct_address: Optional[str] = None
        # refs this client driver holds — released if it detaches uncleanly
        self.held_refs: set = set()
        # set for workers on agent-backed remote nodes
        self.agent = None

    def send(self, msg):
        with self.send_lock:
            self.conn.send(msg)


class AgentHandle:
    """Controller-side handle to a registered node agent (the raylet RPC
    client analog, ``src/ray/raylet_client/``). All traffic to the agent's
    host — worker envelopes, spawn/kill requests, frees — rides this one
    authenticated connection."""

    def __init__(self, node_id: NodeID, conn, arena_name, data_address):
        self.node_id = node_id
        self.conn = conn
        self.arena_name = arena_name
        self.data_address = data_address
        self.send_lock = threading.Lock()
        self.load: dict = {}

    def send(self, msg):
        with self.send_lock:
            self.conn.send(msg)


class _RelayConn:
    """Connection facade for a REMOTE worker: sends wrap in a ``ToWorker``
    envelope on the agent's control connection."""

    def __init__(self, agent: AgentHandle, worker_id: WorkerID):
        self._agent = agent
        self._worker_id = worker_id

    def send(self, msg):
        self._agent.send(P.ToWorker(self._worker_id, msg))

    def close(self):
        pass


class RemoteArenaProxy:
    """Controller-side stand-in for an agent-owned arena. The agent seals
    objects locally before forwarding their locations, so ``seal`` is a
    no-op here; ``delete`` relays the owner-driven free."""

    is_remote = True

    def __init__(self, agent: AgentHandle):
        self.agent = agent
        self.arena_name = agent.arena_name

    def seal(self, object_id, shm_name, size):
        pass

    def delete(self, object_id):
        try:
            self.agent.send(P.FreeLocal([object_id]))
        except (OSError, EOFError):
            pass

    def used_bytes(self) -> int:
        return int(self.agent.load.get("arena_used_bytes", 0))

    def num_objects(self) -> int:
        return 0

    def shutdown(self):
        pass


class PendingTask:
    def __init__(self, spec: TaskSpec, deps: set[ObjectID]):
        self.spec = spec
        self.unresolved = set(deps)
        self.all_deps = set(deps)
        self.retries_left = spec.max_retries
        self.worker: Optional[WorkerHandle] = None
        self.cancelled = False
        self.submit_t: float = time.time()  # head.sched span start
        self.dispatch_t: float = 0.0  # set when handed to a worker
        self.seq = 0  # global submission order (FIFO across shape queues)


class ActorState:
    def __init__(self, actor_id: ActorID, creation_spec: TaskSpec):
        self.actor_id = actor_id
        self.creation_spec = creation_spec
        self.state = "PENDING"  # PENDING | ALIVE | RESTARTING | DEAD
        self.worker: Optional[WorkerHandle] = None
        self.queue: deque[PendingTask] = deque()
        self.inflight = 0
        self.restarts_left = creation_spec.max_restarts
        self.death_cause: Optional[str] = None
        self.name: Optional[str] = None
        # (node, pg_bundle, resources) held while ALIVE.
        self.held: Optional[tuple] = None


class PlacementGroupState:
    def __init__(self, pg_id: PlacementGroupID, bundles: list[dict], strategy: str):
        self.pg_id = pg_id
        self.bundles = bundles  # resource dicts
        self.strategy = strategy
        self.bundle_nodes: list[Optional[NodeID]] = [None] * len(bundles)
        self.bundle_available: list[dict] = [dict(b) for b in bundles]
        self.ready = threading.Event()
        self.removed = False


def _package_path(path: str) -> tuple[str, bytes]:
    """Zip a file/directory for shipment to an agent host; returns
    (basename, zip bytes). Arcnames are rooted at the basename so the agent
    can stage ``<root>/<basename>`` as cwd or an import root."""
    import zipfile
    from io import BytesIO

    base = os.path.basename(path.rstrip(os.sep))
    bio = BytesIO()
    with zipfile.ZipFile(bio, "w", zipfile.ZIP_DEFLATED) as zf:
        if os.path.isdir(path):
            for root, _, files in os.walk(path):
                for f in files:
                    p = os.path.join(root, f)
                    zf.write(p, os.path.join(base, os.path.relpath(p, path)))
        else:
            zf.write(path, base)
    return base, bio.getvalue()


class Controller:
    def __init__(self, config: Config, head_resources: dict[str, float], mode: str = "process"):
        self.config = config
        self.mode = mode
        # RAY_TPU_<NAME> exports for every config field overridden from its
        # default — propagated to EVERY spawned worker: head-local spawns,
        # head-managed remote spawns, and agent lease grants (whose agents
        # spawn pool workers from the lease's env_vars). Without the lease
        # half, a driver's init(config=...) knobs silently reset to
        # defaults inside agent-spawned workers (the PR 13 noted tail).
        self._child_env_overrides = config.override_env()
        # the chips of THIS host, for workers this process spawns (agents
        # keep their own): one process for each chip at a time
        self._chips = ChipPool(chips_requested(head_resources))
        # Core scheduler/cluster-state lock. Registered as a SUBSYSTEM lock:
        # the sharded dispatch tables give some subsystems (KV) their own
        # lock, and locktrace asserts at runtime that no thread ever holds
        # two subsystem locks at once — the invariant that keeps the split
        # deadlock-free (cross-subsystem work sequences, never nests).
        self.lock = locktrace.subsystem_lock("controller.lock", threading.RLock())
        self.shutting_down = False
        # A shared cluster token derives a stable authkey so agents/drivers
        # on other hosts can join without the head's session file.
        self._authkey = (
            P.token_to_authkey(config.cluster_token)
            if config.cluster_token
            else os.urandom(16)
        )

        # Object plane. Prefer the native (C++) arena store; fall back to the
        # Python per-segment store if the toolchain can't build it.
        self.memory_store = MemoryStore()  # object_id -> (kind, payload)
        self.plasma = None
        if config.use_native_plasma:
            try:
                from ray_tpu._native import plasma as native_plasma
                from ray_tpu._private.object_store import NativePlasmaStore

                if native_plasma.available():
                    arena_name = f"/rtpu-{os.getpid()}-{time.time_ns() & 0xFFFFFF:x}"
                    self.plasma = NativePlasmaStore(
                        config.object_store_memory, arena_name
                    )
                    # workers inherit the controller's environ at spawn
                    os.environ["RAY_TPU_ARENA"] = arena_name
            except Exception:
                logger.warning("native plasma unavailable; using Python store",
                               exc_info=True)
        if self.plasma is None:
            os.environ.pop("RAY_TPU_ARENA", None)
            self.plasma = PlasmaStore(config.object_store_memory)
        self.plasma_client = PlasmaClient()

        # Cluster state.
        self.nodes: dict[NodeID, NodeState] = {}
        self.head_node_id = NodeID.from_random()
        self.nodes[self.head_node_id] = NodeState(self.head_node_id, head_resources)

        # Per-node object stores (the distributed data plane). Each node has
        # its own arena; workers attach only their node's arena, and a read
        # of an object resident on another node goes through the chunked
        # pull protocol (reference: ObjectManager/PullManager chunked
        # transfer, object_manager.h:119, pull_manager.h:49). The location
        # directory is the sealed entry itself — its arena name identifies
        # the owning node (OwnershipObjectDirectory merged into the
        # controller the way GCS managers are).
        self.node_stores: dict[NodeID, object] = {self.head_node_id: self.plasma}
        self._stores_by_arena: dict[str, object] = {}
        if hasattr(self.plasma, "arena_name"):
            self._stores_by_arena[self.plasma.arena_name] = self.plasma

        # Scheduling state.
        # Per-TENANT queue groups (the multi-tenant refactor of the old
        # single global shape-queue table): each tenant holds shape-keyed
        # ready queues — (tenant, resources, strategy, env fingerprint) ->
        # FIFO of placeable tasks. WITHIN a tenant, dispatch order across
        # shapes follows each head task's global submission seq (the
        # nested-submit interleave guarantee the single table had); ACROSS
        # tenants, a weighted deficit-round-robin pop bounds skew to the
        # configured shares, quotas park over-cap work at grant, and
        # priority tiers + drain-preemption serve urgent tenants first
        # (see _try_dispatch_locked / _maybe_preempt_locked and
        # ray_tpu/_private/tenants.py).
        self.tenants: dict[str, "tenants_mod.TenantState"] = {}
        # DRR rotation order over tenant names (rotated as credit tops up).
        self._tenant_ring: deque[str] = deque()
        # shape -> leased workers currently running that shape (pipelining
        # candidates for saturated shapes; see _try_pipeline)
        self.lease_index: dict[tuple, set] = defaultdict(set)
        self._enqueue_seq = itertools.count()
        self.waiting_on_deps: dict[ObjectID, list[PendingTask]] = defaultdict(list)
        self.pending_by_id: dict[TaskID, PendingTask] = {}
        self.sched_cv = threading.Condition(self.lock)

        # Workers.
        self.workers: dict[WorkerID, WorkerHandle] = {}
        self.idle_workers: dict[NodeID, list[WorkerHandle]] = defaultdict(list)
        self.starting_workers = 0
        # attached client drivers (ray:// analog) — full API, never scheduled
        self.driver_conns: dict[WorkerID, WorkerHandle] = {}

        # Actors.
        self.actors: dict[ActorID, ActorState] = {}
        self.named_actors: dict[str, ActorID] = {}

        # Placement groups.
        self.placement_groups: dict[PlacementGroupID, PlacementGroupState] = {}

        # Reference counting: driver-held handles + pins from pending tasks.
        self.ref_counts: dict[ObjectID, int] = defaultdict(int)

        # Lineage for object reconstruction (reference:
        # object_recovery_manager.h:43 + task_manager.h:168): return-id ->
        # (producer TaskSpec, approx bytes). Deterministic return ids
        # (ids.py ObjectID.for_return) make a resubmitted producer's results
        # land under the SAME object ids, so blocked getters just wake up.
        self.lineage: "OrderedDict[ObjectID, tuple[TaskSpec, int]]" = OrderedDict()
        self.lineage_bytes = 0
        self._recovering: set[TaskID] = set()
        # Transitive-reconstruction depth per resubmitted producer: a
        # resubmitted task whose OWN deps were lost kicks their producers
        # at depth+1; chains past lineage_reconstruction_max_depth stop
        # with ObjectLostError instead of recursing unboundedly. Entries
        # clear with _recovering (seal / terminal failure / failed
        # resubmit).
        self._recon_depth: dict[TaskID, int] = {}
        # in-flight chunked pushes from arena-less client drivers:
        # object_id -> (buffer, {offset: length})
        self._pending_pushes: dict[ObjectID, tuple[bytearray, dict]] = {}

        # Streaming-generator consumer progress (backpressure): task_id ->
        # highest item index the consumer has taken. Bounded FIFO.
        self._stream_consumed: dict[TaskID, int] = {}
        # on-demand profiling: req_id -> (Event, [stack text])
        self._stack_waiters: dict[int, tuple] = {}
        self._stack_req_counter = itertools.count(1)

        # general pub/sub (reference: GCS pubsub, src/ray/pubsub/ — actor
        # and node event channels with long-poll subscribers; the serve
        # long-poll is the same pattern specialized to replica sets)
        self._pubsub_events: dict[str, deque] = defaultdict(
            lambda: deque(maxlen=1000)
        )
        self._pubsub_seq: dict[str, int] = defaultdict(int)
        self._pubsub_cv = locktrace.register_lock(
            "controller.pubsub_cv", threading.Condition()
        )
        # Producer-side pins of streamed items: sealed stream items have no
        # consumer handle yet, so the producer pins them (else the eager
        # refcount-0 free in _on_object_sealed reclaims them instantly).
        # The pin transfers to the consumer at stream_consumed_report; any
        # leftovers release when the completion record is freed.
        self._stream_pins: dict[TaskID, set[int]] = {}

        # Node drain records: node_id -> status dict (kept after completion
        # so the state API / autoscaler can observe the outcome of a drain
        # whose node has already left the cluster). Bounded FIFO.
        self.drains: "OrderedDict[NodeID, dict]" = OrderedDict()

        # Real remote nodes (agent-backed): node_id -> AgentHandle; plus
        # which objects are resident on each remote arena (the controller
        # can't enumerate a remote store, so it tracks seals/frees itself).
        self.agents: dict[NodeID, AgentHandle] = {}
        self._remote_resident: dict[str, set[ObjectID]] = defaultdict(set)
        # objects an agent spilled to ITS disk: oid -> AgentHandle (their
        # "spilled" entries hold agent-local paths the head cannot open)
        self._agent_spills: dict[ObjectID, AgentHandle] = {}
        # Replica location directory (reference: ownership_object_directory
        # — every node holding a copy can serve it): oid -> {arena_name ->
        # (location, size)} for SECONDARY copies materialized by
        # pull-into-arena; the sealed memory_store entry remains the
        # primary. Invalidated on free / node removal / replica eviction.
        # Guarded by self.lock, with a per-arena reverse index so node
        # removal is O(node's replicas).
        self._object_replicas: dict[ObjectID, dict[str, tuple[str, int]]] = {}
        self._replicas_by_arena: dict[str, set[ObjectID]] = defaultdict(set)
        # per-(arena, oid) single-flight for head-side pull-into-arena:
        # concurrent readers on one node coalesce into a single transfer
        self._arena_pulls: dict[tuple, threading.Event] = {}
        self._arena_pulls_lock = locktrace.register_lock(
            "controller.arena_pulls_lock", threading.Lock()
        )
        # transfer observability: tests assert the zero-re-transfer property
        # through these counters instead of timing
        self.transfer_stats: dict[str, int] = defaultdict(int)
        # serve-ingress observability: proxy_id -> the admission/shed/byte
        # counter snapshot each proxy pushes (report_proxy_stats) — the
        # ``proxy_stats`` op / state API reads the aggregate. Guarded by
        # self.lock; low-rate (one small dict per proxy every ~2 s).
        self._proxy_stats: dict[str, dict] = {}
        # Cluster observability plane (one scrape, one timeline):
        # - metrics_agg merges per-reporter util.metrics snapshots shipped
        #   by workers/agents (report_observability pushes + the
        #   AgentReportBatch piggyback) into a node-labeled cluster view;
        # - _span_store holds shipped lifecycle/app spans (the head's own
        #   spans live in this process's tracing ring) for the merged
        #   timeline, bounded like task_events with a drop counter.
        from ray_tpu.util.metrics import MetricsAggregator

        self.metrics_agg = MetricsAggregator()
        self._span_store: deque = deque(maxlen=config.event_buffer_size)
        self._span_dropped = 0
        # remote rings drop too: reporters ship their CUMULATIVE
        # dropped_spans count with every entry — keep last-per-reporter
        # (bounded LRU, dead reporters evict first and fold into a base
        # so the total stays monotonic; like the MetricsAggregator
        # baselines, the cap must exceed the live reporter count or an
        # evicted live reporter re-adds on its next report) and sum into
        # the cluster dropped_spans figure
        self._span_reporter_dropped: "OrderedDict[str, float]" = (
            OrderedDict()
        )
        self._span_dropped_evicted = 0.0
        # replay guard: a reporter requeues its drained spans on ANY send
        # failure, including a lost reply after we already applied them —
        # dedup on (span_id, start) so the resend folds to zero like the
        # metrics deltas do (a task RETRY reuses the deterministic span id
        # but starts at a different time, so it still lands). Bounded LRU
        # sized to the store.
        self._span_seen: "OrderedDict[tuple, None]" = OrderedDict()
        self._span_lock = threading.Lock()
        # core-stats → util.metrics mirror baselines (the scattered
        # lease/transfer/tenant/proxy counters become real metrics; see
        # _sync_core_metrics)
        self._core_metrics: Optional[dict] = None
        self._core_metric_last: dict[tuple, float] = {}
        # serializes the whole mirror pass: a dashboard /metrics scrape
        # (HTTP thread) racing a cluster_metrics op (dispatch shard) on
        # the read-diff-inc baselines would double-count deltas
        self._core_metric_lock = threading.Lock()
        # actor-creation observability (the agent-owned lease protocol):
        # tests pin "the head never runs a spawn thread for an agent-node
        # actor" through these counters instead of timing/threads
        self.actor_creation_stats: dict[str, int] = defaultdict(int)
        # Batched lease-grant outbox (guarded by self.lock): grants queued
        # during one scheduling round coalesce into ONE LeaseBatch push per
        # agent at round end instead of a wire frame per lease. Flush
        # failure (conn death / injected "lease_batch" chaos) requeues
        # every lease the batch carried — grants are idempotent leases, so
        # re-granting later is safe.
        self._lease_outbox: dict[NodeID, tuple] = {}  # nid -> (agent, [msgs])
        # lease-cache / batching observability: rearm_grants,
        # rearm_refused_{quota,fairness}, lease_batches, leases_batched
        self.lease_stats: dict[str, int] = defaultdict(int)
        # worker ids that died recently: an actor_placed report racing the
        # worker's own death notification must not bind the actor to a
        # corpse (bounded ring; see the actor_placed handler)
        self._recently_dead_workers: "OrderedDict[WorkerID, None]" = (
            OrderedDict()
        )
        # pooled data-plane connections to agents' chunk listeners; the
        # per-peer connection cap matches the transfer window so one
        # windowed pull can saturate a single source
        self._data_pool = P.ChunkConnPool(
            self._authkey,
            max_conns_per_peer=max(1, config.object_transfer_window),
        )
        self._hb_monitor_started = False

        # Internal KV (GCS KV analog).
        self.kv: dict[tuple[str, bytes], bytes] = {}
        # GCS fault-tolerance analog (reference: RedisStoreClient +
        # gcs_init_data reload): KV table persisted to disk when configured
        self._kv_snapshot_path = config.gcs_snapshot_path
        self._kv_dirty = threading.Event()
        self._kv_flusher: Optional[threading.Thread] = None
        # chaos: parse "op=prob,op=prob" once (rpc_chaos analog). Malformed
        # entries AND unknown op names raise: a typo silently disabling
        # fault injection would make chaos tests pass vacuously. The op
        # catalog is P.CONTROLLER_OPS, which tpulint's wire-conformance
        # family keeps in sync with the actual dispatch branches.
        import random

        self._rpc_chaos: dict[str, float] = {}
        self._chaos_rng = random.Random(0)
        for part in (config.testing_rpc_failure or "").split(","):
            if not part.strip():
                continue
            op_name, sep, p = part.partition("=")
            if not sep:
                raise ValueError(
                    f"testing_rpc_failure entry {part!r} is not 'op=prob'"
                )
            self._rpc_chaos[op_name.strip()] = float(p)
        unknown_chaos = (
            set(self._rpc_chaos)
            - P.CONTROLLER_OPS
            - P.AGENT_PUSH_OPS
            - P.INTERNAL_CHAOS_OPS
        )
        if unknown_chaos:
            raise ValueError(
                f"testing_rpc_failure names unknown op(s) "
                f"{sorted(unknown_chaos)}: a typo'd op never injects, so the "
                f"fault-injection tests relying on it pass vacuously "
                f"(known ops: see ray_tpu._private.protocol.CONTROLLER_OPS "
                f"/ AGENT_PUSH_OPS / docs/PROTOCOL.md)"
            )
        # serializes snapshot+rename: without it an in-flight background
        # write (stale snapshot) can land AFTER the shutdown flush
        self._kv_write_lock = locktrace.register_lock(
            "controller.kv_write_lock", threading.Lock()
        )
        # KV subsystem lock: the KV table is self-contained state, so its
        # ops no longer serialize behind the scheduler/object-ref churn on
        # the core lock (sharded dispatch). Subsystem-registered: holding it
        # together with controller.lock raises (see locktrace.subsystem_lock).
        self._kv_lock = locktrace.subsystem_lock(
            "controller.kv", threading.RLock()
        )
        # guards only the lazy flusher-thread start (deliberately NOT a
        # subsystem lock: _persist_kv runs both under the core lock and
        # under the KV lock)
        self._kv_flusher_start_lock = threading.Lock()
        # serializes WHOLE compactions (rotate + snapshot + unlink): the
        # journal-tick trigger and _finish_recovery's compaction can race,
        # and two concurrent rotates would clobber each other's segments
        self._compact_lock = threading.Lock()
        self._boot_snapshot = None
        if self._kv_snapshot_path and os.path.exists(self._kv_snapshot_path):
            try:
                import pickle as _pickle

                with open(self._kv_snapshot_path, "rb") as f:
                    snap = _pickle.load(f)
                if isinstance(snap, dict) and snap.get("version", 0) >= 2:
                    self.kv.update(snap.get("kv", {}))
                    # actors/tasks/pgs restore at the end of __init__ once
                    # the scheduler is live
                    self._boot_snapshot = snap
                else:
                    self.kv.update(snap)  # legacy KV-only snapshot
                logger.info(
                    "restored %d KV entries from %s",
                    len(self.kv), self._kv_snapshot_path,
                )
            except Exception:
                logger.warning("state snapshot restore failed", exc_info=True)

        # ---- head fault tolerance: write-ahead journal + recovery plane
        # (reference: the GCS's Redis-backed tables + gcs_init_data reload,
        # and the raylet resubscribe after NotifyGCSRestart). The snapshot
        # is the compacted base; the WAL is the tail of durable-truth
        # mutations since — a SIGKILL'd head replays snapshot + tail and
        # reconciles live state with its re-attaching agents instead of
        # forgetting everything after the last full snapshot write.
        self._wal = None
        self._wal_suppress = False  # True while replaying (records exist)
        self._wal_append_tick = 0
        self._wal_compacting = False
        self._boot_wal_records: list = []
        # RECOVERING phase state: dispatch is gated until every journaled
        # agent node reconciled (or the grace deadline lapsed)
        self.recovering = False
        self._recovery_deadline = 0.0
        # node_hex -> {"status": waiting|asked|done, "asked_t", "asks"}
        self._recovery_nodes: dict[str, dict] = {}
        # journal-granted leases awaiting agent confirmation:
        # task_id binary -> (PendingTask, node_hex, is_actor_lease)
        self._recovery_parked: dict[bytes, tuple] = {}
        # journal-known ALIVE placements awaiting rebind:
        # actor_id binary -> (node_hex, worker_id binary, direct_address)
        self._recovery_placements: dict[bytes, tuple] = {}
        # journal-known sealed plasma locations awaiting inventory
        # confirmation: oid binary -> (location_name, size)
        self._recovery_objects: dict[bytes, tuple] = {}
        # actor creations DEFERRED during recovery (the actor may be alive
        # on a reconciling agent — resubmitting before its report lands
        # would double-create): actor_id binary -> (spec, name)
        self._recovery_unplaced_actors: dict[bytes, tuple] = {}
        # journal-sealed head-arena locations whose payload died with the
        # crash: surfaced as ObjectLostError at recovery close
        self._recovery_dropped_plasma: list = []
        # first post-restore dispatch stamps time_to_first_dispatch
        self._ttfd_pending = False
        # set once boot restore (snapshot + journal replay) has finished:
        # a RESUMING agent can dial in while replay is still parking
        # leases — its registration must wait, or its reconcile report
        # races an empty table and every held lease reaps as an orphan
        self._restore_done = threading.Event()
        # counters surfaced by the recovery_stats op / rtpu_recovery_*
        self.recovery_counters: dict[str, int] = defaultdict(int)
        # last recovery's shape (durations, per-phase counts)
        self.recovery_info: dict[str, Any] = {}
        self._boot_t = time.monotonic()
        if self._kv_snapshot_path and config.wal_enabled:
            from ray_tpu._private.wal import WriteAheadLog

            wal_path = (
                os.path.join(
                    config.wal_dir,
                    os.path.basename(self._kv_snapshot_path) + ".wal",
                )
                if config.wal_dir
                else self._kv_snapshot_path + ".wal"
            )
            try:
                # replay order: the orphaned pre-compaction segment first (a
                # crash between rotate and snapshot write leaves one), then
                # the live tail — replay application is idempotent, so a
                # record landing in both is harmless
                for seg in (wal_path + ".1", wal_path):
                    if os.path.exists(seg):
                        self._boot_wal_records.extend(
                            WriteAheadLog.replay(seg)
                        )
                self._wal = WriteAheadLog(
                    wal_path,
                    flush_interval_ms=config.wal_flush_interval_ms,
                    on_error=self._on_wal_error,
                    inject_failure=lambda: self._maybe_inject_rpc_failure(
                        "wal_write"
                    ),
                )
            except Exception:
                logger.warning(
                    "WAL unavailable; snapshot-only durability", exc_info=True
                )
                self._wal = None
                self.recovery_counters["wal_errors"] += 1

        # Observability: task events ring buffer.
        self.task_events: deque[dict] = deque(maxlen=config.event_buffer_size)
        # Worker log capture (reference: the per-session log dir layout in
        # _private/node.py + log_monitor.py tailing worker files to the
        # driver). Every spawned worker's stdout/stderr is redirected to
        # per-worker files here; a monitor thread tails new lines to the
        # driver console, a ring buffer feeds the state API, and the files
        # outlive their workers (dead-worker log fetch).
        self.session_log_dir = os.path.join(
            os.path.dirname(self._session_file_path()),
            f"session_{os.getpid()}",
            "logs",
        )
        self._log_buffer: deque[dict] = deque(maxlen=20000)
        self._log_offsets: dict[str, int] = {}
        # worker_hex -> {"pid", "ip", "label"} — survives worker death
        self._log_meta: dict[str, dict] = {}
        self._log_waiters: dict[int, tuple] = {}
        self._log_req_counter = itertools.count(1)
        self._log_to_driver = (
            os.environ.get("RAY_TPU_LOG_TO_DRIVER", "1") != "0"
        )
        if mode == "process":
            try:
                os.makedirs(self.session_log_dir, exist_ok=True)
            except OSError:
                self.session_log_dir = None
            t = threading.Thread(
                target=self._log_monitor_loop, daemon=True, name="ctrl-logmon"
            )
            t.start()
        # messages received from worker/driver/agent connections — the
        # direct actor transport's "head sees nothing" property is asserted
        # against this in tests
        self.worker_msg_count = 0
        # spilling: plasma-resident objects in seal order (LRU-ish) + the
        # on-disk spill directory (reference: external_storage.py
        # FileSystemStorage at :271)
        from collections import OrderedDict as _OD

        self.plasma_resident: "_OD[ObjectID, tuple[str, int]]" = _OD()
        self._spill_lock = locktrace.register_lock(
            "controller.spill_lock", threading.Lock()
        )
        # spilled objects' plasma blocks are reclaimed after a grace period
        # (in-flight readers may hold the already-sent shm location);
        # entries: (spill_time, object_id, size, location_name)
        self._spill_trash: deque[tuple[float, ObjectID, int, str]] = deque()
        self._spill_grace_s = 1.0
        self.spill_dir = os.path.join(
            config.spill_directory or "/tmp",
            f"ray_tpu_spill_{os.getpid()}",
        )
        # (tenant, resource-shape) -> last-seen timestamp of unfulfilled
        # demand: the autoscaler sees WHICH tenant drives each scale-up
        # (over-quota parked work never lands here — a tenant at its cap
        # must not grow the cluster)
        self.pending_demand: dict[tuple, float] = {}

        self.serialization = SerializationContext()
        self._reply_pool = ThreadPoolExecutor(max_workers=8, thread_name_prefix="ctrl-reply")

        # Sharded request dispatch: op -> bound subsystem shard (see
        # _dispatch_request). Built once; the init-time assert catches an op
        # added to a shard ladder + CONTROLLER_OPS but forgotten here (the
        # lint gate covers ladder<->CONTROLLER_OPS drift, this covers
        # table<->ladder drift).
        self._dispatch_table: dict[str, Any] = {}
        for shard_ops, shard_fn in (
            (TASK_SHARD_OPS, self._dispatch_task_ops),
            (ACTOR_SHARD_OPS, self._dispatch_actor_ops),
            (OBJECT_SHARD_OPS, self._dispatch_object_ops),
            (NODE_SHARD_OPS, self._dispatch_node_ops),
            (KV_SHARD_OPS, self._dispatch_kv_ops),
            (OBSERVE_SHARD_OPS, self._dispatch_observe_ops),
        ):
            for op_name in shard_ops:
                self._dispatch_table[op_name] = shard_fn
        if set(self._dispatch_table) != set(P.CONTROLLER_OPS):
            raise AssertionError(
                "dispatch shard tables drifted from protocol.CONTROLLER_OPS: "
                f"missing={sorted(set(P.CONTROLLER_OPS) - set(self._dispatch_table))} "
                f"extra={sorted(set(self._dispatch_table) - set(P.CONTROLLER_OPS))}"
            )

        # OOM protection (reference: memory_monitor.h + worker_killing_policy)
        self.memory_monitor = None
        if config.memory_monitor_enabled and mode == "process":
            from ray_tpu._private.memory_monitor import MemoryMonitor

            self.memory_monitor = MemoryMonitor(
                self,
                threshold=config.memory_usage_threshold,
                poll_interval_s=config.memory_monitor_interval_s,
            )
            self.memory_monitor.start()

        # Control-plane listener for worker processes.
        self.address = None
        self.listener = None
        self._threads: list[threading.Thread] = []
        self.tcp_address = None
        self._tcp_listener = None
        if mode == "process":
            addr_dir = os.environ.get("TMPDIR", "/tmp")
            self.address = os.path.join(addr_dir, f"ray_tpu_{os.getpid()}_{id(self):x}.sock")
            self.listener = Listener(self.address, family="AF_UNIX", authkey=self._authkey)
            t = threading.Thread(
                target=self._accept_loop, args=(self.listener,),
                daemon=True, name="ctrl-accept",
            )
            t.start()
            self._threads.append(t)
            if config.tcp_port is not None:
                # DCN control plane: same wire protocol + authkey over TCP so
                # drivers/workers on other hosts can attach (reference: the
                # gRPC server every GCS/raylet/worker runs, grpc_server.h)
                self._tcp_listener = Listener(
                    ("0.0.0.0", config.tcp_port),
                    family="AF_INET",
                    authkey=self._authkey,
                )
                host = P.routable_host()
                port = self._tcp_listener.address[1]
                self.tcp_address = f"{host}:{port}"
                t2 = threading.Thread(
                    target=self._accept_loop, args=(self._tcp_listener,),
                    daemon=True, name="ctrl-accept-tcp",
                )
                t2.start()
                self._threads.append(t2)
            # session file: lets other processes on this host attach as
            # client drivers with init(address="auto") (reference: the
            # /tmp/ray session dir + ray:// connection info)
            self._write_session_file()

        t = threading.Thread(target=self._schedule_loop, daemon=True, name="ctrl-sched")
        t.start()
        self._threads.append(t)

        if self._boot_snapshot is not None or self._boot_wal_records:
            try:
                self._restore_state(
                    self._boot_snapshot or {}, self._boot_wal_records
                )
            except Exception:
                logger.warning("snapshot state restore failed", exc_info=True)
            self._boot_snapshot = None
            self._boot_wal_records = []
        self._restore_done.set()

    @staticmethod
    def _session_file_path() -> str:
        # per-uid dir: the file holds the cluster authkey, which grants the
        # full remote-code API — must not be readable by other users
        return os.path.join(
            os.environ.get("TMPDIR", "/tmp"),
            f"ray_tpu-{os.getuid()}",
            "session_latest.json",
        )

    def _write_session_file(self):
        import json

        path = self._session_file_path()
        session_dir = os.path.dirname(path)
        try:
            os.makedirs(session_dir, mode=0o700, exist_ok=True)
            os.chmod(session_dir, 0o700)
            info = {
                "address": self.address,
                "tcp_address": self.tcp_address,
                "authkey_hex": self._authkey.hex(),
                "pid": os.getpid(),
            }
            tmp = os.path.join(session_dir, f".session.tmp{os.getpid()}")
            fd = os.open(tmp, os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o600)
            with os.fdopen(fd, "w") as f:
                json.dump(info, f)
            os.replace(tmp, path)
        except OSError:
            logger.warning("could not write session file", exc_info=True)

    def _remove_session_file(self):
        import json

        path = self._session_file_path()
        try:
            with open(path) as f:
                if json.load(f).get("pid") == os.getpid():
                    os.unlink(path)
        except (OSError, ValueError):
            pass

    # ------------------------------------------------------ worker log plane

    def _log_monitor_loop(self):
        """Tail every per-worker log file in the session dir; stream new
        lines to the driver console + the state-API ring buffer (reference:
        ``python/ray/_private/log_monitor.py``)."""
        while not self.shutting_down:
            try:
                self._log_monitor_scan()
            except Exception:  # noqa: BLE001 — the monitor must never die
                pass
            time.sleep(0.2)

    def _log_monitor_scan(self):
        if not self.session_log_dir:
            return
        from ray_tpu._private.log_tail import scan_log_dir

        scan_log_dir(self.session_log_dir, self._log_offsets, self._emit_worker_lines)

    def _emit_worker_lines(self, wid_hex: str, source: str, lines: list):
        """One captured batch: ring-buffer it, prefix-print it to the driver
        (reference: the ``(pid=..., ip=...)`` line prefixes the driver sees)."""
        meta = self._log_meta.get(wid_hex, {})
        label = meta.get("label") or f"worker={wid_hex[:8]}"
        pid = meta.get("pid", "?")
        ip = meta.get("ip", "local")
        now = time.time()
        for line in lines:
            self._log_buffer.append(
                {
                    "worker_id": wid_hex,
                    "source": source,
                    "line": line,
                    "t": now,
                }
            )
        if self._log_to_driver:
            stream = sys.stderr if source == "err" else sys.stdout
            prefix = f"({label} pid={pid}, ip={ip})"
            try:
                for line in lines:
                    stream.write(f"{prefix} {line}\n")
                stream.flush()
            except (OSError, ValueError):
                pass
        # client drivers attached over ray:// see the same stream by
        # subscribing to this channel (reference: the GCS log pubsub the
        # client's log streamer rides)
        try:
            self.publish(
                "worker_logs",
                {"worker_id": wid_hex, "source": source, "lines": list(lines),
                 "pid": pid, "ip": ip, "label": label},
            )
        except Exception:  # noqa: BLE001
            pass

    def _worker_log_paths(self, worker_id: WorkerID):
        """(out, err) file paths for a worker spawned on the head node, or
        None when capture is disabled."""
        if not self.session_log_dir:
            return None
        hexid = worker_id.hex()
        return (
            os.path.join(self.session_log_dir, f"worker-{hexid}.out"),
            os.path.join(self.session_log_dir, f"worker-{hexid}.err"),
        )

    def _register_log_meta(
        self, worker_id: WorkerID, pid=None, ip="local", label=None, agent_node=None
    ):
        entry = self._log_meta.setdefault(worker_id.hex(), {})
        if pid is not None:
            entry["pid"] = pid
        entry["ip"] = ip
        if label:
            entry["label"] = label
        if agent_node is not None:
            entry["agent_node"] = agent_node

    def _log_fetch(self, prefix: str, source: str = "out", tail_bytes: int = 65536):
        """Read a worker's captured output by worker-id hex prefix — works
        for DEAD workers too (files outlive processes). Agent-hosted workers
        are fetched over the agent control channel."""
        matches = [h for h in self._log_meta if h.startswith(prefix)]
        if not matches:
            raise ValueError(f"no worker with id prefix {prefix!r}")
        if len(matches) > 1:
            raise ValueError(f"ambiguous worker prefix {prefix!r}: {matches}")
        wid_hex = matches[0]
        meta = self._log_meta[wid_hex]
        agent_node = meta.get("agent_node")
        if agent_node is not None:
            with self.lock:
                agent = self.agents.get(agent_node)
            if agent is None:
                raise ValueError(f"worker {wid_hex[:8]}'s node has left the cluster")
            req_id = next(self._log_req_counter)
            ev = threading.Event()
            out: list = []
            self._log_waiters[req_id] = (ev, out)
            agent.send(P.FetchLogs(req_id, wid_hex, source, tail_bytes))
            try:
                if not ev.wait(timeout=10.0):
                    raise TimeoutError("agent log fetch timed out")
            finally:
                self._log_waiters.pop(req_id, None)
            return out[0]
        if not self.session_log_dir:
            return ""
        from ray_tpu._private.log_tail import tail_file

        return tail_file(
            os.path.join(self.session_log_dir, f"worker-{wid_hex}.{source}"),
            tail_bytes,
        )

    def _log_list(self):
        out = []
        for wid_hex, meta in self._log_meta.items():
            sizes = {}
            if meta.get("agent_node") is None and self.session_log_dir:
                for source in ("out", "err"):
                    p = os.path.join(
                        self.session_log_dir, f"worker-{wid_hex}.{source}"
                    )
                    try:
                        sizes[source] = os.path.getsize(p)
                    except OSError:
                        sizes[source] = 0
            out.append(
                {
                    "worker_id": wid_hex,
                    "pid": meta.get("pid"),
                    "ip": meta.get("ip", "local"),
                    "label": meta.get("label"),
                    **{f"{k}_bytes": v for k, v in sizes.items()},
                }
            )
        return out

    def _persist_kv(self):
        """Mark controller state dirty; a background flusher writes the
        snapshot (inline per-put writes would be O(table) on every
        connection thread and racy on the shared tmp path). The flusher
        start is guarded by its own tiny lock — callers arrive holding the
        core lock OR the KV subsystem lock, and this path must not nest a
        second subsystem lock.

        With a healthy WAL this is a no-op: every durable-truth mutation
        journals an O(1) record at its own site (``_journal``) and the
        snapshot is written only at compaction — the per-mutation full
        snapshot would be pure write amplification on top of the journal.
        A degraded WAL falls back here (coarser, but never silent)."""
        if not self._kv_snapshot_path:
            return
        if self._wal is not None and self._wal.healthy:
            return
        self._kv_dirty.set()
        with self._kv_flusher_start_lock:
            if self._kv_flusher is None:
                self._kv_flusher = threading.Thread(
                    target=self._kv_flush_loop, daemon=True, name="gcs-flusher"
                )
                self._kv_flusher.start()

    # alias: every table mutation funnels through the same dirty flag
    _persist_state = _persist_kv

    def _build_snapshot(self) -> dict:
        """Full control-plane state for fault tolerance (reference: the GCS
        table storage reloaded by gcs_init_data on boot,
        ``redis_store_client.h:111``). Captured under the lock:

        - KV table
        - named actors (creation spec + restart budget) — the restartable
          population; anonymous actors fate-share with their owner
        - placement groups (bundles + strategy; placement is recomputed)
        - pending normal-task specs (queued work drains after a restart)

        The KV table copies under ITS subsystem lock first — the core lock
        and the KV lock must never be held together (locktrace asserts it).
        """
        with self._kv_lock:
            kv_copy = dict(self.kv)
        with self.lock:
            # the restorable actor population: named actors (the v2 rule)
            # PLUS any actor living on an agent node — those survive a head
            # crash physically and reconcile back by identity (v3)
            def _on_agent(a: "ActorState") -> bool:
                w = a.worker
                if w is not None and w.agent is not None:
                    return True
                tidb = TaskID.for_actor_creation(a.actor_id).binary()
                return any(
                    tidb in n.actor_leases for n in self.nodes.values()
                )

            persisted_actors = [
                a for a in self.actors.values()
                if a.state != "DEAD" and (a.name or _on_agent(a))
            ]
            actors = [
                {
                    "spec": a.creation_spec,
                    "name": a.name,
                    "restarts_left": a.restarts_left,
                }
                for a in persisted_actors
            ]
            cap = self.config.gcs_snapshot_max_pending
            pending = []
            for pt in self.pending_by_id.values():
                if (
                    pt.spec.task_type == TaskType.NORMAL_TASK
                    and not pt.cancelled
                ):
                    pending.append(pt.spec)
                    if len(pending) >= cap:
                        logger.warning(
                            "state snapshot truncated at %d pending tasks",
                            cap,
                        )
                        break
            # actor tasks queued on the restorable actors
            for a in persisted_actors:
                pending.extend(pt.spec for pt in a.queue)
            pgs = [
                {
                    "pg_id": pg_id,
                    "bundles": pg.bundles,
                    "strategy": pg.strategy,
                }
                for pg_id, pg in self.placement_groups.items()
                if not pg.removed
            ]
            # tenant arbitration policy: only explicitly-configured tenants
            # persist (auto-created per-driver tenants carry no policy;
            # usage/deficit rebuild as the restored work re-places)
            tenant_rows = [
                {
                    "name": ts.name,
                    "weight": ts.weight,
                    "priority": ts.priority,
                    "quota": dict(ts.quota) if ts.quota else None,
                }
                for ts in self.tenants.values()
                if ts.configured
            ]
            # ---- v3 recovery tables (the compacted form of the journal's
            # lease / placement / membership / seal records) ----
            nodes_alive = [
                nid.hex()
                for nid, n in self.nodes.items()
                if n.alive and n.agent is not None
            ]
            task_leases = {}
            actor_leases = {}
            for nid, n in self.nodes.items():
                if n.agent is None:
                    continue
                for tidb in n.leased:
                    task_leases[tidb] = nid.hex()
                for tidb in n.actor_leases:
                    actor_leases[tidb] = nid.hex()
            placements = {}
            for a in persisted_actors:
                w = a.worker
                if a.state == "ALIVE" and w is not None and w.agent is not None:
                    placements[a.actor_id.binary()] = (
                        w.agent.node_id.hex(),
                        w.worker_id.binary(),
                        w.direct_address,
                    )
            seals = []
            for oid in list(self.ref_counts):
                entry = self.memory_store.peek(oid)
                if entry is None:
                    continue
                kind, payload = entry
                if kind in ("inline", "error"):
                    seals.append((oid.binary(), kind, payload.to_bytes()))
                elif kind == "plasma":
                    seals.append((oid.binary(), "plasma", tuple(payload)))
                if len(seals) >= cap:
                    logger.warning(
                        "state snapshot truncated at %d sealed objects", cap
                    )
                    break
            # lineage producers (the compacted form of journal kind
            # "lineage"): one spec per producer task, FIRST-insert order —
            # boot replays these through _record_lineage, whose FIFO byte
            # cap then evicts exactly what the pre-crash table had evicted
            # (a spec with N returns re-creates all N entries from one
            # record)
            lineage_specs = []
            lineage_seen: set = set()
            for spec, _cost in self.lineage.values():
                tidb = spec.task_id.binary()
                if tidb not in lineage_seen:
                    lineage_seen.add(tidb)
                    lineage_specs.append(spec)
            return {
                "version": 3,
                "kv": kv_copy,
                "actors": actors,
                "placement_groups": pgs,
                "pending_tasks": pending,
                "tenants": tenant_rows,
                "nodes": nodes_alive,
                "task_leases": task_leases,
                "actor_leases": actor_leases,
                "actor_placements": placements,
                "seals": seals,
                "lineage": lineage_specs,
            }

    def _write_snapshot(self, suffix: str):
        import pickle as _pickle

        with self._kv_write_lock:
            snapshot = self._build_snapshot()
            tmp = self._kv_snapshot_path + suffix
            with open(tmp, "wb") as f:
                _pickle.dump(snapshot, f)
            os.replace(tmp, self._kv_snapshot_path)

    def _kv_flush_loop(self):
        while not self.shutting_down:
            self._kv_dirty.wait(timeout=1.0)
            if self.shutting_down:
                return  # shutdown() writes the final snapshot itself
            if not self._kv_dirty.is_set():
                continue
            self._kv_dirty.clear()
            try:
                self._write_snapshot(f".tmp{os.getpid()}-{threading.get_ident()}")
            except Exception:
                logger.warning("state snapshot write failed", exc_info=True)
            time.sleep(0.2)  # batch bursts of mutations

    def flush_kv_now(self):
        """Synchronous flush (used at shutdown so the last writes persist).
        With a WAL this is the final compaction: the snapshot subsumes the
        journal, which closes truncated."""
        if not self._kv_snapshot_path:
            return
        try:
            self._write_snapshot(f".final{os.getpid()}")
            self._kv_dirty.clear()
            if self._wal is not None:
                self._wal.truncate()
                self._wal.close(final_flush=False)
        except Exception:
            logger.warning("final state snapshot failed", exc_info=True)

    # ------------------------------------------- write-ahead journal (WAL)

    def _journal(self, kind: str, payload) -> None:
        """Append one durable-truth mutation record (O(1): deque append —
        the WAL flusher pickles/writes/fsyncs in batches). Suppressed while
        replaying (the records being applied are already on disk); silent
        no-op when the journal is off or degraded (the legacy dirty-flag
        snapshot flusher owns durability then)."""
        w = self._wal
        if w is None or self._wal_suppress or not w.healthy:
            return
        if self.shutting_down:
            # teardown mutations (remove_node on closed agent conns, final
            # frees) are not membership/work truth — the final compaction
            # snapshot in flush_kv_now records the clean-shutdown state
            return
        w.append(kind, payload)
        self._wal_append_tick += 1
        if self._wal_append_tick >= 512:
            # amortized rotation check: replay must stay O(snapshot + tail)
            self._wal_append_tick = 0
            if (
                not self._wal_compacting
                and w.size_bytes() > self.config.wal_rotate_bytes
            ):
                self._wal_compacting = True
                threading.Thread(
                    target=self._compact_bg, daemon=True, name="wal-compact"
                ).start()

    def _compact_bg(self):
        try:
            self.compact_now()
        finally:
            self._wal_compacting = False

    def compact_now(self):
        """Journal compaction: rotate to a fresh segment, write the full
        snapshot, drop the old segment (see ``WriteAheadLog.rotate`` for
        why this ordering is crash-safe). Serialized: a concurrent pair of
        compactions would clobber each other's rotated segments and race
        on the snapshot temp file."""
        if self._wal is None or not self._kv_snapshot_path:
            return
        with self._compact_lock:
            try:
                self._wal.flush()
                old = self._wal.rotate()
                self._write_snapshot(f".compact{os.getpid()}")
                try:
                    os.unlink(old)
                except OSError:
                    pass
                self.recovery_counters["wal_compactions"] += 1
            except Exception:  # noqa: BLE001 — degrade is handled by the WAL
                logger.warning("WAL compaction failed", exc_info=True)

    def _on_wal_error(self, exc: BaseException):
        """The journal degraded (write/rotate failure): durability falls
        back LOUDLY to the per-mutation snapshot flusher — coarser, but
        never a silent hole in the log (``rtpu_wal_errors`` counts it)."""
        self.recovery_counters["wal_errors"] += 1
        logger.error(
            "WAL degraded — falling back to snapshot-only durability: %s",
            exc,
        )
        # reactivate the legacy dirty-flag path (wal.healthy is False now)
        self._persist_kv()

    def _restore_snapshot(self, snap: dict):
        """Rebuild restorable state from a snapshot (run at the END of
        __init__, once the scheduler is live). Named actors are re-created
        (their processes died with the old head/agents — reference restarts
        them through GcsActorManager the same way); pending tasks resubmit;
        placement groups re-place as capacity registers."""
        # tenant policy FIRST: restored work must route into queue groups
        # with the configured weights/quotas/priorities already in force
        for entry in snap.get("tenants", ()):
            try:
                self.set_tenant_quota(
                    entry["name"],
                    quota=entry.get("quota") or {},
                    weight=entry.get("weight"),
                    priority=entry.get("priority"),
                )
            except Exception:
                logger.warning(
                    "could not restore tenant %s", entry.get("name"),
                    exc_info=True,
                )
        for entry in snap.get("placement_groups", ()):
            pg = PlacementGroupState(
                entry["pg_id"], entry["bundles"], entry["strategy"]
            )
            with self.lock:
                self.placement_groups[entry["pg_id"]] = pg
        for entry in snap.get("actors", ()):
            spec = entry["spec"]
            try:
                with self.lock:
                    actor = ActorState(spec.actor_id, spec)
                    actor.name = entry["name"]
                    actor.restarts_left = entry["restarts_left"]
                    self.actors[spec.actor_id] = actor
                    if entry["name"]:
                        self.named_actors[entry["name"]] = spec.actor_id
                self.submit_task(spec)
            except Exception:
                logger.warning(
                    "could not restore actor %s", entry["name"], exc_info=True
                )
        restored = 0
        for spec in snap.get("pending_tasks", ()):
            try:
                self.submit_task(spec)
                restored += 1
            except Exception:
                logger.warning(
                    "could not restore task %s", spec.name, exc_info=True
                )
        # tasks whose ref args died with the old object store and have no
        # producer to rebuild them must fail, not hang
        self._fail_unrecoverable_waiters()
        if snap.get("actors") or restored:
            logger.info(
                "restored %d named actor(s), %d pending task(s), %d pg(s) "
                "from snapshot",
                len(snap.get("actors", ())), restored,
                len(snap.get("placement_groups", ())),
            )

    # -------------------------------------- crash recovery (snapshot + WAL)

    def _restore_state(self, snap: dict, wal_records: list):
        """Rebuild from the compacted snapshot plus the journal tail. With
        no journal (WAL disabled, legacy v2 snapshot) this is the old
        restore-and-resubmit path; otherwise the merged model drives a
        reconciling recovery: journaled agent nodes get a bounded
        RECOVERING window to confirm what they still hold before anything
        is re-placed."""
        if self._wal is None and not wal_records and snap.get("version", 0) < 3:
            return self._restore_snapshot(snap)
        model = self._build_recovery_model(snap, wal_records)
        self._wal_suppress = True  # records being applied are already on disk
        try:
            self._restore_recovery(model)
        finally:
            self._wal_suppress = False

    def _build_recovery_model(self, snap: dict, records: list) -> dict:
        """Fold the journal tail onto the snapshot base. Application is
        idempotent — a record that also made the snapshot (compaction race,
        orphaned pre-compaction segment) folds to the same state."""
        model: dict = {
            "tenants": {t["name"]: t for t in snap.get("tenants", ())},
            "pgs": {
                e["pg_id"]: e for e in snap.get("placement_groups", ())
            },
            # aid binary -> {"spec","name","restarts_left","placed","dead"}
            "actors": {},
            # tid binary -> spec (submitted, not yet completed)
            "pending": OrderedDict(),
            "task_leases": dict(snap.get("task_leases", ())),
            "actor_leases": dict(snap.get("actor_leases", ())),
            # oid binary -> (kind, payload)
            "seals": OrderedDict(
                (oid, (kind, payload))
                for oid, kind, payload in snap.get("seals", ())
            ),
            "nodes": set(snap.get("nodes", ())),
            # producer specs in append order (snapshot base + journal
            # tail); replay feeds them to _record_lineage SEQUENTIALLY so
            # byte-cap eviction reproduces the pre-crash table exactly —
            # dedup would break that (an evicted-then-resubmitted producer
            # legitimately appears twice, and only the replayed SECOND
            # record survives the cap)
            "lineage": list(snap.get("lineage", ())),
        }
        for entry in snap.get("actors", ()):
            spec = entry["spec"]
            model["actors"][spec.actor_id.binary()] = {
                "spec": spec,
                "name": entry.get("name"),
                "restarts_left": entry.get("restarts_left", 0),
                "placed": None,
                "dead": False,
            }
        for aid, placed in (snap.get("actor_placements") or {}).items():
            rec = model["actors"].get(aid)
            if rec is not None:
                rec["placed"] = tuple(placed)
        for spec in snap.get("pending_tasks", ()):
            model["pending"][spec.task_id.binary()] = spec
        replayed = 0
        for kind, payload in records:
            replayed += 1
            try:
                self._apply_journal_record(model, kind, payload)
            except Exception:  # noqa: BLE001 — one bad record, not the boot
                logger.warning(
                    "WAL record %r failed to apply", kind, exc_info=True
                )
        self.recovery_counters["wal_records_replayed"] += replayed
        return model

    def _apply_journal_record(self, model: dict, kind: str, payload):
        actors = model["actors"]
        if kind == "submit":
            spec, name = payload
            if spec.task_type == TaskType.ACTOR_CREATION_TASK:
                rec = actors.setdefault(
                    spec.actor_id.binary(),
                    {"spec": spec, "name": name,
                     "restarts_left": spec.max_restarts,
                     "placed": None, "dead": False},
                )
                rec["spec"], rec["name"] = spec, name
            else:
                model["pending"][spec.task_id.binary()] = spec
        elif kind == "done":
            model["pending"].pop(payload, None)
            model["task_leases"].pop(payload, None)
            model["actor_leases"].pop(payload, None)
        elif kind == "lease":
            tid, node_hex = payload
            model["task_leases"][tid] = node_hex
        elif kind == "alease":
            tid, node_hex = payload
            model["actor_leases"][tid] = node_hex
        elif kind == "unlease":
            model["task_leases"].pop(payload, None)
            model["actor_leases"].pop(payload, None)
        elif kind == "seal":
            oid, k, p = payload
            model["seals"][oid] = (k, p)
        elif kind == "free":
            model["seals"].pop(payload, None)
        elif kind == "placed":
            aid, node_hex, wid, addr = payload
            rec = actors.get(aid)
            if rec is not None:
                rec["placed"] = (node_hex, wid, addr)
        elif kind == "unplaced":
            rec = actors.get(payload)
            if rec is not None:
                rec["placed"] = None
        elif kind == "actor_dead":
            rec = actors.get(payload)
            if rec is not None:
                rec["dead"] = True
        elif kind == "restarts":
            aid, n = payload
            rec = actors.get(aid)
            if rec is not None:
                rec["restarts_left"] = n
        elif kind == "node_up":
            model["nodes"].add(payload)
        elif kind == "node_down":
            model["nodes"].discard(payload)
        elif kind == "tenant":
            model["tenants"][payload["name"]] = payload
        elif kind == "pg":
            pg_id, bundles, strategy = payload
            model["pgs"][pg_id] = {
                "pg_id": pg_id, "bundles": bundles, "strategy": strategy,
            }
        elif kind == "pg_remove":
            model["pgs"].pop(payload, None)
        elif kind == "kv_put":
            ns, key, value = payload
            with self._kv_lock:
                self.kv[(ns, key)] = value
        elif kind == "kv_del":
            ns, key = payload
            with self._kv_lock:
                self.kv.pop((ns, key), None)
        elif kind == "lineage":
            model["lineage"].append(payload)
        else:
            logger.warning("unknown WAL record kind %r (skipped)", kind)

    def _restore_recovery(self, model: dict):
        """Apply the merged model. When journaled agent nodes exist, enter
        the bounded RECOVERING phase: leases and placements park awaiting
        each agent's reconcile report; the dispatch loop stays gated so
        nothing re-places (and re-EXECUTES) work an agent still holds."""
        expected = {
            h for h in model["nodes"]
            if h != self.head_node_id.hex()
        }
        recovering = bool(expected) and self.mode == "process"
        if recovering:
            with self.lock:
                self.recovering = True
                self._recovery_deadline = (
                    time.monotonic() + self.config.recovery_grace_s
                )
                for h in expected:
                    self._recovery_nodes[h] = {
                        "status": "waiting", "asked_t": 0.0, "asks": 0,
                    }
            self.recovery_info["started_t"] = time.time()
            self.recovery_info["expected_nodes"] = len(expected)
        # tenant policy FIRST: restored work must route into queue groups
        # with the configured weights/quotas/priorities already in force
        for entry in model["tenants"].values():
            try:
                self.set_tenant_quota(
                    entry["name"],
                    quota=entry.get("quota") or {},
                    weight=entry.get("weight"),
                    priority=entry.get("priority"),
                )
            except Exception:
                logger.warning(
                    "could not restore tenant %s", entry.get("name"),
                    exc_info=True,
                )
        for entry in model["pgs"].values():
            pg = PlacementGroupState(
                entry["pg_id"], entry["bundles"], entry["strategy"]
            )
            with self.lock:
                self.placement_groups[entry["pg_id"]] = pg
        # lineage table BEFORE any seal/pending processing: replaying the
        # journaled producer specs in append order through _record_lineage
        # reproduces the pre-crash table (entries AND eviction state — the
        # same FIFO byte cap applies), so _seal_lost_objects below and any
        # post-recovery loss can reconstruct instead of failing getters
        for spec in model.get("lineage", ()):
            try:
                self._record_lineage(spec)
            except Exception:  # noqa: BLE001 — one bad spec, not the boot
                logger.warning(
                    "could not restore lineage record", exc_info=True
                )
        self.recovery_counters["lineage_restored"] += len(self.lineage)
        # sealed objects: inline/error payloads re-seal from the journal;
        # plasma locations lived in arenas — agent-arena copies park until
        # the owning agent's inventory confirms them, head-arena copies
        # died with the crashed process (lineage may rebuild on demand)
        sealed = parked_obj = 0
        dropped_plasma: list[bytes] = []
        for oid_bin, (kind, payload) in model["seals"].items():
            oid = ObjectID(oid_bin)
            if kind in ("inline", "error"):
                self.memory_store.put(
                    oid, (kind, SerializedObject.from_buffer(payload))
                )
                with self.lock:
                    self.ref_counts[oid] += 1  # recovery pin
                sealed += 1
            elif kind == "plasma" and recovering:
                name, size = payload
                self._recovery_objects[oid_bin] = (name, int(size))
                parked_obj += 1
            elif kind == "plasma":
                # head-arena payload: its shared memory died with the
                # crashed process — surfaced as lost after pending restore
                # (a replayed producer may still re-run it)
                dropped_plasma.append(oid_bin)
        self.recovery_counters["seals_restored"] += sealed
        # The submitting clients' return-id refs died with the crashed
        # head (add_ref traffic is not journaled): pin every restored
        # spec's returns with a recovery ref, or the eager refcount-0 free
        # in _on_object_sealed reclaims results the reconnecting driver is
        # blocked on. The driver's re-sent FreeObjects releases the pin.
        def _pin_returns(spec):
            with self.lock:
                for oid in spec.return_ids():
                    self.ref_counts[oid] += 1

        # actors: rebuild identity; placements/creation-leases on expected
        # nodes park for reconcile, everything else re-creates
        resubmit = []
        for aid_bin, rec in model["actors"].items():
            if rec["dead"]:
                continue
            spec, name = rec["spec"], rec.get("name")
            tid_bin = TaskID.for_actor_creation(ActorID(aid_bin)).binary()
            try:
                with self.lock:
                    actor = ActorState(spec.actor_id, spec)
                    actor.name = name
                    actor.restarts_left = rec.get("restarts_left", 0)
                    self.actors[spec.actor_id] = actor
                    if name:
                        self.named_actors[name] = spec.actor_id
                placed = rec.get("placed")
                lease_node = model["actor_leases"].get(tid_bin)
                if recovering and placed and placed[0] in expected:
                    with self.lock:
                        actor.state = "RESTARTING"
                        self._recovery_placements[aid_bin] = tuple(placed)
                        self._recovery_unplaced_actors[aid_bin] = (spec, name)
                elif recovering and lease_node in expected:
                    # creation lease in flight at crash: the agent's spawner
                    # still owns it and will (re)report actor_placed — park
                    # the pending creation under its journaled node
                    with self.lock:
                        deps = {a[1] for a in spec.args if a[0] == "ref"}
                        pt = PendingTask(spec, deps)
                        for d in pt.all_deps:
                            self.ref_counts[d] += 1
                        for oid in spec.return_ids():
                            self.ref_counts[oid] += 1  # recovery pin
                        self.pending_by_id[spec.task_id] = pt
                        self._recovery_parked[tid_bin] = (
                            pt, lease_node, True,
                        )
                        self._recovery_unplaced_actors[aid_bin] = (spec, name)
                elif recovering:
                    # unknown placement: the actor MAY be alive on a
                    # reconciling agent (a lost 'placed' record) — defer
                    # the re-create decision to the end of recovery
                    with self.lock:
                        actor.state = "RESTARTING"
                        self._recovery_unplaced_actors[aid_bin] = (spec, name)
                else:
                    resubmit.append(spec)
            except Exception:
                logger.warning(
                    "could not restore actor %s", name or spec.actor_id.hex(),
                    exc_info=True,
                )
        for spec in resubmit:
            try:
                _pin_returns(spec)
                self._submit_replayed(spec)
            except Exception:
                logger.warning(
                    "could not resubmit actor creation %s", spec.name,
                    exc_info=True,
                )
        # pending tasks: journal-leased ones park under their node;
        # completed-with-lost-'done' ones dedup against their sealed
        # returns; the rest resubmit (dispatch is gated while recovering)
        restored = parked = 0
        for tid_bin, spec in model["pending"].items():
            rets = spec.return_ids()
            if rets and self.memory_store.contains(rets[0]):
                continue  # completed pre-crash; 'done' record lost
            lease_node = model["task_leases"].get(tid_bin)
            try:
                _pin_returns(spec)
                if (
                    recovering
                    and spec.task_type == TaskType.NORMAL_TASK
                    and lease_node in expected
                ):
                    with self.lock:
                        deps = {a[1] for a in spec.args if a[0] == "ref"}
                        pt = PendingTask(spec, deps)
                        for d in pt.all_deps:
                            self.ref_counts[d] += 1
                        self.pending_by_id[spec.task_id] = pt
                        self._recovery_parked[tid_bin] = (
                            pt, lease_node, False,
                        )
                    parked += 1
                else:
                    self.submit_task(spec)
                    restored += 1
            except Exception:
                logger.warning(
                    "could not restore task %s", spec.name, exc_info=True
                )
        self.recovery_counters["tasks_restored"] += restored
        self.recovery_counters["leases_parked"] += parked
        self._ttfd_pending = bool(
            restored or parked or model["actors"] or self._recovery_objects
        )
        self._recovery_dropped_plasma = dropped_plasma if recovering else []
        if recovering:
            logger.warning(
                "head RECOVERING: %d journaled agent node(s), %d parked "
                "lease(s), %d parked placement(s), %d parked object(s) — "
                "dispatch gated for up to %.1fs while agents reconcile",
                len(expected), len(self._recovery_parked),
                len(self._recovery_placements), parked_obj,
                self.config.recovery_grace_s,
            )
            t = threading.Thread(
                target=self._recovery_monitor, daemon=True,
                name="ctrl-recovery",
            )
            t.start()
            self._threads.append(t)
        else:
            self._seal_lost_objects(dropped_plasma)
            self._fail_unrecoverable_waiters()
            if model["actors"] or restored:
                logger.info(
                    "restored %d actor(s), %d pending task(s), %d pg(s) "
                    "from snapshot+journal",
                    len(model["actors"]), restored, len(model["pgs"]),
                )

    def _seal_lost_objects(self, oid_bins) -> None:
        """Journal-sealed plasma objects whose payload did not survive the
        crash (head arena, or an agent that never reconciled) and whose
        producer is not pending: seal ObjectLostError so a reconnecting
        driver's get() FAILS instead of hanging forever on an entry that
        can never re-seal. The journaled lineage table gets the FIRST say:
        reconstruction is attempted for every candidate, and only objects
        whose producer is neither pending nor recovering after that seal
        the loss — a restarted head re-executes instead of failing."""
        if not oid_bins:
            return
        with self.lock:
            for oid_bin in oid_bins:
                # recovery pin (same contract as restored inline/error
                # seals): the clients' add_ref traffic died with the
                # crashed head, so without a pin the reconstructed result
                # — or the ObjectLostError below — frees eagerly at seal
                # and a reconnecting getter hangs forever. The driver's
                # re-sent FreeObjects releases the pin.
                self.ref_counts[ObjectID(oid_bin)] += 1
        self._maybe_recover([ObjectID(b) for b in oid_bins])
        for oid_bin in oid_bins:
            oid = ObjectID(oid_bin)
            if self.memory_store.contains(oid):
                continue
            producer = TaskID(oid_bin[: TaskID.SIZE])
            with self.lock:
                if producer in self.pending_by_id or producer in self._recovering:
                    continue  # a replayed producer will re-seal it
            err = self.serialization.serialize(
                ObjectLostError(
                    f"object {oid.hex()} was sealed before the head crash "
                    f"but its payload did not survive recovery"
                )
            )
            self.memory_store.put(oid, ("error", err))
            self._on_object_sealed(oid)
            self.recovery_counters["objects_lost"] += 1

    # ---------------------------------------- agent-driven reconciliation

    def _ask_reconcile(self, agent: AgentHandle, seq: int = 1):
        """Push the reconcile ask to a re-attached agent. An injected
        'agent_reconcile' chaos failure drops the push before the wire —
        the recovery monitor's single bounded re-ask covers it."""
        h = agent.node_id.hex()
        with self.lock:
            rec = self._recovery_nodes.setdefault(
                h, {"status": "waiting", "asked_t": 0.0, "asks": 0}
            )
            if rec["status"] == "done":
                return
            rec["status"] = "asked"
            rec["asked_t"] = time.monotonic()
            rec["asks"] += 1
            deadline_s = max(0.5, self._recovery_deadline - time.monotonic())
        try:
            self._maybe_inject_rpc_failure("agent_reconcile")
            agent.send(P.AgentReconcile(deadline_s, ask_seq=seq))
            self.recovery_counters["reconcile_asks"] += 1
        except (OSError, EOFError, WorkerCrashedError) as e:
            # lost push: the monitor re-asks once after the resend window
            if isinstance(e, WorkerCrashedError):
                self.recovery_counters["reconcile_ask_injected_failures"] += 1
            else:
                self.recovery_counters["reconcile_ask_failures"] += 1

    def _recovery_monitor(self):
        """Bounded RECOVERING supervisor: re-asks silent agents ONCE after
        the resend window, then closes recovery at the earlier of every
        expected node reconciling or the grace deadline."""
        resend_s = self.config.recovery_reconcile_resend_s
        while not self.shutting_down:
            with self.lock:
                if not self.recovering:
                    return
                deadline = self._recovery_deadline
                recs = {
                    h: dict(r) for h, r in self._recovery_nodes.items()
                }
                agents = dict(self.agents)
            now = time.monotonic()
            if recs and all(r["status"] == "done" for r in recs.values()):
                self._finish_recovery("all agents reconciled")
                return
            if now >= deadline:
                self._finish_recovery("grace deadline lapsed")
                return
            for h, r in recs.items():
                if (
                    r["status"] == "asked"
                    and r["asks"] < 2
                    and now - r["asked_t"] > resend_s
                ):
                    agent = next(
                        (a for nid, a in agents.items() if nid.hex() == h),
                        None,
                    )
                    if agent is not None:
                        self._ask_reconcile(agent, seq=2)
            time.sleep(0.05)

    def _unqueue_pending_locked(self, pt: PendingTask) -> bool:
        """Remove a restored-but-queued task from its tenant ready queue
        (call under self.lock). Covers the fsync window where a lease
        record was lost: the agent's reconcile report proves it holds the
        task, so the queued copy must not dispatch a second execution."""
        shape = self._shape_key(pt.spec)
        ts = self.tenants.get(shape[0])
        if ts is None:
            return False
        q = ts.queues.get(shape)
        if not q:
            return False
        try:
            q.remove(pt)
        except ValueError:
            return False
        ts.reap_queue(shape)
        return True

    def _apply_reconcile_report(self, node_hex: str, report: dict) -> dict:
        """Fold one agent's truth into the recovering head: resume held
        leases, apply completion reports the crashed head never journaled,
        rebind alive actors by identity, confirm arena inventory. Returns
        the orphan verdicts the agent must reap. Idempotent: the node's
        'done' flag makes a duplicate report (head re-ask crossing the
        original reply on the wire) a no-op — no double re-place."""
        drop_tasks: list = []
        drop_actors: list = []
        drop_objects: list = []
        completed_entries = list(report.get("completed") or ())
        with self.lock:
            if not self.recovering:
                # the grace deadline already closed recovery: its journaled
                # work was re-placed/re-created — applying this late report
                # would bind a SECOND live copy of every lease and actor it
                # names. The agent resets on this verdict (exactly-once
                # depends on it).
                self.recovery_counters["reconcile_late_rejected"] += 1
                return {"status": "closed", "drop_tasks": [],
                        "drop_actors": [], "drop_objects": []}
            nid = next(
                (n for n in self.agents if n.hex() == node_hex), None
            )
            node = self.nodes.get(nid) if nid is not None else None
            agent = self.agents.get(nid) if nid is not None else None
            if node is None or agent is None:
                raise ValueError(
                    f"reconcile_report from unregistered node {node_hex}"
                )
            rec = self._recovery_nodes.setdefault(
                node_hex,
                {"status": "waiting", "asked_t": 0.0, "asks": 0},
            )
            if rec["status"] == "done":
                self.recovery_counters["reconcile_duplicates"] += 1
                return {"status": "duplicate", "drop_tasks": [],
                        "drop_actors": [], "drop_objects": []}
            rec["status"] = "done"
            # --- held normal-task leases: resume under this node ---
            for tid_bin in report.get("task_leases") or ():
                entry = self._recovery_parked.pop(tid_bin, None)
                if entry is not None:
                    pt = entry[0]
                elif (pt_q := self.pending_by_id.get(
                        TaskID(tid_bin))) is not None and \
                        self._unqueue_pending_locked(pt_q):
                    # lease record lost in the fsync window: the agent's
                    # possession is the truth — adopt the queued copy
                    pt = pt_q
                else:
                    drop_tasks.append(tid_bin)
                    self.recovery_counters["orphan_tasks_reaped"] += 1
                    continue
                node.leased[tid_bin] = pt
                node.allocate(pt.spec.resources)
                pt._node = node  # type: ignore[attr-defined]
                self._tenant_charge(
                    self._tenant_for(pt.spec), pt.spec.resources
                )
                self.recovery_counters["leases_resumed"] += 1
            # --- creation leases still owned by the agent's spawner ---
            for tid_bin in report.get("actor_leases") or ():
                entry = self._recovery_parked.pop(tid_bin, None)
                if entry is None:
                    drop_tasks.append(tid_bin)
                    self.recovery_counters["orphan_tasks_reaped"] += 1
                    continue
                pt = entry[0]
                node.actor_leases[tid_bin] = pt
                node.allocate(pt.spec.resources)
                pt._node = node  # type: ignore[attr-defined]
                self._tenant_charge(
                    self._tenant_for(pt.spec), pt.spec.resources
                )
                self.recovery_counters["creation_leases_resumed"] += 1
            # --- alive actors: rebind by identity ---
            for aid_bin, wid_bin, direct_address, pid in (
                report.get("actors") or ()
            ):
                actor = self.actors.get(ActorID(aid_bin))
                tid_bin = TaskID.for_actor_creation(ActorID(aid_bin)).binary()
                if tid_bin in node.actor_leases:
                    continue  # creation resumed above; actor_placed will bind
                if actor is None or actor.state == "DEAD":
                    drop_actors.append(aid_bin)
                    self.recovery_counters["orphan_actors_reaped"] += 1
                    continue
                wid = WorkerID(wid_bin)
                handle = self.workers.get(wid)
                if handle is None:
                    handle = WorkerHandle(
                        wid, node.node_id, conn=_RelayConn(agent, wid),
                    )
                    handle.agent = agent
                    handle.agent_owned = True
                    handle.registered.set()
                    self.workers[wid] = handle
                handle.actor_id = actor.actor_id
                if direct_address and not handle.direct_address:
                    handle.direct_address = direct_address
                self._recovery_placements.pop(aid_bin, None)
                self._recovery_unplaced_actors.pop(aid_bin, None)
                actor.state = "ALIVE"
                actor.worker = handle
                node.allocate(actor.creation_spec.resources)
                actor.held = (
                    node, None, dict(actor.creation_spec.resources)
                )
                self._tenant_charge(
                    self._tenant_for(actor.creation_spec),
                    actor.creation_spec.resources,
                )
                self.pending_by_id.pop(
                    TaskID.for_actor_creation(actor.actor_id), None
                )
                self.recovery_counters["actors_rebound"] += 1
                self._journal(
                    "placed",
                    (aid_bin, node_hex, wid_bin, direct_address),
                )
                self.publish(
                    "actors",
                    {"actor_id": actor.actor_id.hex(), "state": "ALIVE"},
                )
                self._pump_actor(actor)
            # --- surviving pool workers: rebuild identity tracking (their
            # own control-plane ops — stacks, log fetch — need handles;
            # the lazy FromWorker path would rebuild them too, but only on
            # the worker's NEXT message) ---
            for wid_bin, _pid in report.get("workers") or ():
                wid = WorkerID(wid_bin)
                if wid not in self.workers:
                    handle = WorkerHandle(
                        wid, node.node_id, conn=_RelayConn(agent, wid),
                    )
                    handle.agent = agent
                    handle.agent_owned = True
                    handle.registered.set()
                    self.workers[wid] = handle
            # --- arena inventory: confirm journaled seal locations ---
            for oid_bin, name, size, is_replica in (
                report.get("objects") or ()
            ):
                oid = ObjectID(oid_bin)
                if is_replica:
                    # secondary copies re-enter the replica directory (the
                    # location string carries the arena)
                    self._register_replica_entry(oid, name, int(size))
                    continue
                if self._recovery_objects.pop(oid_bin, None) is None:
                    if not self.memory_store.contains(oid):
                        drop_objects.append(oid_bin)
                        self.recovery_counters["orphan_objects_reaped"] += 1
                    continue
                self.ref_counts[oid] += 1  # recovery pin
                self.recovery_counters["objects_restored"] += 1
            self.sched_cv.notify_all()
        # re-seal confirmed primaries OUTSIDE the lock (store ops lock
        # themselves); membership tracking rides _seal_plasma
        dropped = set(drop_objects)
        for oid_bin, name, size, is_replica in report.get("objects") or ():
            if is_replica or oid_bin in dropped:
                continue
            oid = ObjectID(oid_bin)
            if not self.memory_store.contains(oid):
                try:
                    self._seal_plasma(oid, name, int(size))
                    self._on_object_sealed(oid)
                except Exception:  # noqa: BLE001 — one object, not the node
                    logger.warning(
                        "could not restore object %s", oid.hex(),
                        exc_info=True,
                    )
        # completion reports the crashed head never journaled: resume the
        # lease, then run the normal done path (seal + release + unpin)
        for tid_bin, results, exec_ms in completed_entries:
            with self.lock:
                entry = self._recovery_parked.pop(tid_bin, None)
                pt = entry[0] if entry else None
                if pt is None:
                    pt_q = self.pending_by_id.get(TaskID(tid_bin))
                    if pt_q is not None and self._unqueue_pending_locked(pt_q):
                        pt = pt_q
                if pt is not None:
                    node.leased[tid_bin] = pt
            if pt is None:
                continue  # already journaled done pre-crash
            self._on_agent_task_done(
                agent,
                P.AgentTaskDone(TaskID(tid_bin), results, exec_ms=exec_ms),
            )
            self.recovery_counters["completions_recovered"] += 1
        logger.info(
            "node %s reconciled: +%d task lease(s), +%d creation lease(s), "
            "%d actor(s) rebound, %d completion(s) recovered; reaping "
            "%d/%d/%d orphan task/actor/object(s)",
            node_hex[:8],
            len(report.get("task_leases") or ()) - len(drop_tasks),
            len(report.get("actor_leases") or ()),
            self.recovery_counters.get("actors_rebound", 0),
            len(completed_entries),
            len(drop_tasks), len(drop_actors), len(drop_objects),
        )
        return {
            "status": "ok",
            "drop_tasks": drop_tasks,
            "drop_actors": drop_actors,
            "drop_objects": drop_objects,
        }

    def _finish_recovery(self, reason: str):
        """Close the RECOVERING phase: re-place journal-granted work no
        agent confirmed, re-create unconfirmed actors, drop unconfirmed
        object locations, open the dispatch loop."""
        with self.lock:
            if not self.recovering:
                return
            self.recovering = False
            parked, self._recovery_parked = self._recovery_parked, {}
            # unconfirmed placements need no processing of their own: every
            # parked placement also lives in _recovery_unplaced_actors,
            # which the re-create loop below drains
            self._recovery_placements.clear()
            unplaced, self._recovery_unplaced_actors = (
                self._recovery_unplaced_actors, {},
            )
            lost_objs, self._recovery_objects = self._recovery_objects, {}
            for tid_bin, (pt, _node_hex, is_actor) in parked.items():
                if is_actor:
                    # the creation lease never re-confirmed: re-place via
                    # the normal lease path (budget untouched — the node
                    # vanished, not the actor)
                    self._enqueue_ready(pt)
                    self.recovery_counters["creation_leases_replaced"] += 1
                else:
                    self._enqueue_ready(pt)
                    self.recovery_counters["leases_replaced"] += 1
            self.sched_cv.notify_all()
        # actors whose placement/creation never re-confirmed: re-create
        # through the normal submit path (restart semantics)
        recreated = 0
        for aid_bin, (spec, name) in unplaced.items():
            with self.lock:
                actor = self.actors.get(ActorID(aid_bin))
                if actor is None or actor.state in ("DEAD", "ALIVE"):
                    continue  # reaped, or a late reconcile rebound it
                if spec.task_id in self.pending_by_id:
                    continue  # parked creation requeued above
                actor.state = "PENDING"
                for oid in spec.return_ids():
                    self.ref_counts[oid] += 1  # recovery pin
            try:
                self._submit_replayed(spec)
                recreated += 1
            except Exception:
                logger.warning(
                    "could not re-create actor %s",
                    name or spec.actor_id.hex(), exc_info=True,
                )
        self.recovery_counters["actors_recreated"] += recreated
        dur = time.time() - self.recovery_info.get("started_t", time.time())
        self.recovery_info.update(
            finished_t=time.time(),
            duration_s=dur,
            reason=reason,
            nodes_reconciled=sum(
                1 for r in self._recovery_nodes.values()
                if r["status"] == "done"
            ),
            lost_objects=len(lost_objs),
        )
        # recovery spans ride the PR 14 tracing plane (head-local ring →
        # merged timeline)
        try:
            from ray_tpu.util import tracing

            if tracing.enabled():
                tracing.record_span(
                    "head.recovery",
                    self.recovery_info.get("started_t", time.time()),
                    time.time(),
                    plane="head",
                    reason=reason,
                    nodes=self.recovery_info.get("nodes_reconciled", 0),
                )
        except Exception:  # noqa: BLE001
            pass
        # getters blocked on objects that never re-confirmed must fail,
        # not hang (lineage reconstruction still gets its chance)
        if lost_objs:
            self._maybe_recover([ObjectID(o) for o in lost_objs])
        self._seal_lost_objects(
            list(lost_objs) + self._recovery_dropped_plasma
        )
        self._recovery_dropped_plasma = []
        self._fail_unrecoverable_waiters()
        logger.warning(
            "head recovery finished (%s) in %.2fs: %s", reason, dur,
            {k: v for k, v in self.recovery_counters.items() if v},
        )
        # recovery settled: compact so the next restart replays this state
        self.compact_now()

    def recovery_report(self) -> dict:
        """The ``recovery_stats`` op: WAL health + recovery phase/counters
        (the ``ray-tpu recovery`` CLI and state API surface)."""
        w = self._wal
        with self.lock:
            out = {
                "recovering": self.recovering,
                "phase": "recovering" if self.recovering else "normal",
                "nodes": {
                    h: r["status"] for h, r in self._recovery_nodes.items()
                },
                "parked_leases": len(self._recovery_parked),
                "parked_placements": len(self._recovery_placements),
                "parked_objects": len(self._recovery_objects),
                "counters": {
                    k: v for k, v in self.recovery_counters.items()
                },
                "last_recovery": dict(self.recovery_info),
            }
        out["wal"] = (
            {
                "enabled": True,
                "path": w.path,
                "healthy": w.healthy,
                "appends": w.appends,
                "flushes": w.flushes,
                "errors": w.errors,
                "bytes_written": w.bytes_written,
                "size_bytes": w.size_bytes(),
                "kind_counts": dict(w.kind_counts),
            }
            if w is not None
            else {"enabled": False}
        )
        return out

    def _fail_unrecoverable_waiters(self):
        with self.lock:
            doomed = []
            for oid, waiters in list(self.waiting_on_deps.items()):
                if self.memory_store.contains(oid):
                    continue
                producer = TaskID(oid.binary()[: TaskID.SIZE])
                if (
                    producer in self.pending_by_id
                    or producer in self._recovering
                    or oid in self.lineage
                ):
                    continue
                doomed.extend((oid, pt) for pt in waiters)
                del self.waiting_on_deps[oid]
        for oid, pt in doomed:
            self._fail_task(
                pt,
                ObjectLostError(
                    f"dependency {oid.hex()} was lost with the previous "
                    f"controller and has no lineage"
                ),
            )

    # -------------------------------------------------------- memory monitor

    def kill_one_task_for_memory(self, usage: float) -> bool:
        """Kill the worker running the most recently dispatched RETRIABLE
        normal task (reference: retriable-FIFO worker killing policy,
        ``worker_killing_policy.h:39``). Returns True if a victim was killed."""
        with self.lock:
            candidates = []  # (dispatch_time, worker, task)
            for w in self.workers.values():
                if w.dead or w.proc is None:
                    continue
                for pt in w.running.values():
                    if (
                        pt.spec.task_type == TaskType.NORMAL_TASK
                        and pt.retries_left > 0
                    ):
                        candidates.append((pt.dispatch_t, w, pt))
            if not candidates:
                return False
            # newest dispatch = cheapest work to redo
            _, victim, pt = max(candidates, key=lambda c: c[0])
        logger.warning(
            "memory usage %.2f >= threshold: killing worker %s (task %s, "
            "%d retries left)",
            usage, victim.worker_id.hex()[:8], pt.spec.name, pt.retries_left,
        )
        try:
            victim.proc.kill()
        except OSError:
            return False
        return True

    # ------------------------------------------------------------------ nodes

    def add_node(self, resources: dict[str, float], labels=None) -> NodeID:
        """Add a fake node (multi-node-on-one-host testing; reference:
        ``python/ray/cluster_utils.py:135``)."""
        with self.lock:
            node_id = NodeID.from_random()
            self.nodes[node_id] = NodeState(node_id, resources, labels)
            self.sched_cv.notify_all()
        self.publish("nodes", {"node_id": node_id.hex(), "event": "added", "resources": dict(resources)})
        return node_id

    def _store_for_node(self, node_id: NodeID):
        """The node's object store; non-head nodes get their own arena
        lazily (each node its own data plane — objects cross nodes only via
        the pull protocol, never via a shared mapping)."""
        with self.lock:
            store = self.node_stores.get(node_id)
            if store is not None:
                return store
            from ray_tpu._private.object_store import NativePlasmaStore

            if not hasattr(self.plasma, "arena_name"):
                # Python per-segment fallback: single shared store
                self.node_stores[node_id] = self.plasma
                return self.plasma
            arena_name = f"/rtpu-{os.getpid()}-n{node_id.hex()[:8]}"
            store = NativePlasmaStore(self.config.object_store_memory, arena_name)
            self.node_stores[node_id] = store
            self._stores_by_arena[arena_name] = store
            return store

    def _store_for_location(self, shm_name: str):
        """Route a location string to the store that owns it."""
        from ray_tpu._private.object_store import parse_arena_location

        loc = parse_arena_location(shm_name)
        if loc is not None:
            store = self._stores_by_arena.get(loc[0])
            if store is not None:
                return store
        return self.plasma

    def remove_node(self, node_id: NodeID):
        with self.lock:
            node = self.nodes.get(node_id)
            if node is None or not node.alive:
                return  # unknown or already being removed
            node.alive = False
            agent = self.agents.pop(node_id, None)
            rec = self._recovery_nodes.get(node_id.hex())
            if rec is not None and rec["status"] != "done":
                # a reconciling node died mid-recovery: stop waiting on it
                # (its journaled leases re-place below / at the deadline)
                rec["status"] = "done"
        self._journal("node_down", node_id.hex())
        if agent is not None:
            try:
                agent.send(P.Shutdown())
            except (OSError, EOFError):
                pass
            try:
                agent.conn.close()
            except (OSError, EOFError):
                pass
            if agent.data_address:
                self._data_pool.drop(agent.data_address)
        self.publish("nodes", {"node_id": node_id.hex(), "event": "removed"})
        dead_arena = None
        with self.lock:
            victims = [w for w in self.workers.values() if w.node_id == node_id]
            # The node's data plane dies with it: every object resident in
            # its arena is LOST (reference: node failure → plasma contents
            # gone; recovery via lineage, object_recovery_manager.h:43).
            store = self.node_stores.pop(node_id, None)
            lost: list[ObjectID] = []
            if store is not None and store is not self.plasma:
                arena = getattr(store, "arena_name", None)
                dead_arena = arena
                if arena is not None:
                    self._stores_by_arena.pop(arena, None)
                    if getattr(store, "is_remote", False):
                        lost = list(self._remote_resident.pop(arena, set()))
                        for oid in lost:
                            self._agent_spills.pop(oid, None)
                    else:
                        prefix = f"@{arena}#"
                        lost = [
                            oid
                            for oid, (name, _) in list(self.plasma_resident.items())
                            if name.startswith(prefix)
                        ]
                    for oid in lost:
                        self.plasma_resident.pop(oid, None)
                        self.memory_store.delete([oid])
                try:
                    store.shutdown()
                except Exception:  # noqa: BLE001
                    pass
        for w in victims:
            self._on_worker_death(w, reason=f"node {node_id.hex()[:8]} removed")
        # tasks leased to the dead node's agent: retry elsewhere or fail
        failed_leased: list = []
        with self.lock:
            for tid_b in node.leased:
                self._journal("unlease", tid_b)
            for tid_b in node.actor_leases:
                self._journal("unlease", tid_b)
            for pt in node.leased.values():
                self._release_task_resources(pt)
                if pt.retries_left > 0:
                    pt.retries_left -= 1
                    pt._avoid_node = node_id  # type: ignore[attr-defined]
                    self._enqueue_ready(pt)
                else:
                    failed_leased.append(pt)
            node.leased.clear()
            # actor CREATION leases mid-flight on the dead node: re-place
            # elsewhere WITHOUT charging the restart budget or the task
            # retry count — the node died, not the actor (reference: GCS
            # rescheduling a creation whose raylet died,
            # gcs_actor_scheduler.cc lease failure path)
            for pt in node.actor_leases.values():
                self._release_task_resources(pt)
                pt._avoid_node = node_id  # type: ignore[attr-defined]
                self._enqueue_ready(pt)
                self.actor_creation_stats["lease_retries"] += 1
            node.actor_leases.clear()
            self.sched_cv.notify_all()
        for pt in failed_leased:
            self._fail_task(
                pt, WorkerCrashedError(f"node {node_id.hex()[:8]} removed")
            )
        # replica directory upkeep: copies hosted ON the dead arena vanish
        # (no loss — primaries live elsewhere); primaries lost WITH the
        # node promote a surviving replica instead of re-running lineage
        if dead_arena is not None:
            self._drop_arena_replicas(dead_arena)
        if lost:
            lost = self._promote_replicas(lost)
        if lost:
            logger.warning(
                "node %s removed: %d resident object(s) lost",
                node_id.hex()[:8], len(lost),
            )
            # getters may already be BLOCKED on these ids: reconstruct what
            # lineage covers, and fail the rest with ObjectLostError so no
            # waiter hangs forever
            self._maybe_recover(lost)
            with self.lock:
                unrecoverable = [
                    oid
                    for oid in lost
                    if not self.memory_store.contains(oid)
                    and TaskID(oid.binary()[: TaskID.SIZE]) not in self.pending_by_id
                    and TaskID(oid.binary()[: TaskID.SIZE]) not in self._recovering
                ]
            for oid in unrecoverable:
                err = self.serialization.serialize(
                    ObjectLostError(
                        f"object {oid.hex()} was on removed node "
                        f"{node_id.hex()[:8]} and has no lineage"
                    )
                )
                self.memory_store.put(oid, ("error", err))
                self._on_object_sealed(oid)

    # -------------------------------------------------------------- node drain

    def drain_node(
        self,
        node_id: NodeID,
        deadline_s: float = 60.0,
        reason: str = "",
        preempt: bool = False,
    ) -> dict:
        """Begin a graceful drain (reference: the DrainRaylet protocol,
        ``node_manager.cc:1989`` / ``ray drain-node``). Marks the node
        DRAINING (no new leases/placements), quiesces its agent, waits for
        in-flight work within ``deadline_s``, migrates restartable actors
        and resident objects off, then releases the node. Idempotent:
        re-draining a draining node returns the existing status.

        ``preempt=True`` is the termination-notice variant (the node WILL
        die when the deadline lapses, announced or not): sole-copy arena
        objects re-replicate to surviving nodes before release, and the
        autoscaler reads ``preempting`` as a dead-launch signal and
        launches the replacement immediately."""
        with self.lock:
            node = self.nodes.get(node_id)
            if node is None or not node.alive:
                raise ValueError(f"unknown or dead node {node_id.hex()[:12]}")
            if node_id == self.head_node_id:
                raise ValueError("cannot drain the head node")
            if node.draining:
                rec = self.drains[node_id]
                if preempt and not node.preempting:
                    # upgrade in place: a SIGTERM notice landing on an
                    # operator-started drain adds the evacuation semantics
                    node.preempting = True
                    rec["preempt"] = True
                return self._drain_record_public(rec)
            node.draining = True
            node.preempting = preempt
            node.drain_reason = reason
            node.drain_deadline = time.time() + deadline_s
            rec = {
                "node_id": node_id.hex(),
                "state": "draining",
                "phase": "quiesce",
                "reason": reason,
                "preempt": preempt,
                "started_t": time.time(),
                "deadline_s": deadline_s,
                "migrated_actors": 0,
                "migrated_objects": 0,
                "replicated_objects": 0,
                "agent_quiesced": node.agent is None,
                "agent_remaining": 0,
            }
            self.drains[node_id] = rec
            while len(self.drains) > 64:
                old_id, old_rec = next(iter(self.drains.items()))
                if old_rec["state"] == "draining":
                    break  # never evict an ACTIVE drain's record
                del self.drains[old_id]
            agent = node.agent
            # the scheduler must stop picking this node immediately
            self.sched_cv.notify_all()
        self.publish(
            "nodes",
            {"node_id": node_id.hex(), "event": "draining", "reason": reason},
        )
        if agent is not None:
            try:
                agent.send(P.DrainAgent(deadline_s, reason))
            except (OSError, EOFError):
                rec["agent_quiesced"] = True  # dead agent: nothing to quiesce
        threading.Thread(
            target=self._drain_loop,
            args=(node, rec, node.drain_deadline),
            daemon=True,
            name=f"drain-{node_id.hex()[:8]}",
        ).start()
        return self._drain_record_public(rec)

    @staticmethod
    def _drain_record_public(rec: dict) -> dict:
        return dict(rec)

    def drain_status(self, node_hex: Optional[str] = None):
        """One drain record (by node-id hex prefix) or all of them."""
        with self.lock:
            recs = [dict(r) for r in self.drains.values()]
        if node_hex is None:
            return recs
        matches = [r for r in recs if r["node_id"].startswith(node_hex)]
        return matches[0] if matches else None

    def _drain_loop(self, node: NodeState, rec: dict, deadline: float):
        try:
            # 1) migrate restartable actors (their in-flight calls finish
            # first; queued calls survive the controlled restart)
            rec["phase"] = "migrate-actors"
            rec["migrated_actors"] = self._drain_migrate_actors(node, deadline)
            # 2) wait for in-flight normal tasks (head-dispatched + leased)
            rec["phase"] = "wait-tasks"
            clean = self._drain_wait_tasks(node, deadline)
            # 2b) preempt drains: sole-copy residents re-home onto
            # SURVIVING nodes (replica-directory promotion at removal is
            # then free); the head pull below stays the fallback for
            # whatever the window didn't cover
            if rec.get("preempt"):
                rec["phase"] = "replicate-objects"
                rec["replicated_objects"] = self._preempt_replicate_objects(
                    node, deadline
                )
            # 3) pull resident objects to the head before the arena dies
            rec["phase"] = "migrate-objects"
            rec["migrated_objects"] = self._migrate_node_objects(node, deadline)
            # 4) agent quiesce handshake (logs flushed, local queue empty).
            # A node that died mid-drain has nothing left to quiesce — stop
            # waiting instead of spinning out the whole deadline.
            rec["phase"] = "wait-agent"
            while (
                not rec["agent_quiesced"]
                and node.alive
                and time.time() < deadline
                and not self.shutting_down
            ):
                time.sleep(0.05)
            rec["state"] = (
                "drained" if clean and rec["agent_quiesced"] else "timeout"
            )
        except Exception:  # noqa: BLE001 — a drain bug must still release
            logger.error("drain of node %s failed:\n%s",
                         node.node_id.hex()[:8], traceback.format_exc())
            rec["state"] = "error"
        rec["phase"] = "release"
        rec["completed_t"] = time.time()
        self.publish(
            "nodes",
            {"node_id": node.node_id.hex(), "event": "drained",
             "state": rec["state"]},
        )
        logger.info(
            "node %s drain %s: %d actor(s) migrated, %d object(s) pulled",
            node.node_id.hex()[:8], rec["state"],
            rec["migrated_actors"], rec["migrated_objects"],
        )
        self.remove_node(node.node_id)

    def _drain_migrate_actors(self, node: NodeState, deadline: float) -> int:
        """Respawn restartable actors elsewhere: wait for each actor's
        in-flight calls to finish, hold its queue, then retire its worker —
        the normal restart path re-places it (the scheduler no longer picks
        the draining node). The restart budget is NOT charged (this is a
        controlled migration, not a failure)."""
        migrated = 0
        while time.time() < deadline and not self.shutting_down:
            candidate = None
            waiting = False
            with self.lock:
                for actor in self.actors.values():
                    if (
                        actor.state == "ALIVE"
                        and actor.worker is not None
                        and actor.worker.node_id == node.node_id
                        and actor.restarts_left != 0
                        and not getattr(actor, "_drain_migrating", False)
                    ):
                        # stop dispatching queued calls onto the old worker
                        # (they replay on the migrated incarnation)
                        actor._drain_hold = True  # noqa: SLF001
                        if actor.inflight == 0:
                            candidate = actor
                            actor._drain_migrating = True  # noqa: SLF001
                            break
                        waiting = True  # in-flight calls still draining
                if node.actor_leases:
                    # a creation lease granted before the drain is still
                    # placing: wait for it to go ALIVE here, then migrate
                    # it like the rest (the scheduler already stopped
                    # granting this node new leases)
                    waiting = True
            if candidate is None:
                if not waiting:
                    return migrated
                time.sleep(0.02)
                continue
            worker = candidate.worker
            if worker is None:
                continue  # died concurrently: the restart path owns it now
            try:
                worker.send(P.KillActor(candidate.actor_id))
            except (OSError, EOFError):
                pass
            if worker.proc is not None:
                try:
                    worker.proc.terminate()
                except OSError:
                    pass
            elif worker.agent is not None:
                try:
                    worker.agent.send(P.KillWorker(worker.worker_id))
                except (OSError, EOFError):
                    pass
            migrated += 1
        return migrated

    def _drain_wait_tasks(self, node: NodeState, deadline: float) -> bool:
        """Block until no task runs on the node (head-dispatched workers +
        agent leases). Returns False when the deadline lapsed first."""
        while time.time() < deadline and not self.shutting_down:
            with self.lock:
                busy = (
                    bool(node.leased)
                    or bool(node.actor_leases)
                    or any(
                        w.running
                        for w in self.workers.values()
                        if w.node_id == node.node_id and not w.dead
                    )
                )
            if not busy:
                return True
            time.sleep(0.05)
        with self.lock:
            return (
                not node.leased
                and not node.actor_leases
                and not any(
                    w.running
                    for w in self.workers.values()
                    if w.node_id == node.node_id and not w.dead
                )
            )

    def _migrate_node_objects(self, node: NodeState, deadline: float) -> int:
        """Pull-before-release: reseal the draining node's resident objects
        into the head's store so node removal loses nothing (the inverse of
        the lazy pull protocol — eager evacuation, reference: the object
        migration step of safe raylet drain)."""
        from ray_tpu._private.object_store import ObjectExistsError

        store = self.node_stores.get(node.node_id)
        if store is None or store is self.plasma:
            return 0  # shared-store fallback: nothing dies with the node
        is_remote = getattr(store, "is_remote", False)
        arena = getattr(store, "arena_name", None)
        with self.lock:
            if is_remote:
                oids = list(self._remote_resident.get(arena, ()))
                oids += [
                    oid
                    for oid, ag in self._agent_spills.items()
                    if ag is store.agent and oid not in oids
                ]
            else:
                prefix = f"@{arena}#"
                oids = [
                    oid
                    for oid, (name, _) in self.plasma_resident.items()
                    if name.startswith(prefix)
                ]
            # a copy already replicated to a SURVIVING arena re-homes for
            # free at removal (replica promotion) — don't also pay a full
            # pull to the head (the preempt evacuation above feeds this)
            oids = [
                oid
                for oid in oids
                if not any(
                    a != arena for a in self._object_replicas.get(oid, ())
                )
            ]
        moved = 0
        for oid in oids:
            if time.time() > deadline:
                logger.warning(
                    "drain deadline hit with %d object(s) left on node %s",
                    len(oids) - moved, node.node_id.hex()[:8],
                )
                break
            entry = self.memory_store.get([oid], timeout=0)[0]
            if entry is None or entry[0] not in ("plasma", "spilled"):
                continue  # freed or already inline meanwhile
            try:
                data = self.resolve_object(entry, object_id=oid).to_bytes()
            except Exception:  # noqa: BLE001 — freed/unreachable: skip
                continue
            try:
                seg, name = self._plasma_create_with_spill(oid, len(data))
                seg.buf[: len(data)] = data
                self._seal_plasma(oid, name, len(data))
            except ObjectExistsError:
                pass  # already resident on the head
            except Exception:  # noqa: BLE001
                logger.warning("object migration failed for %s", oid.hex(),
                               exc_info=True)
                continue
            with self.lock:
                if is_remote:
                    self._remote_resident.get(arena, set()).discard(oid)
                    self._agent_spills.pop(oid, None)
            moved += 1
        return moved

    def _preempt_replicate_objects(self, node: NodeState, deadline: float) -> int:
        """Termination-notice evacuation: re-home the dying node's
        SOLE-COPY resident objects onto surviving schedulable nodes before
        the arena dies (the replica directory then promotes them at
        removal — no reader pays lineage re-execution for an ANNOUNCED
        death). Head-managed target arenas pull synchronously via
        ``pull_into_arena``; real-agent targets get a ``ReplicateObjects``
        push and pull through their own single-flight machinery (which
        registers the replica back via ``register_replica``), with a
        bounded wait on those registrations. Returns how many of the
        sole-copy objects gained a surviving replica."""
        store = self.node_stores.get(node.node_id)
        if store is None or store is self.plasma:
            return 0  # shared-store fallback: nothing dies with the node
        dying = getattr(store, "arena_name", None)
        is_remote = getattr(store, "is_remote", False)
        with self.lock:
            if is_remote:
                oids = list(self._remote_resident.get(dying, ()))
            else:
                prefix = f"@{dying}#"
                oids = [
                    oid
                    for oid, (name, _) in self.plasma_resident.items()
                    if name.startswith(prefix)
                ]
            sole = []
            for oid in oids:
                if any(
                    a != dying for a in self._object_replicas.get(oid, ())
                ):
                    continue  # already survives elsewhere: promotion is free
                entry = self.memory_store.peek(oid)
                if entry is None or entry[0] != "plasma":
                    continue  # freed / inlined meanwhile
                sole.append((oid, int(entry[1][1])))
            targets = [
                n
                for n in self.nodes.values()
                if n.node_id != node.node_id
                and n.schedulable
                and n.node_id != self.head_node_id
            ]
        if not sole or not targets:
            return 0
        # round-robin the sole copies across the survivors, then batch per
        # target: agent-backed nodes take ONE ReplicateObjects push each,
        # head-managed arena nodes pull synchronously from this thread
        assignments: "dict[NodeID, list]" = {}
        for i, pair in enumerate(sole):
            assignments.setdefault(
                targets[i % len(targets)].node_id, []
            ).append(pair)
        pushed: list = []
        for nid, batch in assignments.items():
            with self.lock:
                n = self.nodes.get(nid)
                agent = n.agent if n is not None and n.alive else None
                hosted = n is not None and n.alive
            if not hosted:
                continue  # the target died mid-evacuation: fallback covers
            if agent is not None:
                try:
                    self._maybe_inject_rpc_failure("replicate_objects")
                    agent.send(P.ReplicateObjects(list(batch)))
                    pushed.extend(oid for oid, _ in batch)
                except (OSError, EOFError, WorkerCrashedError):
                    continue  # dropped push: _migrate_node_objects covers
            else:
                for oid, size in batch:
                    try:
                        self.pull_into_arena(nid, oid, size_hint=size)
                    except Exception:  # noqa: BLE001 — fallback covers
                        logger.warning(
                            "preempt replication of %s failed", oid.hex(),
                            exc_info=True,
                        )
        # bounded wait for the pushed agents' register_replica round-trips
        # (never past the notice deadline — the head pull fallback needs
        # what's left of the window)
        while pushed and time.time() < deadline and not self.shutting_down:
            with self.lock:
                pushed = [
                    oid
                    for oid in pushed
                    if not any(
                        a != dying
                        for a in self._object_replicas.get(oid, ())
                    )
                ]
            if pushed:
                time.sleep(0.05)
        with self.lock:
            replicated = sum(
                1
                for oid, _ in sole
                if any(
                    a != dying for a in self._object_replicas.get(oid, ())
                )
            )
            self.transfer_stats["preempt_replications"] += replicated
        return replicated

    def node_preempt_notice(
        self, node_hex: str, notice_s: float, reason: str = ""
    ) -> dict:
        """The ``node_preempt_notice`` op (agent SIGTERM handler, `ray-tpu
        drain --notice-s`): this node will be reclaimed in ``notice_s``
        seconds. Starts a preempt drain — stop leasing, migrate actors,
        re-replicate sole-copy objects — and flags the node ``preempting``
        so the autoscaler launches a replacement NOW (the notice IS the
        death signal; waiting out heartbeat loss wastes the window).
        Idempotent: re-announcing returns the active drain record."""
        nid = NodeID(bytes.fromhex(node_hex))
        return self.drain_node(
            nid,
            deadline_s=max(float(notice_s), 0.0),
            reason=reason or "preempt-notice",
            preempt=True,
        )

    # ------------------------------------------------------------ object plane

    def put_serialized(self, object_id: ObjectID, sobj: SerializedObject, is_error=False):
        """Store a driver-side object (inline or plasma by size)."""
        from ray_tpu._private.object_store import ObjectExistsError

        if sobj.total_bytes() <= self.config.max_inline_object_size or is_error:
            kind = "error" if is_error else "inline"
            self.memory_store.put(object_id, (kind, sobj))
            if (
                self._wal is not None
                and not self._wal_suppress
                and self._wal.healthy
            ):
                # flatten only when actually journaling: to_bytes() copies
                self._journal(
                    "seal", (object_id.binary(), kind, sobj.to_bytes())
                )
        else:
            data = sobj.to_bytes()
            try:
                seg, name = self._plasma_create_with_spill(object_id, len(data))
            except ObjectExistsError:
                # duplicate put (e.g. a retry whose first attempt sealed):
                # idempotent — the sealed object stands
                self._on_object_sealed(object_id)
                return
            seg.buf[: len(data)] = data
            self._seal_plasma(object_id, name, len(data))
        self._on_object_sealed(object_id)

    # ------------------------------------------------------------- spilling

    def _create_with_spill_retry(self, create_fn, object_id: ObjectID, size: int, store=None):
        """Run a plasma create, spilling cold resident objects on
        ObjectStoreFullError (reference: LocalObjectManager::SpillObjects +
        the store-full delay/retry loop, object_store_full_delay_ms).

        The retry matters beyond spilling: under concurrent producers the
        arena can be full of CREATED-but-not-yet-SEALED allocations (their
        seal messages are in flight) — nothing is spillable *yet*, but will
        be milliseconds later."""
        from ray_tpu.exceptions import ObjectStoreFullError

        deadline = time.time() + 10.0
        while True:
            try:
                return create_fn(object_id, size)
            except ObjectStoreFullError:
                if self._spill_objects(size, store=store or self.plasma):
                    continue
                if time.time() > deadline:
                    raise
                time.sleep(self.config.object_store_full_delay_ms / 1000.0)

    def _plasma_create_with_spill(self, object_id: ObjectID, size: int):
        return self._create_with_spill_retry(self.plasma.create, object_id, size)

    def _seal_plasma(self, object_id: ObjectID, name: str, size: int):
        store = self._store_for_location(name)
        store.seal(object_id, name, size)  # idempotent
        self.memory_store.put(object_id, ("plasma", (name, size)))
        # agent-arena locations replay as parked entries a reconciling
        # agent confirms; head-arena payloads die with this process (the
        # record still dedups a completed task against re-execution)
        self._journal("seal", (object_id.binary(), "plasma", (name, size)))
        with self.lock:
            if getattr(store, "is_remote", False):
                # resident on an agent's arena: the agent owns spilling;
                # the controller only tracks membership for loss accounting
                self._remote_resident[store.arena_name].add(object_id)
            else:
                self.plasma_resident[object_id] = (name, size)
                self.plasma_resident.move_to_end(object_id)

    def _spill_objects(self, need_bytes: int, store=None) -> bool:
        """Move the coldest plasma-resident objects to disk files until
        ``need_bytes`` is freed; their store entries become ('spilled', ...).

        Serialized by ``_spill_lock``: concurrent allocation RPCs must not
        spill the same object (one would delete the arena block while the
        other is still reading it — torn spill files)."""
        os.makedirs(self.spill_dir, exist_ok=True)
        with self._spill_lock:
            # 1) reclaim matured trash: blocks of previously-spilled objects
            # whose in-flight-reader grace has passed
            freed = self._reclaim_trash_locked()
            if freed >= need_bytes:
                return True
            store = store or self.plasma
            # 1.5) replicas resident in THIS arena are redundant copies —
            # evict them outright (no disk write, no grace: the primary
            # serves re-pulls) before spilling any primary
            freed += self._evict_replicas_locked(store, need_bytes - freed)
            if freed >= need_bytes:
                return True
            # 2) spill just enough cold residents to cover the remainder —
            # only residents of the arena that is actually full
            with self.lock:
                candidates = [
                    (oid, v)
                    for oid, v in self.plasma_resident.items()
                    if self._store_for_location(v[0]) is store
                ]
            spilled_bytes = 0
            for oid, (name, size) in candidates:
                if freed + spilled_bytes >= need_bytes:
                    break
                with self.lock:
                    if oid not in self.plasma_resident:
                        continue  # freed/spilled meanwhile
                try:
                    sobj = self.plasma_client.read(name, size)
                    path = os.path.join(self.spill_dir, f"{oid.hex()}.bin")
                    with open(path, "wb") as f:
                        f.write(sobj.to_bytes())
                except Exception:
                    logger.warning("spill failed for %s", oid.hex(), exc_info=True)
                    continue
                # commit atomically vs _free_object: the object must still be
                # tracked, or the put would resurrect a freed object
                with self.lock:
                    if oid not in self.plasma_resident:
                        try:
                            os.unlink(path)
                        except OSError:
                            pass
                        continue
                    self.plasma_resident.pop(oid, None)
                    self.memory_store.put(oid, ("spilled", (path, size)))
                    # plasma block reclaimed AFTER the reader grace period —
                    # workers may already hold the old plasma location
                    # (readers also validate-after-read, so the grace is a
                    # courtesy, not the correctness mechanism)
                    self._spill_trash.append((time.time(), oid, size, name))
                spilled_bytes += size
                logger.info("spilled %s (%d bytes) to %s", oid.hex(), size, path)
            if freed + spilled_bytes < need_bytes:
                return freed > 0  # partial progress at best
            # 3) the just-spilled blocks only free space after the grace;
            # wait it out HERE (spilling is serialized anyway) so the caller's
            # retry actually succeeds instead of mass-spilling more residents
            if self._spill_trash:
                mature_at = self._spill_trash[0][0] + self._spill_grace_s
                delay = mature_at - time.time()
                if delay > 0:
                    # sliced, liveness-aware grace wait: _spill_lock only
                    # serializes spilling itself (pacing under it is the
                    # intended design), but shutdown must not sit out the
                    # full reader grace
                    deadline = time.monotonic() + delay
                    while not self.shutting_down:
                        step = min(0.05, deadline - time.monotonic())
                        if step <= 0:
                            break
                        time.sleep(step)  # tpulint: disable=blocking-under-lock
                self._reclaim_trash_locked()
            return True

    def _evict_replicas_locked(self, store, need_bytes: int) -> int:
        """Delete replica copies hosted in ``store``'s arena until
        ``need_bytes`` is freed (caller holds ``_spill_lock``). Replica
        eviction is instant — the directory entry is the only state."""
        arena = getattr(store, "arena_name", None)
        if arena is None or need_bytes <= 0:
            return 0
        freed = 0
        with self.lock:
            victims = [
                (oid, self._object_replicas[oid][arena][1])
                for oid in self._replicas_by_arena.get(arena, ())
                if arena in self._object_replicas.get(oid, {})
            ]
        for oid, size in victims:
            if freed >= need_bytes:
                break
            # atomic membership re-check + unregister: a concurrent
            # promotion (primary's node died) may have turned this copy
            # into THE primary — deleting it then would lose the object
            with self.lock:
                reps = self._object_replicas.get(oid)
                if not reps or arena not in reps:
                    continue  # promoted or freed since the snapshot
                reps.pop(arena, None)
                if not reps:
                    del self._object_replicas[oid]
                self._replicas_by_arena[arena].discard(oid)
            try:
                store.delete(oid)
            except Exception:  # noqa: BLE001 — already relocated/raced
                continue
            freed += size
            logger.info("evicted replica of %s (%d bytes)", oid.hex(), size)
        return freed

    def _reclaim_trash_locked(self) -> int:
        """Delete matured trash blocks; returns bytes freed. Caller holds
        ``_spill_lock``."""
        now = time.time()
        freed = 0
        while self._spill_trash and now - self._spill_trash[0][0] >= self._spill_grace_s:
            _, old_oid, size, name = self._spill_trash.popleft()
            self._store_for_location(name).delete(old_oid)
            freed += size
        return freed

    # ------------------------------------------- agent data plane (pull side)

    def _replica_addresses(self, object_id: ObjectID, exclude=None) -> list:
        """Data addresses of agents holding a replica of ``object_id`` (the
        location-directory read; reference: OwnershipObjectDirectory)."""
        out = []
        with self.lock:
            reps = self._object_replicas.get(object_id)
            if not reps:
                return out
            for arena in reps:
                store = self._stores_by_arena.get(arena)
                if store is None or not getattr(store, "is_remote", False):
                    continue
                addr = store.agent.data_address
                if addr and addr != exclude:
                    out.append(addr)
        return out

    def _primary_data_address(self, object_id: ObjectID):
        """Data address of the agent holding the PRIMARY copy (None when
        the primary is head-resident or inline — served via head relay)."""
        entry = self.memory_store.get([object_id], timeout=10)[0]
        if entry is None:
            return None
        if entry[0] == "spilled":
            agent = self._agent_spills.get(object_id)
            return agent.data_address if agent is not None else None
        if entry[0] != "plasma":
            return None
        store = self._store_for_location(entry[1][0])
        if getattr(store, "is_remote", False):
            return store.agent.data_address
        return None

    def _on_source_failed(self, address: str, _err) -> None:
        """A replica/owner stopped serving mid-pull: drop its pooled conns
        so the next dial is fresh (node-death detection reaps the
        directory entries; this just stops retrying a dead socket)."""
        self._data_pool.drop(address)

    def _pull_chunk_from_agent(
        self, address: str, object_id: ObjectID, offset: int, length: int,
        extra_addresses=(),
    ):
        """One chunk from the owner or any replica, spread + failover."""
        addrs = [address] + [a for a in extra_addresses if a != address]
        fetcher = P.ReplicaFetcher(
            self._data_pool, object_id.binary(), addrs,
            on_source_fail=self._on_source_failed,
        )
        try:
            return fetcher(offset, length)
        except P.ChunkPullError as e:
            raise ObjectLostError(f"agent pull failed: {e}") from e

    def _pull_whole_from_agent(
        self, address: str, object_id: ObjectID, size: int
    ) -> bytearray:
        buf = bytearray(size)
        self._pull_into_buffer(address, object_id, size, memoryview(buf))
        return buf

    def _pull_into_buffer(
        self, address: str, object_id: ObjectID, size: int, mv
    ) -> None:
        """Windowed, replica-aware whole-object pull straight into ONE
        preallocated buffer (caller-owned — a bytearray or an arena view):
        chunks spread across every node that holds a copy, a dying source
        fails over to the survivors mid-pull."""
        addrs = [address] + self._replica_addresses(object_id, exclude=address)
        fetcher = P.ReplicaFetcher(
            self._data_pool, object_id.binary(), addrs,
            on_source_fail=self._on_source_failed,
        )
        try:
            P.pull_windowed(
                fetcher,
                P._buffer_sink(mv),
                size,
                self.config.object_transfer_chunk_bytes,
                self.config.object_transfer_window,
            )
        except P.ChunkPullError as e:
            raise ObjectLostError(f"agent pull failed: {e}") from e
        with self.lock:
            self.transfer_stats["head_peer_chunks_pulled"] += fetcher.peer_chunks

    def resolve_object(self, entry, object_id: ObjectID = None) -> SerializedObject:
        from ray_tpu._private.object_store import ObjectRelocatedError

        kind, payload = entry
        if kind in ("inline", "error"):
            return payload
        if kind == "spilled":
            path, size = payload
            agent = self._agent_spills.get(object_id) if object_id else None
            if agent is not None:
                return SerializedObject.from_buffer(
                    self._pull_whole_from_agent(agent.data_address, object_id, size)
                )
            with open(path, "rb") as f:
                return SerializedObject.from_buffer(f.read())
        shm_name, size = payload
        store = self._store_for_location(shm_name)
        if getattr(store, "is_remote", False):
            # resident on an agent's host: fetch over its data listener
            # (always — even same-host in tests — so the cross-host path is
            # the one that's exercised)
            if object_id is None:
                from ray_tpu._private.object_store import parse_arena_location

                loc = parse_arena_location(shm_name)
                object_id = ObjectID(loc[2]) if loc and loc[2] else None
            if object_id is None:
                raise ObjectLostError(f"cannot pull unkeyed location {shm_name}")
            try:
                return SerializedObject.from_buffer(
                    self._pull_whole_from_agent(
                        store.agent.data_address, object_id, size
                    )
                )
            except (OSError, EOFError, ConnectionError, ObjectLostError):
                # the owner died between the entry read and the pull: node
                # removal deletes the entry and lineage reconstruction
                # reseals it — re-resolve against the FRESH entry
                self._maybe_recover([object_id])
                fresh = self.memory_store.get([object_id], timeout=60)[0]
                if fresh is None or fresh == entry:
                    raise
                return self.resolve_object(fresh, object_id=object_id)
        try:
            return self.plasma_client.read(shm_name, size)
        except ObjectRelocatedError:
            # read raced with spilling: re-resolve from the (updated) entry
            if object_id is None:
                raise
            fresh = self.memory_store.get([object_id], timeout=5.0)[0]
            if fresh is None:
                raise
            return self.resolve_object(fresh)

    def get_entries(self, object_ids: list[ObjectID], timeout=None):
        self._maybe_recover(object_ids)
        return self.memory_store.get(object_ids, timeout=timeout)

    # ------------------------------------------- replica location directory

    def _register_replica_entry(
        self, object_id: ObjectID, location: str, size: int
    ) -> bool:
        """Record a secondary copy in the location directory. False when the
        object was freed while the replica materialized — the caller must
        discard its copy instead of resurrecting a dead id."""
        from ray_tpu._private.object_store import parse_arena_location

        loc = parse_arena_location(location)
        if loc is None:
            return False
        arena = loc[0]
        with self.lock:
            if not self.memory_store.contains(object_id):
                return False
            self._object_replicas.setdefault(object_id, {})[arena] = (
                location,
                size,
            )
            self._replicas_by_arena[arena].add(object_id)
            self.transfer_stats["replicas_registered"] += 1
        return True

    def _unregister_replica(self, object_id: ObjectID, arena: str) -> None:
        with self.lock:
            reps = self._object_replicas.get(object_id)
            if reps is not None:
                reps.pop(arena, None)
                if not reps:
                    del self._object_replicas[object_id]
            self._replicas_by_arena[arena].discard(object_id)

    def _drop_replicas(self, object_id: ObjectID) -> None:
        """Owner-driven invalidation (free / testing loss): every replica
        copy is deleted from its hosting store and forgotten."""
        with self.lock:
            reps = self._object_replicas.pop(object_id, None)
            if reps:
                for arena in reps:
                    self._replicas_by_arena[arena].discard(object_id)
        if not reps:
            return
        for arena in reps:
            store = self._stores_by_arena.get(arena)
            if store is None:
                continue
            try:
                # RemoteArenaProxy relays a FreeLocal to the hosting agent
                store.delete(object_id)
            except Exception:  # noqa: BLE001 — best-effort invalidation
                pass

    def _drop_arena_replicas(self, arena: str) -> None:
        """A node's arena died (node removal): its replica entries vanish —
        no data loss, the primaries live elsewhere."""
        with self.lock:
            for oid in self._replicas_by_arena.pop(arena, set()):
                reps = self._object_replicas.get(oid)
                if reps is not None:
                    reps.pop(arena, None)
                    if not reps:
                        del self._object_replicas[oid]

    def _promote_replicas(self, lost: list) -> list:
        """A node died holding PRIMARY copies: repoint each lost entry at a
        surviving replica instead of running lineage recovery (the copy
        exists — promotion is free). Returns the ids that stay lost."""
        still_lost = []
        for oid in lost:
            promoted = False
            with self.lock:
                reps = self._object_replicas.get(oid)
                while reps:
                    arena, (location, size) = next(iter(reps.items()))
                    reps.pop(arena, None)
                    self._replicas_by_arena[arena].discard(oid)
                    store = self._stores_by_arena.get(arena)
                    if store is None:
                        continue  # that replica's node is gone too
                    if not reps:
                        self._object_replicas.pop(oid, None)
                    self.memory_store.put(oid, ("plasma", (location, size)))
                    if getattr(store, "is_remote", False):
                        self._remote_resident[arena].add(oid)
                    else:
                        self.plasma_resident[oid] = (location, size)
                    self.transfer_stats["replicas_promoted"] += 1
                    promoted = True
                    break
                if not reps:
                    self._object_replicas.pop(oid, None)
            if promoted:
                # dep-waiters that slipped into the delete→promote window
                # must wake (same contract as a fresh seal)
                self._on_object_sealed(oid)
                logger.info("promoted replica of %s after node loss", oid.hex())
            else:
                still_lost.append(oid)
        return still_lost

    # ----------------------------------------------- pull-into-arena (head)

    def pull_into_arena(self, node_id, object_id: ObjectID, size_hint: int = 0):
        """Materialize a remote-resident object into ``node_id``'s arena and
        register that node as a replica, so every subsequent reader on the
        node mmaps the local copy (reference: pulls land in the local
        plasma store, ``pull_manager.h:49``). Returns the local ``(kind,
        payload)`` entry — or None when the node cannot host replicas (the
        caller falls back to a private direct pull). Single-flight per
        (arena, object): concurrent readers coalesce into one transfer."""
        if not self.config.pull_into_arena or node_id is None:
            return None
        store = self._store_for_node(node_id)
        if getattr(store, "is_remote", False) or not hasattr(store, "arena_name"):
            return None  # agent nodes pull via their own agent; no arena = no replica
        local = store.lookup(object_id)
        if local is not None:
            with self.lock:
                self.transfer_stats["arena_replica_hits"] += 1
            return ("plasma", local)
        key = (store.arena_name, object_id)
        with self._arena_pulls_lock:
            ev = self._arena_pulls.get(key)
            leader = ev is None
            if leader:
                ev = self._arena_pulls[key] = threading.Event()
        if not leader:
            # bounded, liveness-aware wait on the in-flight transfer
            deadline = time.monotonic() + 600.0
            while not ev.wait(timeout=1.0):
                if self.shutting_down or time.monotonic() > deadline:
                    return None
            local = store.lookup(object_id)
            if local is not None:
                with self.lock:
                    self.transfer_stats["arena_replica_hits"] += 1
                return ("plasma", local)
            return None  # the leader failed; let the caller direct-pull
        try:
            return self._pull_into_arena_leader(store, object_id)
        finally:
            with self._arena_pulls_lock:
                self._arena_pulls.pop(key, None)
            ev.set()

    def _pull_into_arena_leader(self, store, object_id: ObjectID):
        from ray_tpu._private.object_store import ObjectExistsError

        self._maybe_recover([object_id])
        entry = self.memory_store.get([object_id], timeout=30)[0]
        if entry is None:
            raise ObjectLostError(f"object {object_id.hex()} not found")
        kind, payload = entry
        if kind in ("inline", "error"):
            return (kind, payload.to_bytes())
        if kind == "spilled" and self._agent_spills.get(object_id) is None:
            return entry  # head-local spill file: same-host readers open it
        if kind == "plasma" and self._store_for_location(payload[0]) is store:
            return entry  # raced with a concurrent seal: already local
        size = payload[1]
        try:
            seg, name = self._create_with_spill_retry(
                store.create, object_id, size, store=store
            )
        except ObjectExistsError:
            local = store.lookup(object_id)
            if local is not None:
                return ("plasma", local)
            raise
        try:
            # fill the arena allocation DIRECTLY — no staging buffer, no
            # second full-object memcpy (it matters at multi-GB)
            self._fill_from_entry(
                memoryview(seg.buf)[:size], entry, object_id, size
            )
        except BaseException:
            # reclaim the unsealed allocation — a failed pull must not pin
            # arena space
            try:
                store.arena.delete(object_id.binary())
            except Exception:  # noqa: BLE001
                pass
            raise
        store.seal(object_id, name, size)
        if not self._register_replica_entry(object_id, name, size):
            # freed while the bytes were in flight: a freed-then-recreated
            # id must not find a stale replica
            try:
                store.delete(object_id)
            except Exception:  # noqa: BLE001
                pass
            raise ObjectLostError(f"object {object_id.hex()} freed during pull")
        with self.lock:
            self.transfer_stats["arena_pulls"] += 1
        return ("plasma", (name, size))

    def _fill_from_entry(self, mv, entry, object_id: ObjectID, size: int):
        """Write the object's FLAT payload bytes into ``mv`` from wherever
        the entry points (agent data plane / spill file / sibling arena) —
        the zero-staging fill behind pull-into-arena."""
        kind, payload = entry
        if kind == "spilled":
            path, _ = payload
            agent = self._agent_spills.get(object_id)
            if agent is not None:
                self._pull_into_buffer(agent.data_address, object_id, size, mv)
                return
            with open(path, "rb") as f:
                got = f.readinto(mv)
            if got != size:
                raise ObjectLostError(
                    f"short spill read for {object_id.hex()}: {got}/{size}"
                )
            return
        name, _ = payload
        store = self._store_for_location(name)
        if getattr(store, "is_remote", False):
            self._pull_into_buffer(store.agent.data_address, object_id, size, mv)
            return
        # same-process arena (another head-side node): one validated copy of
        # the raw flat buffer (seqlock protocol — see PlasmaClient.read)
        from ray_tpu._private.object_store import (
            ObjectRelocatedError,
            parse_arena_location,
        )

        loc = parse_arena_location(name)
        if loc is None or not hasattr(store, "arena"):
            # legacy per-segment store: re-flatten (small objects only)
            data = self.plasma_client.read(name, size).to_bytes()
            mv[: len(data)] = data
            return
        mv[:] = store.arena.view(loc[1], size)
        got = store.arena.lookup(object_id.binary())
        if got is None or got[0] != loc[1]:
            raise ObjectRelocatedError(name)

    def _on_object_sealed(self, object_id: ObjectID):
        with self.lock:
            producer = TaskID(object_id.binary()[: TaskID.SIZE])
            self._recovering.discard(producer)
            self._recon_depth.pop(producer, None)
            waiters = self.waiting_on_deps.pop(object_id, [])
            for pt in waiters:
                pt.unresolved.discard(object_id)
                if not pt.unresolved:
                    if pt.spec.is_actor_task():
                        # Actor tasks stay queued on their actor (head-of-line
                        # blocking preserves ordering); just re-pump.
                        actor = self.actors.get(pt.spec.actor_id)
                        if actor is not None:
                            self._pump_actor(actor)
                    else:
                        self._enqueue_ready(pt)
            if waiters:
                self.sched_cv.notify_all()
            # All handles to this object were already dropped: free eagerly.
            if object_id not in self.ref_counts:
                self._free_object(object_id)

    def publish(self, channel: str, event: dict):
        """Append an event to a pubsub channel and wake long-pollers."""
        with self._pubsub_cv:
            self._pubsub_seq[channel] += 1
            self._pubsub_events[channel].append(
                (self._pubsub_seq[channel], {**event, "t": time.time()})
            )
            self._pubsub_cv.notify_all()

    def pubsub_poll(self, channel: str, after_seq: int, timeout: float):
        """Long-poll: block until the channel has events newer than
        ``after_seq`` (or timeout); returns (latest_seq, [events])."""
        deadline = time.monotonic() + max(timeout, 0.0)
        with self._pubsub_cv:
            while True:
                events = [
                    (s, e)
                    for s, e in self._pubsub_events.get(channel, ())
                    if s > after_seq
                ]
                if events:
                    return (events[-1][0], [e for _, e in events])
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return (self._pubsub_seq.get(channel, 0), [])
                self._pubsub_cv.wait(remaining)

    def _maybe_pin_stream_item(self, object_id: ObjectID):
        """Pin a freshly-sealed stream item on behalf of its producer (the
        consumer has no handle yet; without this the refcount-0 eager free
        reclaims it before the consumer's wait() can see it)."""
        idx = object_id.return_index()
        if idx == 0 or object_id.is_put_object():
            return
        task_id = object_id.task_id()
        with self.lock:
            pt = self.pending_by_id.get(task_id)
            if pt is None or pt.spec.num_returns != "streaming":
                return
            pins = self._stream_pins.setdefault(task_id, set())
            if idx in pins:
                return  # retried producer re-putting an item: already pinned
            self.ref_counts[object_id] += 1
            pins.add(idx)

    # Reference counting -----------------------------------------------------

    def add_ref(self, object_id: ObjectID):
        with self.lock:
            self.ref_counts[object_id] += 1

    def remove_ref(self, object_id: ObjectID):
        with self.lock:
            self.ref_counts[object_id] -= 1
            if self.ref_counts[object_id] <= 0:
                del self.ref_counts[object_id]
                self._free_object(object_id)

    def _free_object(self, object_id: ObjectID):
        # atomic vs the spill commit (also under self.lock): the entry read
        # and the resident removal must observe one consistent state, or a
        # concurrent spill repoints the entry after we read 'plasma' and its
        # file is never unlinked
        with self.lock:
            if not object_id.is_put_object() and object_id.return_index() == 0:
                # a freed streaming completion record orphans the producer's
                # pins on never-consumed items — release them too
                task_id = object_id.task_id()
                pins = self._stream_pins.pop(task_id, None)
                if pins:
                    for idx in pins:
                        self.remove_ref(ObjectID.for_return(task_id, idx))
                pt = self.pending_by_id.get(task_id)
                if pt is not None and pt.spec.num_returns == "streaming":
                    # consumer abandoned a LIVE stream: -1 tells a
                    # backpressured producer to stop instead of polling a
                    # zero count forever
                    self._stream_consumed[task_id] = -1
                else:
                    self._stream_consumed.pop(task_id, None)
            entry = self.memory_store.get([object_id], timeout=0)[0]
            self.memory_store.delete([object_id])
            self.plasma_resident.pop(object_id, None)
        if entry is not None and entry[0] == "plasma":
            store = self._store_for_location(entry[1][0])
            store.delete(object_id)
            if getattr(store, "is_remote", False):
                with self.lock:
                    self._remote_resident[store.arena_name].discard(object_id)
        elif entry is None or entry[0] not in ("inline", "error"):
            # unknown/unsealed ids may still own an arena allocation;
            # inline/error entries never did — skipping the native
            # unpin+delete round trip here removes two ctypes calls per
            # free on the small-result hot path (measured ~15% of the 1:1
            # sync actor-call round trip under load)
            self.plasma.delete(object_id)
        if entry is not None and entry[0] == "spilled":
            with self.lock:
                agent = self._agent_spills.pop(object_id, None)
            if agent is not None:
                # the spill file lives on the agent's host
                with self.lock:
                    self._remote_resident[agent.arena_name].discard(object_id)
                try:
                    agent.send(P.FreeLocal([object_id]))
                except (OSError, EOFError):
                    pass
            else:
                try:
                    os.unlink(entry[1][0])
                except OSError:
                    pass
        # secondary copies die with the primary: a freed-then-recreated id
        # must never be served from a stale replica
        self._drop_replicas(object_id)
        self._journal("free", object_id.binary())

    # ------------------------------------------------------------- submission

    def _validate_runtime_env(self, spec: TaskSpec):
        """Reject unusable runtime envs at SUBMISSION (reference:
        RuntimeEnvSetupError surfaces on the task) — a bad py_modules path
        discovered at worker-spawn time would otherwise respawn doomed
        workers forever while the task hangs in the ready queue."""
        rt = spec.runtime_env or {}
        for key in ("container", "image_uri"):
            if rt.get(key):
                # explicit refusal, not silence: this image has no container
                # runtime (reference: runtime_env/container — out of scope)
                raise ValueError(
                    f"runtime_env {key!r} is not supported: ray_tpu has no "
                    "container runtime; use pip/uv (offline wheel cache), "
                    "py_modules, working_dir, or env_vars instead"
                )
        for mod in rt.get("py_modules") or ():
            p = os.path.abspath(os.path.expanduser(str(mod)))
            if not os.path.exists(p):
                raise ValueError(
                    f"runtime_env py_modules path does not exist on the "
                    f"cluster host: {p}"
                )
        from ray_tpu._private.runtime_env_pip import (
            normalize_pip_spec,
            validate_pip_spec,
        )

        pip_spec = normalize_pip_spec(rt)
        if pip_spec:
            validate_pip_spec(pip_spec)
            if self.mode == "thread":
                raise ValueError(
                    "runtime_env pip requires process mode (thread-mode "
                    "workers share the driver interpreter and cannot enter "
                    "a venv); ray_tpu.init(mode='process')"
                )
            # resolve ONCE at submission: the fingerprint is recomputed in
            # the scheduler hot path (shape keys, worker matching), which
            # must never re-read a requirements file or the env var — a
            # deleted/edited file would otherwise stall dispatch or strand
            # spawned workers with mismatched fingerprints. The resolved
            # spec (which carries its "tool") lives under "pip"; a raw "uv"
            # key would be re-normalized into a conflict.
            spec.runtime_env = {
                **{k: v for k, v in rt.items() if k != "uv"},
                "pip": pip_spec,
            }

    def submit_task(self, spec: TaskSpec):
        self._validate_runtime_env(spec)
        self._record_lineage(spec)
        with self.lock:
            # idempotent replay (same dedup as submit_batch): a client's
            # retry envelope re-sends this op across a head restart — the
            # spec may already be pending (replayed from the journal, or
            # resumed as a live lease on a reconciled agent) or already
            # completed; re-enqueueing would execute it twice and orphan
            # the overwritten PendingTask's bookkeeping
            rets = spec.return_ids()
            if spec.task_id in self.pending_by_id or (
                rets and self.memory_store.contains(rets[0])
            ):
                return
            self._submit_one_locked(spec)
            self.sched_cv.notify_all()
        self._journal("submit", (spec, None))
        self._persist_state()

    def _submit_replayed(self, spec: TaskSpec):
        """Recovery-path submission: dedups on PENDING only. Actor
        re-creation legitimately re-runs a creation task whose pre-crash
        RESULT is journal-sealed — the full sealed-returns dedup of
        submit_task would silently skip the respawn."""
        self._validate_runtime_env(spec)
        self._record_lineage(spec)
        with self.lock:
            if spec.task_id in self.pending_by_id:
                return
            self._submit_one_locked(spec)
            self.sched_cv.notify_all()
        self._journal("submit", (spec, None))
        self._persist_state()

    def _submit_one_locked(self, spec: TaskSpec):
        """Enqueue one validated spec (call under ``self.lock``). The caller
        owns validation/lineage (outside the lock), the scheduler wake, and
        the persist — so a coalesced batch pays ONE lock hold and ONE wake
        for N specs instead of N of each (see ``submit_batch``)."""
        deps = {a[1] for a in spec.args if a[0] == "ref"}
        pt = PendingTask(spec, deps)
        self.pending_by_id[spec.task_id] = pt
        # Pin deps for the task's lifetime.
        for d in pt.all_deps:
            self.ref_counts[d] += 1
        if spec.task_type == TaskType.ACTOR_TASK:
            self._submit_actor_task(pt)
            return
        unresolved = {d for d in pt.unresolved if not self.memory_store.contains(d)}
        pt.unresolved = unresolved
        if unresolved:
            for d in unresolved:
                self.waiting_on_deps[d].append(pt)
            # a dep may be LOST (not merely pending) — kick recovery. A
            # resubmitted producer's own chain depth carries through, so
            # transitive reconstruction counts against the depth cap.
            self._maybe_recover(
                unresolved, depth=self._recon_depth.get(spec.task_id, 0)
            )
        else:
            self._enqueue_ready(pt)

    def submit_batch(self, items: list, caller=None):
        """Apply one client-coalesced control batch in FIFO order. Items:
        ``("submit", spec, actor_name)`` | ``("add_ref", [oid, ...])`` |
        ``("free", [oid, ...])``.

        This is the head's half of the client-side submit coalescer: one
        ``Request`` carries N submissions plus the ref traffic that used to
        cost a fire-and-forget request per submit, and the whole batch is
        applied under ONE lock hold with ONE scheduler wake (the batched
        drain replacing one wake per spec).

        Replay-safe: chaos injection (``testing_rpc_failure`` /
        ``RAY_TPU_WORKER_RPC_FAILURE``) fails the request BEFORE any item
        applies, so a client retries the identical batch; specs already
        pending or completed are skipped (no double-dispatch, no lost
        spec). Per-item submission errors seal error results onto the
        spec's return ids — an async submission's failure surfaces at
        ``get()`` without poisoning the rest of the batch."""
        prepared: list = []
        failed: list = []  # (PendingTask, exception) — sealed after apply

        def _fail_item(spec, exc):
            # empty dep set: these specs never pinned args, so _fail_task
            # must not unpin anything
            failed.append((PendingTask(spec, set()), exc))

        for item in items:
            if item[0] != "submit":
                prepared.append(item)
                continue
            spec = item[1]
            try:
                self._validate_runtime_env(spec)
            except Exception as e:  # noqa: BLE001 — sealed onto the returns
                _fail_item(spec, e)
                continue
            self._record_lineage(spec)
            prepared.append(item)
        frees: list = []
        with self.lock:
            for item in prepared:
                kind = item[0]
                if kind == "add_ref":
                    for oid in item[1]:
                        self.ref_counts[oid] += 1
                elif kind == "free":
                    # applied after the lock drops: a free can cascade into
                    # store/agent I/O that must not ride the batch hold
                    frees.extend(item[1])
                elif kind == "submit":
                    spec, name = item[1], item[2]
                    rets = spec.return_ids()
                    if spec.task_id in self.pending_by_id or (
                        rets and self.memory_store.contains(rets[0])
                    ):
                        continue  # idempotent replay of an applied batch
                    if spec.task_type == TaskType.ACTOR_CREATION_TASK:
                        if spec.actor_id in self.actors:
                            continue  # replayed creation
                        if name and name in self.named_actors:
                            _fail_item(
                                spec,
                                ValueError(f"actor name {name!r} already taken"),
                            )
                            continue
                        actor = ActorState(spec.actor_id, spec)
                        actor.name = name
                        self.actors[spec.actor_id] = actor
                        if name:
                            self.named_actors[name] = spec.actor_id
                    # return-id refs fold into the batch apply: the client
                    # no longer pays a separate add_ref request per submit
                    for oid in rets:
                        self.ref_counts[oid] += 1
                    self._submit_one_locked(spec)
                    self._journal("submit", (spec, name))
                else:
                    logger.error("submit_batch: unknown item kind %r", kind)
            self.sched_cv.notify_all()
        for oid in frees:
            self.remove_ref(oid)
        for pt, exc in failed:
            with self.lock:
                for oid in pt.spec.return_ids():
                    self.ref_counts[oid] += 1
            self._fail_task(pt, exc)
        self._persist_state()

    # -------------------------------------------------- lineage reconstruction

    def _record_lineage(self, spec: TaskSpec):
        """Remember the producer spec of every retriable task's returns,
        bounded by ``max_lineage_bytes`` FIFO (reference: task_manager.h:177).
        """
        n_returns = len(spec.return_ids())  # 1 for "streaming"
        if (
            self.config.max_lineage_bytes <= 0
            or spec.max_retries == 0
            or n_returns < 1
            or spec.task_type == TaskType.ACTOR_CREATION_TASK
        ):
            return
        cost = len(spec.function_blob or b"") + 256
        for a in spec.args:
            if a[0] == "value" and isinstance(a[1], (bytes, bytearray)):
                cost += len(a[1])
        per_return = max(cost // n_returns, 1)
        with self.lock:
            for oid in spec.return_ids():
                if oid not in self.lineage:
                    self.lineage_bytes += per_return
                self.lineage[oid] = (spec, per_return)
            while self.lineage_bytes > self.config.max_lineage_bytes and self.lineage:
                _, (_, old_cost) = self.lineage.popitem(last=False)
                self.lineage_bytes -= old_cost
        # journal the producer spec (kind "lineage") so the table survives
        # a head restart: boot replays these through this same method, so
        # the byte-cap eviction above reproduces itself deterministically.
        # Suppressed during replay (the record is already on disk) and
        # compacted into the snapshot's "lineage" list.
        self._journal("lineage", spec)

    def _maybe_recover(self, object_ids, depth: int = 0):
        """Resubmit producers of LOST objects (reference:
        ``object_recovery_manager.h:43``). An object is lost when no entry
        exists AND no pending task will produce it. Recovery is recursive
        through ``submit_task``: a resubmitted producer whose own args were
        lost kicks their producers in turn (lineage chains) — at
        ``depth+1``, so a chain deeper than
        ``lineage_reconstruction_max_depth`` stops with ObjectLostError
        (counted as ``reconstruction_depth_capped``) instead of recursing
        unboundedly."""
        max_depth = self.config.lineage_reconstruction_max_depth
        to_resubmit = []
        with self.lock:
            for oid in object_ids:
                if self.memory_store.contains(oid):
                    continue
                producer = TaskID(oid.binary()[: TaskID.SIZE])
                if producer in self.pending_by_id or producer in self._recovering:
                    continue  # already in flight
                entry = self.lineage.get(oid)
                if entry is None:
                    continue  # not reconstructable (non-retriable or evicted)
                if max_depth <= 0 or depth >= max_depth:
                    self.recovery_counters["reconstruction_failures"] += 1
                    self.recovery_counters["reconstruction_depth_capped"] += 1
                    logger.warning(
                        "lineage reconstruction of %s stopped: chain depth "
                        "%d reached lineage_reconstruction_max_depth=%d",
                        oid.hex(), depth, max_depth,
                    )
                    continue
                spec = entry[0]
                if spec.is_actor_task():
                    actor = self.actors.get(spec.actor_id)
                    if actor is None or actor.state == "DEAD":
                        self.recovery_counters["reconstruction_failures"] += 1
                        continue  # producer actor gone — unrecoverable
                self._recovering.add(producer)
                self._recon_depth[producer] = depth + 1
                to_resubmit.append(spec)
        for spec in to_resubmit:
            logger.warning(
                "lineage reconstruction: resubmitting task %s for lost object(s)",
                spec.name,
            )
            try:
                self.submit_task(spec)
            except Exception:  # noqa: BLE001
                # the producer must NOT stay marked as in-flight recovery:
                # a leaked _recovering entry permanently blocks every
                # future reconstruction of this object (the waiter skips
                # "already recovering" forever)
                with self.lock:
                    self._recovering.discard(spec.task_id)
                    self._recon_depth.pop(spec.task_id, None)
                    self.recovery_counters["reconstruction_failures"] += 1
                logger.warning(
                    "lineage resubmit of %s failed", spec.name, exc_info=True
                )
            else:
                with self.lock:
                    self.recovery_counters["reconstructions"] += 1

    def _shape_key(self, spec: TaskSpec) -> tuple:
        """Queue/lease key. The TENANT leads the tuple so lease pipelining
        and work stealing (keyed on whole shapes) never mix tenants — a
        saturated tenant cannot ride another tenant's leased workers past
        the fair-share pop. The env fingerprint stays LAST (steal-matching
        reads shape[-1])."""
        s = spec.strategy
        return (
            self._tenant_for(spec),
            tuple(sorted(spec.resources.items())),
            s.kind,
            getattr(s, "node_id", None),
            getattr(s, "placement_group_id", None),
            getattr(s, "bundle_index", -1),
            self._env_fingerprint(spec),
        )

    # ------------------------------------------------------------- tenants

    @staticmethod
    def _tenant_for(spec: TaskSpec) -> str:
        """The tenant a spec bills to (the submitting API always stamps
        one; internal/legacy specs fall back to the shared default)."""
        return getattr(spec, "tenant", None) or tenants_mod.DEFAULT_TENANT

    def _tenant_state(self, name: str) -> "tenants_mod.TenantState":
        """Get-or-create a tenant's scheduling state (call under lock)."""
        ts = self.tenants.get(name)
        if ts is None:
            ts = self.tenants[name] = tenants_mod.TenantState(name)
            self._tenant_ring.append(name)
        return ts

    def _effective_priority(self, spec: TaskSpec) -> int:
        """Per-spec priority, falling back to the tenant's configured
        default tier."""
        p = getattr(spec, "priority", None)
        if p is not None:
            return int(p)
        ts = self.tenants.get(self._tenant_for(spec))
        return ts.priority if ts is not None else 0

    def _tenant_charge(self, tenant: str, demand: dict) -> None:
        """Mirror of a node/bundle debit made for this tenant's work (call
        under lock, exactly where the node charge happens)."""
        self._tenant_state(tenant).charge(demand)

    def _tenant_credit(self, tenant: str, demand: dict) -> None:
        ts = self.tenants.get(tenant)
        if ts is not None:
            ts.credit(demand)

    @staticmethod
    def _tenant_contending(
        ts: "tenants_mod.TenantState", against: dict
    ) -> bool:
        """Delegates to ``TenantState.contending_for`` — the shared
        fairness gate of pipelining and the lease-cache re-arm."""
        return ts.contending_for(against)

    def set_tenant_quota(
        self,
        tenant: str,
        quota: Optional[dict] = None,
        weight: Optional[float] = None,
        priority: Optional[int] = None,
    ) -> dict:
        """Configure a tenant's arbitration policy (the ``set_tenant_quota``
        op): resource caps, fair-share weight, default priority tier.
        ``quota=None`` leaves the current quota, ``{}`` clears it. Raising a
        quota wakes the scheduler so parked work resumes immediately."""
        with self.lock:
            ts = self._tenant_state(tenant)
            if quota is not None:
                ts.quota = (
                    {k: float(v) for k, v in quota.items()} if quota else None
                )
            if weight is not None:
                ts.weight = max(float(weight), tenants_mod.MIN_WEIGHT)
            if priority is not None:
                ts.priority = int(priority)
            ts.configured = True
            snap = ts.snapshot()
            self.sched_cv.notify_all()
            self._journal(
                "tenant",
                {
                    "name": ts.name,
                    "weight": ts.weight,
                    "priority": ts.priority,
                    "quota": dict(ts.quota) if ts.quota else None,
                },
            )
        self._persist_state()
        return snap

    def tenant_stats(self) -> list[dict]:
        """Per-tenant shares/quota/usage/queue-depth/preemption counters
        (the ``tenant_stats`` op), plus which tenant drives each pending
        autoscale demand shape."""
        now = time.time()
        with self.lock:
            rows = [ts.snapshot() for ts in self.tenants.values()]
            for row in rows:
                row["pending_demand"] = [
                    dict(shape)
                    for (t, shape), at in self.pending_demand.items()
                    if t == row["tenant"] and now - at < 60
                ]
        return rows

    def _enqueue_ready(self, pt: PendingTask):
        pt.seq = next(self._enqueue_seq)
        shape = self._shape_key(pt.spec)
        ts = self._tenant_state(shape[0])
        q = ts.queues.get(shape)
        if q is None:
            q = ts.queues[shape] = deque()
        q.append(pt)

    def _iter_ready(self):
        for ts in self.tenants.values():
            for q in ts.queues.values():
                yield from q

    def _submit_actor_task(self, pt: PendingTask):
        actor = self.actors.get(pt.spec.actor_id)
        if actor is None or actor.state == "DEAD":
            reason = actor.death_cause if actor else "actor not found"
            self._fail_task(pt, ActorDiedError(pt.spec.actor_id.hex(), reason or "actor died"))
            return
        actor.queue.append(pt)
        self._pump_actor(actor)

    def _pump_actor(self, actor: ActorState):
        """Dispatch queued actor calls respecting max_concurrency + ordering."""
        if actor.state != "ALIVE" or actor.worker is None:
            return
        if getattr(actor, "_drain_hold", False):
            # node drain is retiring this worker: queued calls wait for the
            # migrated incarnation (released in _on_actor_worker_death)
            return
        maxc = actor.creation_spec.max_concurrency
        while actor.queue and actor.inflight < maxc:
            if actor.state != "ALIVE" or actor.worker is None:
                # the dispatch below can kill the worker REENTRANTLY
                # (send failure → _on_worker_death under this same RLock
                # nulls actor.worker and requeues); without this re-check
                # the next iteration dispatches into None, strands
                # inflight at 1, and wedges a maxc=1 actor forever
                return
            pt = actor.queue[0]
            unresolved = {d for d in pt.unresolved if not self.memory_store.contains(d)}
            if unresolved:
                # Keep ordering: wait for the head-of-line task's deps.
                pt.unresolved = unresolved
                for d in unresolved:
                    if pt not in self.waiting_on_deps[d]:
                        self.waiting_on_deps[d].append(pt)
                break
            actor.queue.popleft()
            actor.inflight += 1
            self._dispatch_to_worker(actor.worker, pt)

    # ------------------------------------------------------------- scheduling

    def _schedule_loop(self):
        while True:
            with self.sched_cv:
                if self.shutting_down:
                    return
                try:
                    progressed = self._try_dispatch_locked()
                    # Retry placement of pending placement groups whenever
                    # the cluster state may have changed (resources freed,
                    # nodes joined) — reference: GcsPlacementGroupMgr retries.
                    # Gated while RECOVERING (like dispatch): bundles must
                    # not reserve capacity that reconciling leases will
                    # re-claim.
                    if not self.recovering:
                        for pg in self.placement_groups.values():
                            if not pg.removed and not pg.ready.is_set():
                                if self._try_place_pg(pg):
                                    progressed = True
                        # Priority preemption: a higher-priority tenant
                        # starved past the bounded wait drains
                        # lower-priority restartable actors (checked every
                        # round — other tenants progressing must not mask
                        # the starvation).
                        self._maybe_preempt_locked()
                    # one LeaseBatch push per agent carrying every grant
                    # this round made (batched wire ops, PR 12)
                    self._flush_lease_outbox_locked()
                except Exception:
                    # The scheduler thread must never die; a scheduling bug on
                    # one task must not freeze the cluster.
                    logger.error("scheduler iteration failed:\n%s", traceback.format_exc())
                    progressed = False
                if not progressed:
                    # Nothing dispatchable: pipelined work may be stuck
                    # behind a blocked task — rebalance before sleeping.
                    self._maybe_steal_locked()
                    # Sleep until a task is submitted, a worker frees
                    # up/registers, or a node joins.
                    self.sched_cv.wait(timeout=0.5)

    def _try_dispatch_locked(self) -> bool:
        """One scheduling round over the per-tenant queue groups.

        WITHIN a tenant, tasks with the same (resources, strategy, env)
        shape are scheduled FIFO from one queue, and the tenant's head is
        the oldest seq across its unblocked shapes — exactly the global
        FIFO the single table had, scoped per tenant (nested submits still
        interleave by arrival). A head that cannot place blocks ONLY its
        (tenant, shape) for this round, so a round stays O(shapes +
        dispatched), not O(queued).

        ACROSS tenants, a strict priority tier then a weighted
        deficit-round-robin pop picks whose head goes next: only tenants
        whose head sits in the highest priority tier compete; each DRR
        visit tops a tenant's deficit up by its weight and each dispatch
        costs 1.0, so steady-state dispatch shares converge to the
        configured weights (reference shape: scheduling-class queues of
        ``cluster_task_manager.h:44`` + the job manager's per-job
        arbitration, PAPER.md L5). Over-QUOTA heads park (blocked without
        an autoscale hint or starvation clock); heads that fail placement
        start the starvation clock priority preemption reads."""
        if self.recovering:
            # RECOVERING gate: nothing dispatches until every journaled
            # agent reconciled (or the grace deadline lapsed) — dispatching
            # a parked-but-unconfirmed lease would execute it twice
            return False
        progressed = False
        blocked: set = set()  # (tenant, shape) held out for this round
        while True:
            picked = self._drr_next_locked(blocked)
            if picked is None:
                break
            ts, shape, pt = picked
            q = ts.queues[shape]
            if pt.spec.task_type == TaskType.ACTOR_TASK:
                q.popleft()
                ts.reap_queue(shape)
                actor = self.actors.get(pt.spec.actor_id)
                if actor is not None:
                    actor.queue.appendleft(pt)
                    self._pump_actor(actor)
                progressed = True
                continue
            if ts.over_quota(pt.spec.resources):
                # park at grant: stays queued, resumes on usage drop /
                # quota raise; deliberately NO autoscale hint (a capped
                # tenant must not grow the cluster) and NO starvation
                # clock (being over your own cap is not starvation — a
                # clock started when the head merely lacked capacity is
                # cleared too, or preemption would drain victims for a
                # head its own quota blocks)
                if not getattr(pt, "_park_counted", False):
                    # count TASKS that parked, not scheduler wakeups
                    pt._park_counted = True  # type: ignore[attr-defined]
                    ts.stats["quota_parked"] += 1
                if ts.starved_head is pt:
                    # only the clock THIS head started — an older head of
                    # another shape may be genuinely capacity-starved,
                    # and its preemption claim must survive a sibling
                    # shape parking behind the tenant's own cap
                    ts.starved_since = None
                    ts.starved_head = None
                blocked.add((ts.name, shape))
                continue
            if self._try_place(pt):
                q.popleft()
                ts.reap_queue(shape)
                ts.deficit -= tenants_mod.TASK_COST
                # count each TASK once: a steal/retry re-enqueue re-pops
                # the same task, and share accounting (tenant_stats, the
                # fairness bench) must not read re-dispatch churn as
                # throughput
                if not getattr(pt, "_drr_counted", False):
                    pt._drr_counted = True  # type: ignore[attr-defined]
                    ts.stats["dispatched"] += 1
                if ts.starved_head is pt:
                    # only the head that STARTED the clock clears it — a
                    # sibling CPU shape dispatching every round must not
                    # keep resetting a TPU head's preemption claim
                    ts.starved_since = None
                    ts.starved_head = None
                progressed = True
            else:
                blocked.add((ts.name, shape))
                if ts.starved_since is None:
                    # clock and head bind together: a LATER failing
                    # sibling must not retarget the elapsed clock at its
                    # own (different) demand
                    ts.starved_since = time.monotonic()
                    ts.starved_head = pt
        if progressed and self._ttfd_pending:
            # first real dispatch after a restart's restore: the
            # recovery bench / recovery_stats read this
            self._ttfd_pending = False
            self.recovery_info["time_to_first_dispatch_s"] = (
                time.monotonic() - self._boot_t
            )
        return progressed

    def _drr_next_locked(self, blocked: set):
        """Pick the next (tenant, shape, head task) to try, or None.

        1. Per tenant: oldest-seq head across unblocked shapes (cancelled
           heads reaped, emptied shape keys deleted; a tenant with no
           queued work at all forfeits its banked deficit — classic DRR).
        2. Priority tier: only tenants whose head has the maximum
           effective priority stay eligible.
        3. Weighted DRR over the eligible set: rotate the tenant ring,
           topping up ``deficit += weight`` per visit, until a tenant can
           afford one task. Bounded: a full eligible pass adds at least
           MIN_WEIGHT everywhere, so at most ~1/MIN_WEIGHT passes."""
        heads: dict[str, tuple] = {}  # name -> (seq, shape, pt)
        reapable: list[str] = []
        for name, ts in self.tenants.items():
            best = None
            for shape in list(ts.queues):
                if (name, shape) in blocked:
                    continue
                q = ts.queues[shape]
                while q and q[0].cancelled:
                    q.popleft()
                if not q:
                    del ts.queues[shape]
                    continue
                if best is None or q[0].seq < best[0]:
                    best = (q[0].seq, shape, q[0])
            if best is not None:
                heads[name] = best
            elif not ts.queues:
                ts.deficit = 0.0  # empty tenant banks no credit
                if not ts.usage and not ts.configured:
                    # auto-created (per-driver/per-job) tenant gone idle:
                    # nothing queued, nothing charged, no policy to keep —
                    # reap it, or a long-lived head's scheduler rounds
                    # degrade O(total tenants ever seen) and the registry
                    # leaks one entry per job forever. Resubmission
                    # recreates it on demand (stats restart from zero);
                    # configured tenants always persist.
                    reapable.append(name)
        for name in reapable:
            del self.tenants[name]
            try:
                self._tenant_ring.remove(name)
            except ValueError:
                pass
        if not heads:
            return None
        top = max(
            self._effective_priority(h[2].spec) for h in heads.values()
        )
        eligible = {
            n
            for n, h in heads.items()
            if self._effective_priority(h[2].spec) == top
        }
        ring = self._tenant_ring
        # prune ring entries whose tenant vanished (defensive; tenants are
        # currently never deleted) and bound the top-up spin
        max_spins = len(ring) * (int(1.0 / tenants_mod.MIN_WEIGHT) + 2)
        for _ in range(max(max_spins, 1)):
            name = ring[0]
            if name not in self.tenants:
                ring.popleft()
                if not ring:
                    return None
                continue
            if name not in eligible:
                ring.rotate(-1)
                continue
            ts = self.tenants[name]
            if ts.deficit >= tenants_mod.TASK_COST:
                seq, shape, pt = heads[name]
                return ts, shape, pt
            ts.deficit += ts.weight
            ring.rotate(-1)
        # unreachable with MIN_WEIGHT-clamped weights; fail open to FIFO
        name = min(eligible, key=lambda n: heads[n][0])
        ts = self.tenants[name]
        return ts, heads[name][1], heads[name][2]

    def _pick_node(self, pt: PendingTask) -> Optional[NodeState]:
        """Scheduling policies (reference: ``raylet/scheduling/policy/``)."""
        spec = pt.spec
        strat = spec.strategy
        demand = dict(spec.resources)
        # draining nodes accept no new work (they are finishing what they
        # have; reference: DrainRaylet rejects new leases)
        alive = [n for n in self.nodes.values() if n.schedulable]

        if strat.kind == "placement_group":
            pg = self.placement_groups.get(strat.placement_group_id)
            if pg is None or pg.removed:
                return None
            indices = (
                [strat.bundle_index]
                if strat.bundle_index >= 0
                else range(len(pg.bundles))
            )
            for i in indices:
                nid = pg.bundle_nodes[i]
                if nid is None:
                    continue
                avail = pg.bundle_available[i]
                if all(avail.get(k, 0.0) + 1e-9 >= v for k, v in demand.items()):
                    node = self.nodes.get(nid)
                    # a DRAINING node takes no new work, bundle or not —
                    # the task waits (it would be killed mid-run at release
                    # otherwise, the exact loss the drain protocol prevents)
                    if node is not None and node.schedulable:
                        pt._pg_bundle = (pg, i)  # type: ignore[attr-defined]
                        return node
            return None

        if strat.kind == "node_affinity":
            node = self.nodes.get(strat.node_id)
            if node is not None and node.schedulable and node.fits(demand):
                return node
            if strat.soft:
                pass  # fall through to default policy
            else:
                return None

        candidates = [n for n in alive if n.fits(demand)]
        if not candidates:
            return None
        avoid = getattr(pt, "_avoid_node", None)
        if avoid is not None:
            # one-shot spillback hint: prefer any other node, but a saturated
            # single-node cluster may still retry the spiller
            pt._avoid_node = None  # type: ignore[attr-defined]
            others = [n for n in candidates if n.node_id != avoid]
            if others:
                candidates = others
        if strat.kind == "spread":
            # Round-robin by lowest utilization (reference: spread policy).
            return min(candidates, key=lambda n: n.utilization())
        # Hybrid policy: prefer head/local node below the spread threshold,
        # else least-utilized (reference: hybrid_scheduling_policy.h:50).
        head = self.nodes.get(self.head_node_id)
        if (
            head is not None
            and head.schedulable
            and head.fits(demand)
            and head.utilization() < self.config.scheduler_spread_threshold
        ):
            return head
        return min(candidates, key=lambda n: n.utilization())

    def _leasable(self, spec: TaskSpec) -> bool:
        """Normal tasks without shipped packages or streaming returns go to
        the agent's local dispatcher; the rest use head-managed workers."""
        if spec.task_type != TaskType.NORMAL_TASK or spec.num_returns == "streaming":
            return False
        rt = spec.runtime_env or {}
        # pip rides the package-shipping SpawnWorker path (the wheel cache
        # must travel to the agent host), so it is head-managed like
        # working_dir/py_modules
        return (
            not rt.get("working_dir")
            and not rt.get("py_modules")
            and not rt.get("pip")
        )

    def _lease_backlog_cap(self, node: NodeState) -> int:
        """Max outstanding leases per node — matches the agent's own spill
        threshold so zero-demand floods queue HERE instead of ping-ponging
        lease→overload-spill→re-lease over the wire."""
        return max(4 * (int(node.total.get("CPU", 0)) + 4), 64)

    def _lease_to_agent(self, node: NodeState, pt: PendingTask) -> bool:
        """First-level placement decided: hand the task to the node's agent
        (LocalTaskManager analog) and charge the node. The agent reports
        AgentTaskDone or spills the task back."""
        if len(node.leased) >= self._lease_backlog_cap(node):
            return False
        spec = pt.spec
        resolved_args, _lost = self._resolve_args(pt)
        if resolved_args is None:
            from ray_tpu.exceptions import ObjectLostError

            self._fail_task(pt, ObjectLostError(_lost.hex()))
            return True  # consumed (failed), not requeued
        demand = spec.resources
        pg_bundle = getattr(pt, "_pg_bundle", None)
        # queued, not sent: the scheduling round's grants for this agent
        # coalesce into one LeaseBatch push at round end (flush failure
        # requeues the lease — see _flush_lease_outbox_locked)
        # driver config overrides ride the lease's env_vars (the agent's
        # pool workers rebuild Config.from_env() from them, same as
        # _spawn_worker_process's exports); explicit runtime_env vars win
        lease_env = dict(self._child_env_overrides)
        lease_env.update((spec.runtime_env or {}).get("env_vars") or {})
        self._queue_lease_locked(
            node,
            P.LeaseTask(
                spec,
                resolved_args,
                chips_requested(spec.resources),
                lease_env,
            ),
        )
        if pg_bundle is not None:
            pg, i = pg_bundle
            for k, v in demand.items():
                pg.bundle_available[i][k] = pg.bundle_available[i].get(k, 0.0) - v
        else:
            node.allocate(demand)
            pt._node = node  # type: ignore[attr-defined]
        tenant = self._tenant_for(spec)
        self._tenant_charge(tenant, demand)
        node.leased[spec.task_id.binary()] = pt
        self._journal("lease", (spec.task_id.binary(), node.node_id.hex()))
        pt.dispatch_t = time.time()
        self.pending_demand.pop(
            (tenant, tuple(sorted(demand.items()))), None
        )
        self.task_events.append(
            {"task_id": spec.task_id.hex(), "name": spec.name,
             "event": "LEASED", "node": node.node_id.hex(), "t": pt.dispatch_t,
             "trace_id": getattr(spec, "trace_id", None),
             "parent_span_id": getattr(spec, "parent_span_id", None),
             "submit_t": pt.submit_t}
        )
        # the lease message in the outbox carries this spec by reference:
        # stamping here lands on the wire at the round's batch flush
        self._record_sched_span(pt, "LEASED", node.node_id.hex()[:12])
        return True

    def _lease_actor_to_agent(self, node: NodeState, pt: PendingTask) -> bool:
        """Grant a CREATION LEASE for this actor to the node's agent
        (reference: GcsActorScheduler::Schedule leasing creation to the
        raylet, ``gcs_actor_scheduler.cc:55``). Resources are charged at
        grant — exactly as for task leases — and held until the agent
        reports ``actor_placed`` (charge transfers to ``actor.held``) or
        ``actor_creation_failed`` / node death (charge released). The agent
        owns the whole local lifecycle: pool pop or fresh spawn,
        runtime-env staging, creation dispatch, registration handshake."""
        spec = pt.spec
        try:
            self._maybe_inject_rpc_failure("lease_actor")
        except WorkerCrashedError:
            # chaos: the grant is "lost" before it reaches the wire — the
            # task stays queued and the next scheduling round retries
            # (no double-spawn: the agent never saw this grant)
            self.actor_creation_stats["lease_grant_injected_failures"] += 1
            return False
        resolved_args, _lost = self._resolve_args(pt)
        if resolved_args is None:
            self._fail_task(pt, ObjectLostError(_lost.hex()))
            return True  # consumed (failed), not requeued
        rt = spec.runtime_env or {}
        packages, extra_env = self._runtime_packages(rt)
        # env_vars ship RAW (str-coerced only at spawn, like LeaseTask):
        # the agent's warm pool is keyed on (tpu, env_vars) and task leases
        # ship raw values — coercing here would make every non-str value
        # miss the pool and silently defeat the warm pop path. Driver
        # config overrides ride underneath (explicit vars win), so the
        # actor's worker sees the same resolved table as head-local spawns.
        env_vars = dict(self._child_env_overrides)
        env_vars.update(rt.get("env_vars") or {})
        env_vars.update(extra_env)
        # queued, not sent: coalesced into the round's LeaseBatch for this
        # agent (flush failure requeues — the creation lease protocol is
        # already idempotent end-to-end)
        self._queue_lease_locked(
            node,
            P.LeaseActor(
                spec,
                resolved_args,
                chips_requested(spec.resources),
                env_vars,
                self._env_fingerprint(spec),
                packages,
            ),
        )
        demand = spec.resources
        pg_bundle = getattr(pt, "_pg_bundle", None)
        if pg_bundle is not None:
            pg, i = pg_bundle
            for k, v in demand.items():
                pg.bundle_available[i][k] = pg.bundle_available[i].get(k, 0.0) - v
        else:
            node.allocate(demand)
            pt._node = node  # type: ignore[attr-defined]
        tenant = self._tenant_for(spec)
        self._tenant_charge(tenant, demand)
        node.actor_leases[spec.task_id.binary()] = pt
        self._journal("alease", (spec.task_id.binary(), node.node_id.hex()))
        pt.dispatch_t = time.time()
        self.pending_demand.pop(
            (tenant, tuple(sorted(demand.items()))), None
        )
        self.actor_creation_stats["leases_granted"] += 1
        self.task_events.append(
            {"task_id": spec.task_id.hex(), "name": spec.name,
             "event": "ACTOR_LEASED", "node": node.node_id.hex(),
             "t": pt.dispatch_t,
             "trace_id": getattr(spec, "trace_id", None),
             "parent_span_id": getattr(spec, "parent_span_id", None),
             "submit_t": pt.submit_t}
        )
        self._record_sched_span(pt, "ACTOR_LEASED", node.node_id.hex()[:12])
        return True

    def _queue_lease_locked(self, node: NodeState, msg) -> None:
        """Buffer one lease grant for the node's agent (call under
        self.lock); the scheduling round flushes one LeaseBatch per agent."""
        entry = self._lease_outbox.get(node.node_id)
        if entry is None:
            entry = self._lease_outbox[node.node_id] = (node.agent, [])
        entry[1].append(msg)

    def _flush_lease_outbox_locked(self) -> None:
        """Push every buffered grant, ONE frame per agent (call under
        self.lock). A failed push — dead connection, or injected
        "lease_batch" chaos dropping the whole batch before the wire —
        requeues every lease it carried: the grants are idempotent leases,
        so a later round re-grants with no double-spawn (the agent never
        saw the lost batch)."""
        if not self._lease_outbox:
            return
        outbox, self._lease_outbox = self._lease_outbox, {}
        for nid, (agent, msgs) in outbox.items():
            try:
                if len(msgs) == 1:
                    agent.send(msgs[0])
                else:
                    self._maybe_inject_rpc_failure("lease_batch")
                    agent.send(P.LeaseBatch(msgs))
                    self.lease_stats["lease_batches"] += 1
                    self.lease_stats["leases_batched"] += len(msgs)
            except (OSError, EOFError, WorkerCrashedError) as e:
                if isinstance(e, WorkerCrashedError):
                    self.lease_stats["lease_batch_injected_failures"] += 1
                self._requeue_unsent_leases_locked(nid, msgs)

    def _requeue_unsent_leases_locked(self, nid: NodeID, msgs: list) -> None:
        """A lease batch never reached its agent: uncharge and requeue every
        lease still tracked against the node (node removal may already have
        re-placed them — only requeue what is still ours)."""
        node = self.nodes.get(nid)
        if node is None:
            return  # remove_node already re-placed this node's leases
        for msg in msgs:
            tid_b = msg.spec.task_id.binary()
            table = (
                node.actor_leases
                if isinstance(msg, P.LeaseActor)
                else node.leased
            )
            pt = table.pop(tid_b, None)
            if pt is None:
                continue  # killed/reclaimed meanwhile
            self._journal("unlease", tid_b)
            self._release_task_resources(pt)
            self._enqueue_ready(pt)
        self.sched_cv.notify_all()

    def _maybe_rearm_locked(self, node: Optional[NodeState], agent, spec) -> None:
        """Agent lease caching: a node that just completed a lease for
        shape S may immediately re-arm on the next queued spec of the same
        (tenant, shape), cutting the scheduler-wake grant round trip off
        the steady-state hot path. The head still arbitrates: a re-arm is
        REFUSED like an over-quota grant when the tenant is over its cap,
        and yielded entirely when any OTHER tenant has queued work (the DRR
        pop must arbitrate — the same fairness yield _try_pipeline makes),
        so quotas and weighted shares hold exactly as without the cache."""
        if not self.config.agent_lease_cache or self.recovering:
            return
        if node is None or not node.schedulable or node.agent is not agent:
            return
        shape = self._shape_key(spec)
        ts = self.tenants.get(shape[0])
        if ts is None:
            return
        q = ts.queues.get(shape)
        if q:
            # reap cancelled heads exactly like the DRR pop — the fast
            # path must never dispatch (and execute) a cancelled task
            while q and q[0].cancelled:
                q.popleft()
            ts.reap_queue(shape)
            q = ts.queues.get(shape)
        if not q:
            return  # no same-shape follower queued: nothing to cache
        held = dict(shape[1])
        for other_name, other_ts in self.tenants.items():
            if other_name != ts.name and other_ts.contending_for(held):
                # same fairness yield the pipelining fast path makes: a
                # re-arm bypasses the DRR pop, so a contending tenant's
                # claim wins and this grant goes back through the scheduler
                self.lease_stats["rearm_refused_fairness"] += 1
                return
        pt = q[0]
        if (
            pt.spec.task_type != TaskType.NORMAL_TASK
            or not self._leasable(pt.spec)
        ):
            return  # only plain task leases ride the cache
        if ts.over_quota(pt.spec.resources):
            self.lease_stats["rearm_refused_quota"] += 1
            return
        if len(node.leased) >= self._lease_backlog_cap(node):
            return
        if self._lease_to_agent(node, pt):
            q.popleft()
            ts.reap_queue(shape)
            ts.deficit -= tenants_mod.TASK_COST
            if not getattr(pt, "_drr_counted", False):
                pt._drr_counted = True  # type: ignore[attr-defined]
                ts.stats["dispatched"] += 1
            if ts.starved_head is pt:
                # dispatched: the preemption claim this head started must
                # die with it (mirrors the DRR dispatch path) — else the
                # stale clock drain-preempts victims for satisfied demand
                ts.starved_since = None
                ts.starved_head = None
            self.lease_stats["rearm_grants"] += 1

    def _try_place(self, pt: PendingTask) -> bool:
        spec = pt.spec
        node = self._pick_node(pt)
        if node is not None:
            if (
                node.agent is not None
                and spec.task_type == TaskType.ACTOR_CREATION_TASK
            ):
                # agent-node actor creation is ALWAYS a lease: the head
                # never spawns a worker or runs a registration handshake
                # for it (send-failure leaves the task queued for the next
                # round — no fallback to head-managed dispatch)
                return self._lease_actor_to_agent(node, pt)
            if node.agent is not None and self._leasable(spec):
                # terminal: backlog-full/send-failure leaves the task queued
                # for the next round (no fallback to head-managed dispatch —
                # the agent owns this node's normal-task workers)
                return self._lease_to_agent(node, pt)
            worker = self._acquire_worker(node, pt)
            if worker is not None:
                demand = spec.resources
                pg_bundle = getattr(pt, "_pg_bundle", None)
                if pg_bundle is not None:
                    # bundle resources were debited from the node when the
                    # placement group committed; charging the node again
                    # would double-count
                    pg, i = pg_bundle
                    for k, v in demand.items():
                        pg.bundle_available[i][k] = pg.bundle_available[i].get(k, 0.0) - v
                else:
                    node.allocate(demand)
                tenant = self._tenant_for(spec)
                self._tenant_charge(tenant, demand)
                # demand satisfied: stop advertising this shape to the
                # autoscaler (otherwise a scaled-down group relaunches for
                # stale demand)
                self.pending_demand.pop(
                    (tenant, tuple(sorted(demand.items()))), None
                )
                if spec.task_type == TaskType.NORMAL_TASK:
                    # the LEASE holds the charge; the task carries none, so
                    # same-shape followers can pipeline behind it
                    worker.lease = (self._shape_key(spec), node, pg_bundle, dict(demand))
                    self.lease_index[worker.lease[0]].add(worker)
                    pt._pg_bundle = None  # type: ignore[attr-defined]
                else:
                    # actor creation: per-task charge, held for the actor's
                    # lifetime via actor.held
                    pt._node = node  # type: ignore[attr-defined]
                self._dispatch_to_worker(worker, pt)
                return True
            # no worker free (spawn in flight / pool capped): fall through
            # to pipelining instead of blocking the shape
        else:
            self._maybe_autoscale_hint(pt)
        if spec.task_type == TaskType.NORMAL_TASK:
            return self._try_pipeline(pt)
        return False

    def _try_pipeline(self, pt: PendingTask) -> bool:
        """Dispatch onto the least-loaded leased worker already running this
        shape (FIFO on the worker's task pool), bounded by
        ``max_tasks_in_flight_per_worker``."""
        depth = self.config.max_tasks_in_flight_per_worker
        if depth <= 1:
            return False
        shape = self._shape_key(pt.spec)
        # Cross-tenant fairness gate: a pipelined dispatch rides the
        # worker's EXISTING lease, so it bypasses capacity acquisition —
        # alone, that is pure throughput (the lease rotates as soon as the
        # queue drains), but under cross-tenant contention it would let
        # one tenant hold its slots for whole queue lifetimes and the DRR
        # pop would arbitrate nothing. With any OTHER tenant CONTENDING
        # for the resources this lease holds, every dispatch must win
        # capacity the weighted way — a tenant parked behind its own
        # quota, or backlogged on disjoint resources (a TPU queue cannot
        # use CPU slots), contends for nothing here and must not cost
        # everyone else the pipeline path.
        held = dict(shape[1])
        for name, ts in self.tenants.items():
            if name != shape[0] and self._tenant_contending(ts, held):
                return False
        cands = self.lease_index.get(shape)
        if not cands:
            return False
        best, best_n = None, depth
        for w in cands:
            if w.dead:
                continue
            wnode = self.nodes.get(w.node_id)
            if wnode is not None and not wnode.schedulable:
                continue  # draining nodes take no new work
            n = len(w.running)
            if n < best_n:
                best, best_n = w, n
        if best is None:
            return False
        # the LEASE on `best` holds the node/bundle charge; this task must
        # not carry one (a bundle hint left by _pick_node would be credited
        # on completion without ever being debited)
        pt._pg_bundle = None  # type: ignore[attr-defined]
        self._dispatch_to_worker(best, pt)
        return True

    def _maybe_steal_locked(self):
        """Rebalance pipelined dispatches (call under self.lock). For every
        shape whose ready queue is empty but whose leased workers still hold
        queued tasks behind a (possibly blocked) head task: move queued tasks
        to an idle same-env worker, or grow the pool if none exists — without
        this, two interdependent tasks pipelined onto one worker deadlock
        (reference: work stealing alongside the in-flight task pipeline)."""
        if self.config.max_tasks_in_flight_per_worker <= 1:
            return
        for shape, workers in list(self.lease_index.items()):
            owner = self.tenants.get(shape[0])  # shape[0] is the tenant
            if owner is not None and owner.queues.get(shape):
                continue  # undispatched work exists; idle workers take that
            victim = None
            for w in workers:
                if not w.dead and len(w.running) > 1 and not w.steal_pending:
                    if victim is None or len(w.running) > len(victim.running):
                        victim = w
            if victim is None:
                continue
            env_fp = shape[-1]
            thief = None
            for nid, idle in self.idle_workers.items():
                inode = self.nodes.get(nid)
                if inode is not None and not inode.schedulable:
                    continue  # never steal work ONTO a draining node
                for w in idle:
                    if not w.dead and w.fingerprint == env_fp:
                        thief = w
                        break
                if thief is not None:
                    break
            if thief is None:
                # nowhere to move the work: grow the pool; the steal fires
                # once the new worker registers idle (growth is allowed
                # because a blocked pipeline stops completing tasks)
                node = self.nodes.get(victim.node_id)
                sample = next(iter(victim.running.values()), None)
                if node is not None and node.schedulable and sample is not None:
                    self._acquire_worker(node, sample)
                continue
            victim.steal_pending = True
            try:
                victim.send(P.StealTasks(len(victim.running) - 1))
            except (OSError, EOFError):
                victim.steal_pending = False

    # -------------------------------------------------- priority preemption

    def _maybe_preempt_locked(self):
        """Serve starved higher-priority tenants by drain-migrating
        lower-priority restartable actors (call under self.lock).

        A tenant is STARVED when its queue head has failed placement
        continuously for ``Config.preemption_wait_s`` (the clock starts in
        _try_dispatch_locked; quota-parked heads never start it — being at
        your own cap is not starvation). Preemption is the node-drain
        migration, not a kill: the victim's in-flight calls finish, its
        queued calls hold and replay on the migrated incarnation, the
        restart budget is NOT charged, and the victim re-places through
        the normal (lease) path — behind the higher-priority work, queued,
        never failed. Non-restartable actors, bundle-held actors, and
        anything at or above the starved priority are never victims."""
        wait = self.config.preemption_wait_s
        if wait <= 0 or not self.tenants:
            return
        now = time.monotonic()
        # snapshot: charging a victim's (possibly reaped) tenant below
        # inserts into self.tenants — mutating mid-iteration raises
        for ts in list(self.tenants.values()):
            if ts.starved_since is None or now - ts.starved_since < wait:
                continue
            pt = ts.starved_head
            if (
                pt is None
                or pt.cancelled
                or pt.spec.task_id not in self.pending_by_id
            ):
                # head was cancelled/failed out of band: not starvation
                ts.starved_since = None
                ts.starved_head = None
                continue
            spec = pt.spec
            if spec.strategy.kind == "placement_group":
                continue  # bundle demand is the PG's to serve, not ours
            if ts.over_quota(spec.resources):
                # the head is blocked by its OWN cap (usage changed since
                # the clock started): draining victims cannot help it
                ts.starved_since = None
                ts.starved_head = None
                continue
            if any(
                getattr(a, "_preempting", False)
                and getattr(a, "_preempt_for", None) == ts.name
                for a in self.actors.values()
            ):
                # a victim set for this tenant is still draining: its
                # capacity has not freed yet — selecting MORE victims
                # every wait interval would over-preempt across the
                # cluster for one starved head
                ts.starved_since = now
                continue
            prio = self._effective_priority(spec)
            victims = self._select_preemption_victims(spec, prio)
            if not victims:
                continue
            ts.starved_since = now  # clock restarts while victims drain
            ts.stats["preemptions"] += len(victims)
            for actor in victims:
                actor._preempting = True  # noqa: SLF001
                actor._preempt_for = ts.name  # noqa: SLF001
                vts = self._tenant_state(
                    self._tenant_for(actor.creation_spec)
                )
                vts.stats["preempted"] += 1
                self.task_events.append(
                    {"task_id": actor.creation_spec.task_id.hex(),
                     "name": actor.creation_spec.name, "event": "PREEMPTED",
                     "for_tenant": ts.name, "t": time.time()}
                )
                logger.info(
                    "preempting actor %s (tenant %s, prio %d) for starved "
                    "tenant %s (prio %d)",
                    actor.actor_id.hex()[:8],
                    self._tenant_for(actor.creation_spec),
                    self._effective_priority(actor.creation_spec),
                    ts.name, prio,
                )
                threading.Thread(
                    target=self._preempt_actor, args=(actor,), daemon=True,
                    name=f"preempt-{actor.actor_id.hex()[:8]}",
                ).start()

    def _select_preemption_victims(self, spec: TaskSpec, prio: int) -> list:
        """The smallest set of strictly-lower-priority restartable actors
        on ONE schedulable node whose release lets ``spec`` fit there
        (call under self.lock). Bundle-held actors are exempt — their
        reservation belongs to the placement group, which preemption never
        revokes."""
        demand = spec.resources
        strat = spec.strategy

        def fits(avail):
            return all(
                avail.get(k, 0.0) + 1e-9 >= v for k, v in demand.items()
            )

        by_node: dict[NodeID, list] = defaultdict(list)
        for actor in self.actors.values():
            if (
                actor.state != "ALIVE"
                or actor.worker is None
                or actor.held is None
                or actor.restarts_left == 0
                or getattr(actor, "_preempting", False)
                or getattr(actor, "_drain_migrating", False)
                or getattr(actor, "_drain_hold", False)
            ):
                continue
            node, pg_bundle, _resources = actor.held
            if node is None or pg_bundle is not None:
                continue
            if self._effective_priority(actor.creation_spec) >= prio:
                continue
            by_node[node.node_id].append(actor)
        best: Optional[list] = None
        for node_id, actors in by_node.items():
            node = self.nodes.get(node_id)
            if node is None or not node.schedulable:
                continue
            if (
                strat.kind == "node_affinity"
                and not strat.soft
                and node_id != strat.node_id
            ):
                continue
            # cheapest victims first: lowest priority, then smallest hold
            actors.sort(
                key=lambda a: (
                    self._effective_priority(a.creation_spec),
                    sum(a.held[2].values()),
                )
            )
            avail = dict(node.available)
            chosen: list = []
            for a in actors:
                if fits(avail):
                    break
                # a victim must CONTRIBUTE to some still-unmet dimension
                # of the demand: draining CPU-only actors frees nothing
                # for a TPU-starved head — skip them or the "smallest
                # set" degenerates into migrating every cheap bystander
                if not any(
                    v > 0
                    and avail.get(k, 0.0) + 1e-9 < demand.get(k, 0.0)
                    for k, v in a.held[2].items()
                ):
                    continue
                for k, v in a.held[2].items():
                    avail[k] = avail.get(k, 0.0) + v
                chosen.append(a)
            if chosen and fits(avail):
                if best is None or len(chosen) < len(best):
                    best = chosen
        return best or []

    def _preempt_actor(self, actor: ActorState):
        """Drain-migrate one preemption victim (dedicated thread; the same
        controlled-respawn shape as ``_drain_migrate_actors``): hold its
        queue, wait — bounded — for in-flight calls to finish, mark the
        respawn budget-free, then retire its worker. A victim that cannot
        quiesce within ``preemption_drain_timeout_s`` is released
        untouched (preemption is drain, never a mid-call kill)."""
        deadline = (
            time.monotonic() + self.config.preemption_drain_timeout_s
        )
        worker = None
        while time.monotonic() < deadline and not self.shutting_down:
            with self.lock:
                if actor.state != "ALIVE" or actor.worker is None:
                    # died/killed/migrated concurrently: nothing to preempt
                    actor._preempting = False  # noqa: SLF001
                    return
                actor._drain_hold = True  # noqa: SLF001
                if actor.inflight == 0:
                    actor._drain_migrating = True  # noqa: SLF001
                    worker = actor.worker
                    break
            time.sleep(0.02)
        if worker is None:
            with self.lock:
                actor._preempting = False  # noqa: SLF001
                if actor.state == "ALIVE":
                    actor._drain_hold = False  # noqa: SLF001
                    self._pump_actor(actor)
            return
        try:
            worker.send(P.KillActor(actor.actor_id))
        except (OSError, EOFError):
            pass
        if worker.proc is not None:
            try:
                worker.proc.terminate()
            except OSError:
                pass
        elif worker.agent is not None:
            try:
                worker.agent.send(P.KillWorker(worker.worker_id))
            except (OSError, EOFError):
                pass
        with self.lock:
            self.actor_creation_stats["preempt_migrations"] += 1

    def _on_tasks_stolen(self, worker: WorkerHandle, msg: P.TasksStolen):
        with self.lock:
            worker.steal_pending = False
            for tid_b in msg.task_ids:
                pt = worker.running.pop(TaskID(tid_b), None)
                if pt is None:
                    continue
                pt.worker = None
                self._enqueue_ready(pt)
            # the steal may have emptied the pipeline (its TaskDone raced
            # ahead): release the lease or the worker leaks out of the pool
            self._maybe_end_lease_and_idle(worker)
            self.sched_cv.notify_all()

    def _end_lease(self, worker: WorkerHandle):
        """Release the worker's lease charge (call under self.lock)."""
        lease = worker.lease
        if lease is None:
            return
        worker.lease = None
        shape, node, pg_bundle, demand = lease
        s = self.lease_index.get(shape)
        if s is not None:
            s.discard(worker)
            if not s:
                del self.lease_index[shape]
        if pg_bundle is not None:
            pg, i = pg_bundle
            if not pg.removed:
                for k, v in demand.items():
                    pg.bundle_available[i][k] = pg.bundle_available[i].get(k, 0.0) + v
        elif node is not None:
            node.release(demand)
        # the lease's charge was billed to its tenant (shape[0]) at grant
        self._tenant_credit(shape[0], demand)

    def _maybe_end_lease_and_idle(self, worker: WorkerHandle):
        """After a normal task left ``worker.running``: if the pipeline
        drained, release the lease and return the worker to the idle pool
        (call under self.lock)."""
        if worker.running:
            return
        self._end_lease(worker)
        if not worker.dead and worker.actor_id is None:
            pool = self.idle_workers[worker.node_id]
            if worker not in pool:  # e.g. an empty steal reply after TaskDone
                worker.last_idle_t = time.monotonic()
                pool.append(worker)
                self._pool_worker_freed(worker)

    def _maybe_autoscale_hint(self, pt: PendingTask):
        """Record unfulfilled demand for the autoscaler, attributed to the
        demanding tenant (reference: GcsAutoscalerStateManager fed by
        scheduler backlog, per-job demand accounting)."""
        shape = tuple(sorted(pt.spec.resources.items()))
        self.pending_demand[(self._tenant_for(pt.spec), shape)] = time.time()

    @staticmethod
    def _env_fingerprint(spec: TaskSpec):
        """Workers are only reusable by tasks with the same environment needs
        (the chips a worker sees are baked in at spawn; runtime_env vars
        likewise)."""
        from ray_tpu._private.runtime_env_pip import normalize_pip_spec

        rt = spec.runtime_env or {}
        env_vars = rt.get("env_vars") or {}
        pip_spec = normalize_pip_spec(rt)
        return (
            chips_requested(spec.resources),
            tuple(sorted(env_vars.items())),
            rt.get("working_dir"),
            tuple(str(m) for m in (rt.get("py_modules") or ())),
            json.dumps(pip_spec, sort_keys=True) if pip_spec else None,
        )

    def _startup_concurrency(self) -> int:
        """Effective per-node worker-startup throttle. Thread-mode "spawn"
        is a pair of in-process threads (no fork/exec, no venv): the
        reference's conservative process throttle would serialize the
        1000-actor envelope behind 2-at-a-time thread creation."""
        if self.mode == "thread":
            return max(self.config.maximum_startup_concurrency, 32)
        return self.config.maximum_startup_concurrency

    def _worker_pool_cap(self, node: NodeState) -> int:
        if self.config.worker_pool_soft_limit > 0:
            return self.config.worker_pool_soft_limit
        return int(node.total.get("CPU", 0)) + 4

    def _acquire_worker(self, node: NodeState, pt: PendingTask) -> Optional[WorkerHandle]:
        idle = self.idle_workers.get(node.node_id, [])
        want = self._env_fingerprint(pt.spec)
        for i in range(len(idle) - 1, -1, -1):
            w = idle[i]
            if w.dead:
                idle.pop(i)
            elif w.fingerprint == want:
                idle.pop(i)
                return w
        # PER-NODE startup throttle (reference: maximum_startup_concurrency
        # is per raylet, worker_pool.cc): a global cap would serialize
        # worker/actor creation cluster-wide — with N agents, spawns must
        # pipeline N× in parallel (each agent owns its own spawn +
        # registration handshake; the head only picks the node)
        if node.starting_workers >= self._startup_concurrency():
            return None
        # Soft pool cap: past it, grow only while the pool is *blocked*
        # (nothing completed recently). Short-task churn keeps completing, so
        # a deep queue of cheap tasks reuses a bounded pool instead of
        # spawning a worker per scheduling round (the 100k-queue cliff was
        # exactly this: thousands of one-shot worker threads strangling the
        # host). Blocking workloads (e.g. zero-CPU gates) stop completing, so
        # the pool still fans out — rate-limited by startup concurrency.
        if node.task_workers + node.starting_workers >= self._worker_pool_cap(node):
            if time.monotonic() - node.last_task_done_t < self.config.worker_pool_growth_idle_s:
                # A mismatched-fingerprint idle worker at cap would deadlock
                # the shape; evict one to make room for the right env.
                evicted = False
                for i in range(len(idle) - 1, -1, -1):
                    if not idle[i].dead and idle[i].fingerprint != want:
                        w = idle.pop(i)
                        self._kill_pooled_worker(w)
                        evicted = True
                        break
                if not evicted:
                    return None
        if want[0]:
            if node.starting_chips + want[0] > node.total.get("TPU", 0):
                return None  # the spawns in flight already cover the chips
            # One process for each chip: an idle worker that was spawned for
            # a TPU grant keeps the device library loaded. It exits before
            # this node's chips go to another process (the new worker's
            # spawn waits in ChipPool.acquire until it has).
            for i in range(len(idle) - 1, -1, -1):
                if idle[i].fingerprint[0] and not idle[i].dead:
                    self._kill_pooled_worker(idle.pop(i))
            node.starting_chips += want[0]
        self.starting_workers += 1
        node.starting_workers += 1
        # Pinned by tests: agent-node actors NEVER take a head-side spawn
        # thread (creation is leased end-to-end to the agent); head spawn
        # threads remain for the head's own node, fake test nodes, and
        # non-leasable normal tasks.
        if pt.spec.is_actor_creation():
            key = (
                "agent_actor_spawn_threads"
                if node.agent is not None
                else "head_actor_spawn_threads"
            )
            self.actor_creation_stats[key] += 1
        self.actor_creation_stats["spawn_threads_total"] += 1
        threading.Thread(
            target=self._start_worker, args=(node.node_id, pt.spec), daemon=True
        ).start()
        return None

    def _uncount_pooled(self, w: WorkerHandle):
        """Remove a worker from its node's pool gauge (idempotent via the
        per-worker flag; call under self.lock)."""
        if not w.pooled_counted:
            return
        w.pooled_counted = False
        node = self.nodes.get(w.node_id)
        if node is not None and node.task_workers > 0:
            node.task_workers -= 1

    def _pool_worker_freed(self, w: WorkerHandle):
        """A pooled worker finished its task and returned to idle: stamp the
        churn clock (the growth throttle keys off pooled-worker completions
        only — actor method completions never free a pooled worker and must
        not suppress growth). Call under self.lock."""
        node = self.nodes.get(w.node_id)
        if node is not None:
            node.last_task_done_t = time.monotonic()

    def _kill_pooled_worker(self, w: WorkerHandle):
        """Retire an idle pooled worker (fingerprint eviction / idle reap)."""
        w.dead = True
        try:
            w.send(P.Shutdown())
        except Exception:
            pass
        self._uncount_pooled(w)
        self.workers.pop(w.worker_id, None)

    def _start_worker(self, node_id: NodeID, spec_hint: TaskSpec):
        try:
            worker = self._spawn_worker_process(node_id, spec_hint)
            timeout = self.config.worker_register_timeout_s
            if (spec_hint.runtime_env or {}).get("pip"):
                # the spawn may be building the offline venv (agent-side it
                # happens after SpawnWorker is sent, inside this window) —
                # don't declare the worker dead mid-install
                timeout += self.config.pip_env_build_timeout_s
            ok = worker.registered.wait(timeout)
            with self.lock:
                self.starting_workers -= 1
                node = self.nodes.get(node_id)
                if node is not None and node.starting_workers > 0:
                    node.starting_workers -= 1
                    node.starting_chips -= chips_requested(spec_hint.resources)
                if ok and not worker.dead:
                    # registered-then-died race: _on_worker_death may have run
                    # already (worker.dead set under this lock) — don't count
                    # or pool a corpse
                    worker.pooled_counted = True
                    if node is not None:
                        node.task_workers += 1
                    self.idle_workers[node_id].append(worker)
                elif not ok:
                    worker.dead = True
                    logger.error("worker failed to register in time")
                    if worker.proc is not None:
                        # it may hold chips, and they only come back at exit
                        worker.proc.terminate()
                self.sched_cv.notify_all()
        except Exception as e:
            with self.lock:
                self.starting_workers -= 1
                node = self.nodes.get(node_id)
                if node is not None and node.starting_workers > 0:
                    node.starting_workers -= 1
                    node.starting_chips -= chips_requested(spec_hint.resources)
            logger.error("worker spawn failed:\n%s", traceback.format_exc())
            from ray_tpu.exceptions import RuntimeEnvSetupError

            if isinstance(e, RuntimeEnvSetupError):
                # a doomed env must fail its tasks, not respawn forever
                self._fail_pending_for_env(self._env_fingerprint(spec_hint), e)

    def _spawn_worker_process(self, node_id: NodeID, spec_hint: TaskSpec) -> WorkerHandle:
        if self.mode == "thread":
            handle = self._spawn_worker_thread(node_id)
            handle.fingerprint = self._env_fingerprint(spec_hint)
            return handle
        node = self.nodes.get(node_id)
        if node is not None and node.agent is not None:
            return self._spawn_remote_worker(node.agent, node_id, spec_hint)
        import subprocess

        worker_id = WorkerID.from_random()
        env = dict(os.environ)
        env["RAY_TPU_WORKER"] = "1"
        env["RAY_TPU_AUTHKEY"] = self._authkey.hex()
        # Propagate the driver's resolved config table (reference:
        # ray_config_def.h — RAY_CONFIG values propagate to child
        # processes): a fresh worker rebuilds Config.from_env(), so every
        # field overridden away from its default rides its RAY_TPU_<NAME>
        # env var — otherwise `init(config={...})` knobs (serve admission
        # budgets, transfer windows, batching) silently reset to defaults
        # inside process-mode workers. Ambient env pins win untouched.
        for _key, _val in self._child_env_overrides.items():
            env.setdefault(_key, _val)
        # Make the ray_tpu package + the driver's modules importable in the
        # fresh interpreter (reference: services.py propagates sys.path).
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        extra_path = [pkg_root, os.getcwd()]
        existing = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = os.pathsep.join(
            [p for p in extra_path if p] + ([existing] if existing else [])
        )
        # Accelerator visibility: workers only see the TPU if their tasks ask
        # for it (reference: accelerators/tpu.py TPU_VISIBLE_CHIPS).
        tpu_chips = chips_requested(spec_hint.resources)
        if not tpu_chips:
            env.setdefault("JAX_PLATFORMS", "cpu")
        # Data-plane visibility: the worker attaches ONLY its node's arena;
        # objects on other nodes come through the chunked pull protocol.
        node_store = self._store_for_node(node_id)
        if hasattr(node_store, "arena_name"):
            env["RAY_TPU_ARENA"] = node_store.arena_name
        else:
            env.pop("RAY_TPU_ARENA", None)
        env_overrides = spec_hint.runtime_env.get("env_vars", {}) if spec_hint.runtime_env else {}
        env.update({k: str(v) for k, v in env_overrides.items()})
        # runtime_env working_dir (reference: working_dir packaging; local
        # dirs only here — no URI upload): worker runs with cwd + import
        # path in the requested directory
        working_dir = (
            spec_hint.runtime_env.get("working_dir")
            if spec_hint.runtime_env
            else None
        )
        if working_dir:
            working_dir = os.path.abspath(os.path.expanduser(working_dir))
            env["PYTHONPATH"] = os.pathsep.join(
                [working_dir, env.get("PYTHONPATH", "")]
            )
        # runtime_env py_modules (reference: _private/runtime_env/py_modules
        # — URI-packaged module dirs; local-path staging here): each entry is
        # staged into a per-session dir and prepended to the worker's import
        # path, so workers import code the driver never installed
        py_modules = (
            spec_hint.runtime_env.get("py_modules")
            if spec_hint.runtime_env
            else None
        )
        if py_modules:
            staged = self._stage_py_modules(py_modules)
            env["PYTHONPATH"] = os.pathsep.join(
                staged + [env.get("PYTHONPATH", "")]
            )
        # runtime_env pip: the worker interpreter is the spec's offline
        # venv (created once, content-addressed) — reference pip.py/uv.py
        from ray_tpu._private.runtime_env_pip import (
            ensure_pip_env,
            normalize_pip_spec,
        )

        pip_spec = normalize_pip_spec(spec_hint.runtime_env or {})
        python_exe = ensure_pip_env(pip_spec) if pip_spec else sys.executable
        argv = [python_exe, "-m", "ray_tpu._private.worker_main", self.address, worker_id.hex()]
        # A worker spawned for a TPU grant sees exactly the chips it was
        # granted, taken only once their previous holder has exited.
        chips: list[int] = []
        if tpu_chips:
            argv.append(str(tpu_chips))
            chips = self._chips.acquire(
                tpu_chips, self.config.worker_register_timeout_s
            )
            env.update(chip_worker_env(chips, self._chips.n_chips, env_overrides))
        # capture stdout/stderr to per-worker session files; a `print`
        # inside a task streams to the driver via the log monitor and stays
        # fetchable after the worker dies (reference: log_monitor.py)
        stdout = stderr = proc = None
        try:
            log_paths = self._worker_log_paths(worker_id)
            if log_paths is not None:
                env["PYTHONUNBUFFERED"] = "1"  # lines must reach the file promptly
                try:
                    stdout = open(log_paths[0], "ab", buffering=0)
                    stderr = open(log_paths[1], "ab", buffering=0)
                except OSError:
                    # degrade to no-capture (deleted session dir, fd limit) —
                    # the worker must still spawn
                    if stdout is not None:
                        stdout.close()
                    stdout = stderr = None
            proc = subprocess.Popen(
                argv,
                env=env,
                cwd=working_dir or None,
                stdout=stdout,
                stderr=stderr,
            )
        finally:
            # the child holds the fds now; ours would leak one pair per worker
            for fh in (stdout, stderr):
                if fh is not None:
                    fh.close()
            self._chips.bind(chips, proc)  # proc None: the chips come back
        self._register_log_meta(worker_id, pid=proc.pid, label=None)
        handle = WorkerHandle(worker_id, node_id, proc=proc)
        handle.fingerprint = self._env_fingerprint(spec_hint)
        with self.lock:
            self.workers[worker_id] = handle
        return handle

    def _spawn_remote_worker(
        self, agent: AgentHandle, node_id: NodeID, spec_hint: TaskSpec
    ) -> WorkerHandle:
        """Start a worker on an agent's host (the RequestWorkerLease →
        WorkerPool::StartWorkerProcess path across a real process/host
        boundary). Runtime-env directories are shipped by value — the agent
        host shares no filesystem with the driver (reference: working_dir
        packaging through the GCS KV, _private/runtime_env/packaging.py)."""
        worker_id = WorkerID.from_random()
        rt = spec_hint.runtime_env or {}
        packages, extra_env = self._runtime_packages(rt)
        env_vars = dict(self._child_env_overrides)
        env_vars.update(
            {k: str(v) for k, v in (rt.get("env_vars") or {}).items()}
        )
        env_vars.update(extra_env)
        handle = WorkerHandle(
            worker_id, node_id, proc=None, conn=_RelayConn(agent, worker_id)
        )
        handle.agent = agent
        handle.fingerprint = self._env_fingerprint(spec_hint)
        ip = (agent.data_address or "remote").rpartition(":")[0] or "remote"
        self._register_log_meta(worker_id, ip=ip, agent_node=node_id)
        with self.lock:
            self.workers[worker_id] = handle
        agent.send(
            P.SpawnWorker(
                worker_id,
                env_vars,
                chips_requested(spec_hint.resources),
                handle.fingerprint,
                packages,
            )
        )
        return handle

    def _runtime_packages(self, rt: dict) -> tuple[list, dict]:
        """Runtime-env payloads for shipment to an agent host (no shared
        filesystem): ``(packages, extra_env_vars)``. Shared by the
        head-managed SpawnWorker path and the actor creation-lease grant —
        working_dir/py_modules travel as content-cached zips, pip as the
        wheel-cache zip plus a spec env var the agent's venv builder reads."""
        packages: list[tuple] = []
        extra_env: dict[str, str] = {}
        working_dir = rt.get("working_dir")
        if working_dir:
            path = os.path.abspath(os.path.expanduser(working_dir))
            packages.append(("working_dir", *self._package_cached(path)))
        for mod in rt.get("py_modules") or ():
            path = os.path.abspath(os.path.expanduser(str(mod)))
            packages.append(("py_module", *self._package_cached(path)))
        from ray_tpu._private.runtime_env_pip import normalize_pip_spec

        pip_spec = normalize_pip_spec(rt)
        if pip_spec:
            if pip_spec["find_links"]:
                packages.append(
                    ("pip_wheels", *self._package_cached(pip_spec["find_links"]))
                )
            extra_env["RAY_TPU_PIP_SPEC"] = json.dumps(
                {
                    "packages": pip_spec["packages"],
                    "tool": pip_spec.get("tool", "pip"),
                }
            )
        return packages, extra_env

    def _package_cached(self, path: str) -> tuple[str, bytes]:
        """Zip a runtime-env path for shipment, cached by content
        fingerprint — respawns must not re-walk + re-compress the tree
        (mirrors _stage_py_modules' content-addressed staging)."""
        tag = self._tree_fingerprint(path)
        with self.lock:
            cache = getattr(self, "_pkg_cache", None)
            if cache is None:
                cache = self._pkg_cache = {}
            hit = cache.get((path, tag))
            if hit is not None:
                return hit
        result = _package_path(path)
        with self.lock:
            cache[(path, tag)] = result
            # bound memory: keep only the most recent handful of packages
            while len(cache) > 8:
                cache.pop(next(iter(cache)))
        return result

    def _stage_py_modules(self, py_modules: list) -> list[str]:
        """Copy each module dir/file into the session's runtime-env staging
        area (once, content-addressed by path+mtime) and return the import
        roots to prepend."""
        import shutil

        base = os.path.join(
            tempfile.gettempdir(), f"rtpu-pymods-{os.getpid()}"
        )
        os.makedirs(base, exist_ok=True)
        roots = []
        for mod in py_modules:
            src = os.path.abspath(os.path.expanduser(str(mod)))
            if not os.path.exists(src):
                raise ValueError(f"py_modules path does not exist: {src}")
            tag = self._tree_fingerprint(src)
            dst_root = os.path.join(base, tag)
            dst = os.path.join(dst_root, os.path.basename(src))
            if not os.path.exists(dst):
                os.makedirs(dst_root, exist_ok=True)
                if os.path.isdir(src):
                    shutil.copytree(src, dst, dirs_exist_ok=True)
                else:
                    shutil.copy2(src, dst)
            roots.append(dst_root)
        return roots

    @staticmethod
    def _tree_fingerprint(src: str) -> str:
        """Content fingerprint over every contained file's (path, mtime,
        size) — a directory's own mtime does NOT change when a nested file
        is edited, so staging keyed on it would serve stale code."""
        import hashlib

        h = hashlib.sha256(src.encode())
        if os.path.isdir(src):
            for root, _, files in sorted(os.walk(src)):
                for f in sorted(files):
                    p = os.path.join(root, f)
                    try:
                        st = os.stat(p)
                    except OSError:
                        continue
                    h.update(
                        f"{os.path.relpath(p, src)}:{st.st_mtime_ns}:{st.st_size}".encode()
                    )
        else:
            st = os.stat(src)
            h.update(f"{st.st_mtime_ns}:{st.st_size}".encode())
        return h.hexdigest()[:16]

    def _spawn_worker_thread(self, node_id: NodeID) -> WorkerHandle:
        """Thread-mode worker: same execution loop, in-process (local_mode
        analog; reference: ``ray.init(local_mode=True)``)."""
        from ray_tpu._private.worker_runtime import WorkerRuntime, InProcessChannel

        worker_id = WorkerID.from_random()
        chan_a, chan_b = InProcessChannel.pair()
        handle = WorkerHandle(worker_id, node_id, proc=None, conn=chan_a)
        runtime = WorkerRuntime(worker_id, chan_b, in_process=True)
        t = threading.Thread(target=runtime.run, daemon=True, name=f"worker-{worker_id.hex()[:6]}")
        t.start()
        with self.lock:
            self.workers[worker_id] = handle
        reader = threading.Thread(
            target=self._worker_reader, args=(handle,), daemon=True, name=f"rd-{worker_id.hex()[:6]}"
        )
        reader.start()
        handle.registered.wait(5)
        return handle

    # ------------------------------------------------------- worker transport

    def _accept_loop(self, listener):
        import errno

        while not self.shutting_down:
            try:
                conn = listener.accept()
            except OSError as e:
                # EBADF/EINVAL = the listener itself was closed (shutdown).
                # Anything else (ECONNRESET from a peer that dropped mid
                # authkey-challenge — e.g. a bare TCP health probe) is
                # per-connection: exiting here would silently kill the
                # accept loop and strand every later connect in the backlog
                # until SYN timeout.
                if self.shutting_down or e.errno in (errno.EBADF, errno.EINVAL):
                    return
                time.sleep(0.05)  # persistent errors (EMFILE) must not spin
                continue
            except Exception:  # noqa: BLE001 — failed/aborted handshake
                continue  # keep serving other clients
            threading.Thread(target=self._handshake, args=(conn,), daemon=True).start()

    def _handshake(self, conn):
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            conn.close()
            return
        if isinstance(msg, P.RegisterDriver):
            # client driver (ray:// analog): full API over the channel, but
            # never a scheduling target
            handle = WorkerHandle(msg.driver_id, self.head_node_id, conn=conn)
            handle.is_driver = True
            handle.registered.set()
            with self.lock:
                self.driver_conns[msg.driver_id] = handle
            logger.info("client driver %s attached", msg.driver_id.hex()[:8])
            self._worker_reader(handle)
            return
        if isinstance(msg, P.RegisterAgent):
            self._register_agent(msg, conn)
            return
        if not isinstance(msg, P.RegisterWorker):
            conn.close()
            return
        with self.lock:
            handle = self.workers.get(msg.worker_id)
            if handle is None:
                conn.close()
                return
            handle.conn = conn
            handle.direct_address = getattr(msg, "direct_address", None)
            handle.registered.set()
        self._worker_reader(handle)

    # ------------------------------------------------------------ node agents

    def _register_agent(self, msg: P.RegisterAgent, conn):
        """A REAL node joins (reference: NodeManager registration with the
        GCS, ``gcs_node_manager``). The agent owns its host's worker pool
        and arena; the controller records the node, routes spawns through
        the agent, and reads the node's objects over its data listener."""
        resume = getattr(msg, "resume", False)
        if resume:
            # boot replay may still be parking this node's journaled leases
            # — deciding the resume verdict (or applying a reconcile
            # report) against a half-restored table would reap held work
            # as orphans and double-execute it after re-place
            self._restore_done.wait(timeout=60.0)
        if resume and not self.recovering:
            # preserved-state re-attach refused: either the head never died
            # (its reader EOF already re-placed this node's leases) or the
            # recovery window closed (journaled leases were re-placed at
            # the deadline) — accepting held work now would execute it
            # twice. The agent resets and re-registers fresh.
            try:
                conn.send(
                    P.AgentAck(msg.node_id.hex(), resume_verdict="reset")
                )
            except (OSError, EOFError):
                pass
            conn.close()
            return
        with self.lock:
            existing = self.nodes.get(msg.node_id)
        if existing is not None and existing.alive:
            # re-registration after a transient disconnect (the head never
            # died): retire the old incarnation first — its workers/arena
            # are gone on the agent side, and overwriting the NodeState
            # in place would corrupt resource accounting (releases against
            # a fresh full-capacity table)
            self.remove_node(msg.node_id)
        agent = AgentHandle(msg.node_id, conn, msg.arena_name, msg.data_address)
        # Ack BEFORE the node becomes schedulable: once the scheduler can
        # pick this node, a SpawnWorker may be serialized onto the conn, and
        # the joining agent's blocking recv expects the ack first.
        try:
            agent.send(
                P.AgentAck(
                    msg.node_id.hex(),
                    resume_verdict="reconcile" if resume else "fresh",
                )
            )
        except (OSError, EOFError):
            conn.close()
            return
        with self.lock:
            node = NodeState(msg.node_id, msg.resources, msg.labels)
            node.agent = agent
            self.nodes[msg.node_id] = node
            self.agents[msg.node_id] = agent
            proxy = RemoteArenaProxy(agent)
            self.node_stores[msg.node_id] = proxy
            if msg.arena_name:
                self._stores_by_arena[msg.arena_name] = proxy
            if not self._hb_monitor_started:
                self._hb_monitor_started = True
                t = threading.Thread(
                    target=self._heartbeat_monitor, daemon=True, name="ctrl-hb"
                )
                t.start()
                self._threads.append(t)
            self.sched_cv.notify_all()
        logger.info(
            "node agent registered: %s host=%s resources=%s%s",
            msg.node_id.hex()[:8], msg.hostname, msg.resources,
            " (resume: reconciling)" if resume else "",
        )
        self._journal("node_up", msg.node_id.hex())
        self.publish(
            "nodes",
            {
                "node_id": msg.node_id.hex(),
                "event": "added",
                "resources": dict(msg.resources),
                "hostname": msg.hostname,
            },
        )
        if resume:
            # ask for the node's truth; the agent answers with the
            # reconcile_report op on this connection
            self._ask_reconcile(agent)
        self._agent_reader(agent)

    def _agent_reader(self, agent: AgentHandle):
        conn = agent.conn
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break
            except TypeError:
                # another thread close()d this connection mid-recv (drain's
                # remove_node): the handle is None now — same as EOF
                break
            self.worker_msg_count += 1
            if isinstance(msg, P.FromWorker):
                with self.lock:
                    handle = self.workers.get(msg.worker_id)
                    if handle is None and isinstance(msg.msg, P.RegisterWorker):
                        # agent-owned pool worker (spawned by the agent's
                        # local dispatcher): track identity for its own
                        # control-plane ops, but never schedule onto it —
                        # the agent owns its queue
                        handle = WorkerHandle(
                            msg.worker_id, agent.node_id,
                            conn=_RelayConn(agent, msg.worker_id),
                        )
                        handle.agent = agent
                        handle.agent_owned = True
                        handle.registered.set()
                        self.workers[msg.worker_id] = handle
                if handle is not None:
                    self._route_worker_msg(handle, msg.msg)
            elif isinstance(msg, P.AgentTaskDone):
                self._on_agent_task_done(agent, msg)
            elif isinstance(msg, P.AgentReportBatch):
                # one frame, N completion reports (agent flush tick); FIFO
                # order preserved — and each completion may re-arm the node
                # through the lease cache exactly as a lone report would
                for item in msg.items:
                    self._on_agent_task_done(agent, item)
                # the node's span/metric payload piggybacks on this tick
                # (see protocol.AgentReportBatch.observability)
                obs = getattr(msg, "observability", None)
                if obs:
                    self._apply_observability(agent.node_id.hex()[:12], obs)
            elif isinstance(msg, P.TaskSpilled):
                self._on_task_spilled(agent, msg)
            elif isinstance(msg, P.Heartbeat):
                with self.lock:
                    node = self.nodes.get(agent.node_id)
                    if node is not None:
                        node.last_heartbeat = time.monotonic()
                agent.load = msg.load
            elif isinstance(msg, P.AgentDrained):
                with self.lock:
                    rec = self.drains.get(agent.node_id)
                if rec is not None:
                    rec["agent_remaining"] = msg.remaining
                    rec["agent_quiesced"] = True
            elif isinstance(msg, P.WorkerDied):
                with self.lock:
                    handle = self.workers.get(msg.worker_id)
                if handle is not None:
                    self._on_worker_death(handle, reason=msg.reason)
                    if msg.reason.startswith("pip env failed"):
                        # the agent could not build this env: every queued
                        # task needing it is doomed — fail, don't respawn
                        from ray_tpu.exceptions import RuntimeEnvSetupError

                        self._fail_pending_for_env(
                            handle.fingerprint,
                            RuntimeEnvSetupError(msg.reason),
                        )
            elif isinstance(msg, P.WorkerLogLines):
                # agent-owned pool workers are spawned without head
                # involvement — their first captured lines register them in
                # the log table so list/fetch can find them
                meta = self._log_meta.setdefault(msg.worker_id_hex, {})
                meta.setdefault(
                    "ip",
                    (agent.data_address or "remote").rpartition(":")[0]
                    or "remote",
                )
                meta.setdefault("agent_node", agent.node_id)
                self._emit_worker_lines(msg.worker_id_hex, msg.source, msg.lines)
            elif isinstance(msg, P.LogsReply):
                waiter = self._log_waiters.get(msg.req_id)
                if waiter is not None:
                    waiter[1].append(msg.text)
                    waiter[0].set()
            elif isinstance(msg, P.Request):
                # the agent's own control RPCs. A chunk pull can block on a
                # not-yet-sealed entry whose seal arrives on THIS thread —
                # never handle those inline.
                if msg.op in (
                    "pull_object_chunk", "pubsub_poll", "object_locations",
                ):
                    threading.Thread(
                        target=self._handle_request, args=(agent, msg), daemon=True
                    ).start()
                else:
                    self._handle_request(agent, msg)
        logger.warning("node agent %s disconnected", agent.node_id.hex()[:8])
        self.remove_node(agent.node_id)

    def _heartbeat_monitor(self):
        """Declare agent nodes dead after a silent window (reference:
        ``gcs_health_check_manager.h``). Connection EOF usually fires first;
        this catches half-open TCP (host crash, network partition)."""
        timeout = self.config.agent_heartbeat_timeout_s
        while not self.shutting_down:
            time.sleep(min(timeout / 3.0, 2.0))
            now = time.monotonic()
            with self.lock:
                stale = [
                    nid
                    for nid, agent in self.agents.items()
                    if (n := self.nodes.get(nid)) is not None
                    and n.alive
                    and now - n.last_heartbeat > timeout
                ]
            for nid in stale:
                logger.warning(
                    "node %s missed heartbeats for %.0fs: removing",
                    nid.hex()[:8], timeout,
                )
                self.remove_node(nid)

    def _worker_reader(self, handle: WorkerHandle):
        conn = handle.conn
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break
            self.worker_msg_count += 1
            self._route_worker_msg(handle, msg)
        if handle.is_driver:
            with self.lock:
                self.driver_conns.pop(handle.worker_id, None)
            # release whatever the client still held (a crashed client's
            # ObjectRef finalizers never ran) — else its objects pin the
            # store for the cluster's lifetime
            for oid in list(handle.held_refs):
                try:
                    self.remove_ref(oid)
                except Exception:
                    pass
            handle.held_refs.clear()
            logger.info("client driver %s detached", handle.worker_id.hex()[:8])
            return
        self._on_worker_death(handle, reason="connection closed")

    def _route_worker_msg(self, handle: WorkerHandle, msg):
        """Dispatch one worker-originated message (shared between direct
        connections and agent-relayed envelopes)."""
        if isinstance(msg, P.RegisterWorker):
            handle.direct_address = getattr(msg, "direct_address", None)
            handle.registered.set()
        elif isinstance(msg, P.TaskDone):
            self._on_task_done(handle, msg)
        elif isinstance(msg, P.GetObjects):
            # Blocking op: dedicated thread so waiters can't starve the
            # control plane (no bounded pool → no waiter deadlock).
            threading.Thread(
                target=self._handle_get, args=(handle, msg), daemon=True
            ).start()
        elif isinstance(msg, P.PutObject):
            self._handle_put(handle, msg)
        elif isinstance(msg, P.Request):
            if handle.is_driver and msg.op == "add_ref":
                handle.held_refs.update(msg.payload)
            if msg.op in (
                "wait", "pg_ready", "get_entries", "worker_stacks",
                "pubsub_poll", "pull_object_chunk", "pull_into_arena",
                "object_locations",
            ):
                threading.Thread(
                    target=self._handle_request, args=(handle, msg), daemon=True
                ).start()
            else:
                self._handle_request(handle, msg)
        elif isinstance(msg, P.FreeObjects):
            for oid in msg.object_ids:
                handle.held_refs.discard(oid)
                self.remove_ref(oid)
        elif isinstance(msg, P.TasksStolen):
            self._on_tasks_stolen(handle, msg)
        elif isinstance(msg, P.StacksReply):
            waiter = self._stack_waiters.get(msg.req_id)
            if waiter is not None:
                waiter[1].append(msg.text)
                waiter[0].set()
        elif isinstance(msg, P.WorkerError):
            logger.error("worker %s error: %s", handle.worker_id.hex()[:8], msg.message)

    def _handle_get(self, handle: WorkerHandle, msg: P.GetObjects):
        self._maybe_recover(msg.object_ids)
        entries = self.memory_store.get(msg.object_ids, timeout=None)
        results = []
        for oid, entry in zip(msg.object_ids, entries):
            kind, payload = entry
            if kind in ("inline", "error"):
                results.append((oid, kind, payload.to_bytes()))
            else:
                results.append((oid, kind, payload))  # plasma | spilled
        try:
            handle.send(P.GetReply(msg.req_id, results))
        except (OSError, EOFError):
            pass

    def seal_object(self, object_id: ObjectID, kind: str, payload) -> None:
        """Seal one worker-produced object (stream items included). Shared
        by the PutObject channel handler and thread-mode workers sealing
        in-process — an inline actor task must NOT push its stream items
        through the worker channel, whose only reply pump is the very
        thread executing the task (see WorkerRuntime._inproc_controller)."""
        self._maybe_pin_stream_item(object_id)
        if kind in ("inline", "error"):
            self.memory_store.put(
                object_id, (kind, SerializedObject.from_buffer(payload))
            )
            self._journal("seal", (object_id.binary(), kind, bytes(payload)))
        else:
            shm_name, size = payload
            self._seal_plasma(object_id, shm_name, size)
        self._on_object_sealed(object_id)

    def _handle_put(self, handle: WorkerHandle, msg: P.PutObject):
        self.seal_object(msg.object_id, msg.kind, msg.payload)
        try:
            handle.send(P.PutAck(msg.req_id))
        except (OSError, EOFError):
            pass

    def _handle_request(self, handle: WorkerHandle, msg: P.Request):
        try:
            payload = self._dispatch_request(msg.op, msg.payload, caller=handle)
            reply = P.Reply(msg.req_id, payload)
        except Exception as e:  # noqa: BLE001
            reply = P.Reply(msg.req_id, None, error=f"{type(e).__name__}: {e}")
        try:
            handle.send(reply)
        except (OSError, EOFError):
            pass

    def _maybe_inject_rpc_failure(self, op: str):
        """Config-driven chaos (reference: ``rpc/rpc_chaos.h:23`` — inject
        request failures per method via RAY_testing_rpc_failure)."""
        if not self._rpc_chaos:
            return
        prob = self._rpc_chaos.get(op)
        if prob and self._chaos_rng.random() < prob:
            raise WorkerCrashedError(
                f"injected rpc failure for {op!r} (testing_rpc_failure)"
            )

    def _dispatch_request(self, op: str, payload, caller: "WorkerHandle" = None):
        """Route one string-keyed request to its subsystem's dispatch
        shard. The old single if-ladder serialized every op behind one
        string-compare walk; the table routes in O(1) and each shard
        documents which subsystem lock its handlers take (reference:
        the per-manager gRPC services of ``src/ray/gcs/`` vs one
        monolithic handler). Chaos injection stays here so every op —
        batched or not — remains injectable by name."""
        self._maybe_inject_rpc_failure(op)
        shard = self._dispatch_table.get(op)
        if shard is None:
            raise ValueError(f"unknown controller op: {op}")
        return shard(op, payload, caller)

    def _dispatch_task_ops(self, op: str, payload, caller: "WorkerHandle" = None):
        """Dispatch shard: task submission / cancellation / task-state queries."""
        if op == "submit_task":
            spec, name = payload
            if spec.task_type == TaskType.ACTOR_CREATION_TASK:
                # register_actor submits under its ONE lock hold (no second
                # lock take through submit_task) and raises synchronously —
                # named creations stay a sync op so duplicate names surface
                # at the call site, not at get()
                self.register_actor(spec, name=name)
            else:
                self.submit_task(spec)
            return None
        if op == "submit_batch":
            # client-coalesced submits + ref traffic, one lock hold, one
            # scheduler wake (see Controller.submit_batch for replay rules)
            if caller is not None and getattr(caller, "is_driver", False):
                # crash-reap bookkeeping parity with the unbatched add_ref/
                # FreeObjects paths: a detached client's refs must release
                for item in payload:
                    if item[0] == "add_ref":
                        caller.held_refs.update(item[1])
                    elif item[0] == "submit":
                        caller.held_refs.update(item[1].return_ids())
                    elif item[0] == "free":
                        caller.held_refs.difference_update(item[1])
            self.submit_batch(payload, caller=caller)
            return None
        if op == "cancel":
            self.cancel_task(payload)
            return None
        if op == "tasks_pending":
            # liveness of specific task ids (direct transport's head-queue
            # drain check — cross-path per-caller ordering)
            with self.lock:
                return [tid in self.pending_by_id for tid in payload]
        if op == "task_events":
            return list(self.task_events)
        if op == "list_tasks":
            limit = payload or 1000
            with self.lock:
                running = [
                    {
                        "task_id": pt.spec.task_id.hex(),
                        "name": pt.spec.name,
                        "state": "RUNNING",
                        "worker_id": w.worker_id.hex(),
                    }
                    for w in self.workers.values()
                    for pt in w.running.values()
                ]
                queued = [
                    {"task_id": pt.spec.task_id.hex(), "name": pt.spec.name,
                     "state": "PENDING_SCHEDULING", "worker_id": None}
                    for pt in self._iter_ready()
                ]
                ready_ids = {pt.spec.task_id for pt in self._iter_ready()}
                running_ids = {
                    pt.spec.task_id
                    for w in self.workers.values()
                    for pt in w.running.values()
                }
                actor_queued_ids = {
                    pt.spec.task_id
                    for a in self.actors.values()
                    for pt in a.queue
                }
                blocked = [
                    {"task_id": pt.spec.task_id.hex(), "name": pt.spec.name,
                     "state": "PENDING_ARGS_AVAIL", "worker_id": None}
                    for pt in self.pending_by_id.values()
                    if pt.spec.task_id not in ready_ids
                    and pt.spec.task_id not in running_ids
                    and pt.spec.task_id not in actor_queued_ids
                ]
                actor_queued = [
                    {"task_id": pt.spec.task_id.hex(), "name": pt.spec.name,
                     "state": "PENDING_ACTOR", "worker_id": None}
                    for a in self.actors.values()
                    for pt in a.queue
                ]
            return (running + queued + blocked + actor_queued)[:limit]
        if op == "debug_worker_msg_count":
            return self.worker_msg_count
        raise ValueError(f"unknown controller op: {op}")

    def _dispatch_actor_ops(self, op: str, payload, caller: "WorkerHandle" = None):
        """Dispatch shard: actor lifecycle, placement reports, actor-state queries."""
        if op == "actor_direct_endpoint":
            # direct actor-call transport: resolve the actor's worker
            # endpoint ONCE per caller (cached caller-side; invalidated when
            # the connection breaks). Reference: ActorTaskSubmitter resolves
            # the actor's rpc address from the GCS actor table, then pushes
            # calls peer-to-peer (actor_task_submitter.h).
            with self.lock:
                actor = self.actors.get(payload)
                if (
                    actor is not None
                    and actor.state == "ALIVE"
                    and actor.worker is not None
                    and not actor.worker.dead
                    and actor.worker.direct_address
                ):
                    return ("ALIVE", actor.worker.direct_address)
                return (actor.state if actor is not None else "UNKNOWN", None)
        if op == "get_named_actor":
            actor_id = self.get_named_actor(payload)
            if actor_id is None:
                return None
            actor = self.actors[actor_id]
            return (actor_id, actor.creation_spec.max_concurrency)
        if op == "actor_state":
            actor = self.actors.get(payload)
            return actor.state if actor else None
        if op == "kill_actor":
            actor_id, no_restart = payload
            self.kill_actor(actor_id, no_restart)
            return None
        # ---- state API (reference: util/state/api.py over GcsTaskManager
        #      and per-entity GCS tables) ----
        if op == "list_actors":
            with self.lock:
                return [
                    {
                        "actor_id": a.actor_id.hex(),
                        "class_name": a.creation_spec.name.split(".")[0],
                        "state": a.state,
                        "name": a.name or "",
                        "pending_tasks": len(a.queue),
                        "restarts_left": a.restarts_left,
                        "death_cause": a.death_cause,
                    }
                    for a in self.actors.values()
                ]
        if op == "actor_placed":
            # The agent completed a creation lease end-to-end (spawn,
            # registration handshake, creation task): bind the actor to its
            # worker and go ALIVE. Verdicts: "ok" (bound; idempotent on a
            # duplicate report) or "dead" (the actor was killed/superseded
            # meanwhile, or the worker already died — the agent must reap
            # the worker / the lease was re-placed).
            actor_id, worker_id, direct_address, results, exec_ms = payload
            if not isinstance(caller, AgentHandle):
                raise ValueError("actor_placed requires an agent caller")
            return self._on_actor_placed(
                caller, actor_id, worker_id, direct_address, results, exec_ms
            )
        if op == "actor_placed_batch":
            # N coalesced placement reports (one agent flush tick): one
            # round trip carrying a verdict per item, order-preserving.
            # Each item is idempotent exactly like a lone actor_placed, so
            # a replayed batch draws the same verdicts.
            if not isinstance(caller, AgentHandle):
                raise ValueError("actor_placed_batch requires an agent caller")
            verdicts = []
            for item in payload:
                actor_id, worker_id, direct_address, results, exec_ms = item
                verdicts.append(
                    self._on_actor_placed(
                        caller, actor_id, worker_id, direct_address,
                        results, exec_ms,
                    )
                )
            return verdicts
        if op == "actor_creation_failed":
            # The agent could not place the leased actor. retryable=True →
            # infra failure (worker/spawn/handshake death, drain race):
            # re-place per the budget policy; retryable=False → the
            # creation task itself failed (raising __init__): terminal.
            actor_id, reason, retryable, results, exec_ms = payload
            if not isinstance(caller, AgentHandle):
                raise ValueError("actor_creation_failed requires an agent caller")
            self._on_actor_creation_failed(
                caller, actor_id, reason, retryable, results, exec_ms
            )
            return None
        if op == "actor_creation_stats":
            with self.lock:
                return dict(self.actor_creation_stats)
        raise ValueError(f"unknown controller op: {op}")

    def _dispatch_object_ops(self, op: str, payload, caller: "WorkerHandle" = None):
        """Dispatch shard: object plane: refs, waits, chunk transfer, streams, replicas."""
        if op == "add_ref":
            for oid in payload:
                self.add_ref(oid)
            return None
        if op == "wait":
            object_ids, num_returns, timeout = payload
            return self.memory_store.wait(object_ids, num_returns, timeout)
        if op == "shm_create":
            # native-arena allocation for a worker (the plasma-create RPC;
            # reference: plasma client protocol CreateRequest), spilling
            # cold objects to disk when the arena is full. The allocation
            # lands in the CALLER's node's arena — each node owns its data
            # plane.
            from ray_tpu._private.object_store import ObjectExistsError

            object_id, size = payload
            store = (
                self._store_for_node(caller.node_id)
                if caller is not None and caller.node_id is not None
                else self.plasma
            )
            try:
                return self._create_with_spill_retry(
                    store.create_remote, object_id, size, store=store
                )
            except ObjectExistsError:
                # duplicate put: tell the worker to skip the write — the
                # sealed object stands (idempotent put semantics)
                entry = store.lookup(object_id)
                if entry is not None:
                    return ("exists", entry[0], entry[1])
                raise
        if op == "push_object_chunk":
            # inverse of pull: an arena-less client driver streams a put's
            # bytes to the head, which seals them into its own store
            # (reference: PushManager, push_manager.h:27). Chunks may be
            # retried (chaos / transient failures) — writes are idempotent
            # and completion counts only distinct offsets.
            object_id, offset, total, data = payload
            if self.memory_store.contains(object_id):
                # retried chunk arriving after the push completed and sealed:
                # ack without re-opening a pending buffer (it would never
                # complete and leak `total` bytes)
                return None
            with self.lock:
                buf, received = self._pending_pushes.setdefault(
                    object_id, (bytearray(total), {})
                )
                buf[offset : offset + len(data)] = data
                received[offset] = len(data)  # idempotent on chunk retry
                done = sum(received.values()) >= total
                if done:
                    del self._pending_pushes[object_id]
            if done:
                self.put_serialized(
                    object_id, SerializedObject.from_buffer(bytes(buf))
                )
            return None
        if op == "pull_object_chunk":
            # chunked node-to-node transfer (reference: ObjectManager::Push
            # streaming chunks, object_buffer_pool.h): serve [offset,
            # offset+length) of the object's payload bytes from wherever it
            # currently lives (arena or spill file). The entry is re-read
            # per chunk so a spill mid-pull transparently switches backend.
            object_id, offset, length = payload
            length = min(length, self.config.object_transfer_chunk_bytes)
            self._maybe_recover([object_id])
            entry = self.memory_store.get([object_id], timeout=30)[0]
            if entry is None:
                raise ObjectLostError(f"object {object_id.hex()} not found")
            with self.lock:
                self.transfer_stats["chunks_served"] += 1
            if self.config.testing_chunk_delay_ms:
                # simulated cross-host RTT (runs on this op's dedicated
                # handler thread; see _route_worker_msg threading)
                time.sleep(self.config.testing_chunk_delay_ms / 1000.0)
            kind, p = entry
            if kind == "spilled":
                path, size = p
                agent = self._agent_spills.get(object_id)
                if agent is not None:
                    # spilled onto an AGENT's disk: its data listener (or
                    # any replica holder) serves
                    return self._pull_chunk_from_agent(
                        agent.data_address, object_id, offset, length,
                        extra_addresses=self._replica_addresses(object_id),
                    )
                with open(path, "rb") as f:
                    f.seek(offset)
                    return (size, f.read(length))
            if kind == "plasma":
                name, size = p
                from ray_tpu._private.object_store import (
                    ObjectRelocatedError,
                    parse_arena_location,
                )

                loc = parse_arena_location(name)
                if loc is None:
                    # legacy per-segment store: read whole + slice
                    sobj = self.plasma_client.read(name, size)
                    return (size, sobj.to_bytes()[offset : offset + length])
                store = self._store_for_location(name)
                if getattr(store, "is_remote", False):
                    # resident on an agent: relay the chunk read to the
                    # owner's data listener, spread across replica holders
                    # (client drivers and head-local workers pull here)
                    return self._pull_chunk_from_agent(
                        store.agent.data_address, object_id, offset, length,
                        extra_addresses=self._replica_addresses(object_id),
                    )
                chunk = bytes(
                    store.arena.view(loc[1] + offset, min(length, size - offset))
                )
                # validate-after-copy (same protocol as PlasmaClient.read)
                got = store.arena.lookup(object_id.binary())
                if got is None or got[0] != loc[1]:
                    raise ObjectRelocatedError(name)
                return (size, chunk)
            # inline/error entries are small: serve from their bytes
            data = p.to_bytes()
            return (len(data), data[offset : offset + length])
        if op == "pull_into_arena":
            # A head-side worker asks for a remote object to be
            # materialized into ITS node's arena (agent-host workers never
            # reach here — their agent intercepts the op locally).
            object_id, size_hint = payload
            return self.pull_into_arena(
                getattr(caller, "node_id", None), object_id, size_hint
            )
        if op == "object_locations":
            # Full replica set: every data address that can serve this
            # object's chunks — the owner plus registered replicas
            # (reference: OwnershipObjectDirectory — any node holding a
            # copy serves it). Pullers spread load across the set and fail
            # over mid-pull when a source dies.
            primary = self._primary_data_address(payload)
            addrs = [primary] if primary else []
            addrs += self._replica_addresses(payload, exclude=primary)
            return addrs
        if op == "register_replica":
            # An arena node materialized a pulled object locally
            # (pull-into-arena) and now serves it to peers. "freed" tells
            # the caller the object died mid-pull: discard the copy.
            object_id, shm_name, size = payload
            if self._register_replica_entry(object_id, shm_name, size):
                return None
            return "freed"
        if op == "unregister_replica":
            # The holder wants to evict its copy (arena pressure / drain).
            # "primary" tells it NOT to: the copy was since PROMOTED (its
            # original primary died) — the holder must take the normal
            # spill path, or the object's last copy dies with the eviction.
            object_id, arena = payload
            from ray_tpu._private.object_store import parse_arena_location

            with self.lock:
                reps = self._object_replicas.get(object_id)
                if reps is not None and arena in reps:
                    self._unregister_replica(object_id, arena)
                    return None
                entry = self.memory_store.peek(object_id)
            if entry is not None and entry[0] == "plasma":
                loc = parse_arena_location(entry[1][0])
                if loc is not None and loc[0] == arena:
                    return "primary"
            return None
        if op == "transfer_stats":
            with self.lock:
                return dict(self.transfer_stats)
        if op == "report_agent_spill":
            # An agent moved a resident object to ITS disk; the entry now
            # points at an agent-local spill path (same-host workers open it
            # directly; everyone else pulls chunks from the agent). Commit
            # atomically vs _free_object: if the last ref dropped while the
            # agent was spilling, the put would resurrect a freed object —
            # tell the agent to discard the spill file instead.
            object_id, path, size = payload
            if not isinstance(caller, AgentHandle):
                raise ValueError("report_agent_spill requires an agent caller")
            with self.lock:
                if object_id not in self._remote_resident.get(caller.arena_name, ()):
                    return "freed"
                self._agent_spills[object_id] = caller
                self.memory_store.put(object_id, ("spilled", (path, size)))
            return None
        if op == "testing_lose_object":
            # Test hook: destroy an object's sole copy WITHOUT touching ref
            # counts or lineage — simulates a crashed store/node (reference:
            # the killer-actor + free() loss pattern in recovery tests).
            object_id = payload
            entry = self.memory_store.get([object_id], timeout=0)[0]
            with self.lock:
                self.memory_store.delete([object_id])
                self.plasma_resident.pop(object_id, None)
            if entry is not None and entry[0] == "plasma":
                self._store_for_location(entry[1][0]).delete(object_id)
            elif entry is not None and entry[0] == "spilled":
                try:
                    os.unlink(entry[1][0])
                except OSError:
                    pass
            # the hook simulates losing EVERY copy: replicas go too, or the
            # "lost" object would keep serving from the directory
            self._drop_replicas(object_id)
            return entry is not None
        if op == "stream_consumed_report":
            # consumer progress: feeds backpressure and transfers the
            # producer's pin of the taken item to the consumer (who has
            # already add_ref'd it — FIFO on the channel guarantees order)
            task_id, count = payload
            with self.lock:
                # -1 (consumer abandoned the stream) is STICKY: a progress
                # report processed after the abandon marker must not revive
                # a dead-stream producer's poll loop
                current = self._stream_consumed.get(task_id, 0)
                if current >= 0 and count > current:
                    self._stream_consumed[task_id] = count
                if len(self._stream_consumed) > 4096:
                    # evict only finished streams: dropping a live counter
                    # would deadlock its backpressured producer against its
                    # consumer
                    for tid in list(self._stream_consumed):
                        if tid not in self.pending_by_id:
                            del self._stream_consumed[tid]
                            if len(self._stream_consumed) <= 4096:
                                break
                pins = self._stream_pins.get(task_id)
                if pins is not None:
                    for idx in [i for i in pins if i <= count]:
                        pins.discard(idx)
                        self.remove_ref(ObjectID.for_return(task_id, idx))
                    if not pins:
                        self._stream_pins.pop(task_id, None)
            return None
        if op == "stream_abandoned":
            # Explicit consumer-gone: the serve handle's finalize watcher
            # reports an abandoned stream directly instead of relying on the
            # completion refcount reaching zero (a stray interpreter-held
            # ObjectRef instance must not keep a dead stream's producer
            # polling). Force-drops the completion record; _free_object's
            # stream branch releases producer pins and sets the sticky -1.
            with self.lock:
                self.ref_counts.pop(payload, None)
                self._free_object(payload)
            return None
        if op == "stream_consumed_get":
            with self.lock:
                return self._stream_consumed.get(payload, 0)
        if op == "list_objects":
            with self.lock:
                return {
                    "num_objects_in_memory_store": self.memory_store.size(),
                    "num_plasma_objects": (
                        self.plasma.num_objects()
                        if hasattr(self.plasma, "num_objects")
                        else len(getattr(self.plasma, "_sealed", {}))
                    ),
                    "plasma_used_bytes": self.plasma.used_bytes(),
                    "ref_counted": len(self.ref_counts),
                }
        if op == "head_arena":
            # client drivers probe-attach this arena: same-host clients get
            # the shared-memory data plane, cross-host ones fall back to
            # chunked push/pull
            return getattr(self.plasma, "arena_name", None)
        raise ValueError(f"unknown controller op: {op}")

    def _dispatch_node_ops(self, op: str, payload, caller: "WorkerHandle" = None):
        """Dispatch shard: cluster membership, placement groups, tenants, autoscaling."""
        if op == "add_node":
            resources, labels = payload
            return self.add_node(resources, labels).hex()
        if op == "remove_node":
            from ray_tpu._private.ids import NodeID as _NodeID

            self.remove_node(_NodeID(bytes.fromhex(payload)))
            return True
        if op == "drain_node":
            from ray_tpu._private.ids import NodeID as _NodeID

            node_hex, deadline_s, reason = payload
            return self.drain_node(
                _NodeID(bytes.fromhex(node_hex)),
                deadline_s=float(deadline_s),
                reason=reason or "",
            )
        if op == "drain_status":
            return self.drain_status(payload)
        if op == "node_preempt_notice":
            node_hex, notice_s, reason = payload
            return self.node_preempt_notice(
                node_hex, float(notice_s), reason or ""
            )
        if op == "nodes":
            return self.node_infos()
        if op == "cluster_resources":
            return self.cluster_resources()
        if op == "available_resources":
            return self.available_resources()
        if op == "autoscaler_state":
            # demand younger than 60s + per-node utilization snapshot; each
            # demand entry names the tenant driving it (per-tenant scale-up
            # attribution — the 60s TTL sweep is per (tenant, shape) key)
            now = time.time()
            with self.lock:
                self.pending_demand = {
                    k: t for k, t in self.pending_demand.items() if now - t < 60
                }
                demand = [
                    {"resources": dict(shape), "tenant": tenant}
                    for (tenant, shape) in self.pending_demand
                ]
                nodes = [
                    {
                        "node_id": n.node_id.hex(),
                        "total": dict(n.total),
                        "available": dict(n.available),
                        "labels": dict(n.labels),
                        "idle": not n.leased and not n.actor_leases and all(
                            abs(n.available.get(k, 0) - v) < 1e-9
                            for k, v in n.total.items()
                        ),
                        "alive": n.alive,
                        "draining": n.draining,
                        "preempting": n.preempting,
                    }
                    for n in self.nodes.values()
                ]
            return {"pending_demand": demand, "nodes": nodes}
        if op == "list_workers":
            with self.lock:
                return [
                    {
                        "worker_id": w.worker_id.hex(),
                        "node_id": w.node_id.hex(),
                        "pid": getattr(getattr(w, "proc", None), "pid", None),
                        "running_tasks": len(w.running),
                        "idle": not w.running,
                    }
                    for w in self.workers.values()
                ]
        if op == "pg_create":
            bundles, strategy, name = payload
            return self.create_placement_group(bundles, strategy, name)
        if op == "pg_ready":
            pg_id, timeout = payload
            return self.pg_ready(pg_id, timeout)
        if op == "pg_remove":
            self.remove_placement_group(payload)
            return None
        if op == "pg_table":
            pg = self.placement_groups.get(payload)
            if pg is None:
                return None
            return {
                "bundles": pg.bundles,
                "strategy": pg.strategy,
                "nodes": [n.hex() if n else None for n in pg.bundle_nodes],
                "ready": pg.ready.is_set(),
            }
        if op == "list_placement_groups":
            with self.lock:
                return [
                    {
                        "placement_group_id": pg_id.hex(),
                        "strategy": pg.strategy,
                        "bundles": pg.bundles,
                        "state": (
                            "REMOVED" if pg.removed
                            else "CREATED" if pg.ready.is_set() else "PENDING"
                        ),
                    }
                    for pg_id, pg in self.placement_groups.items()
                ]
        if op == "reconcile_report":
            # a re-attached agent's truth during head recovery: held
            # task/creation leases, alive actors (with incarnations),
            # recently-completed reports, arena inventory — the reply
            # carries the orphan verdicts the agent must reap
            node_hex, report = payload
            return self._apply_reconcile_report(node_hex, report)
        if op == "set_tenant_quota":
            tenant, quota, weight, priority = payload
            return self.set_tenant_quota(
                tenant, quota=quota, weight=weight, priority=priority
            )
        if op == "tenant_stats":
            return self.tenant_stats()
        raise ValueError(f"unknown controller op: {op}")

    def _dispatch_kv_ops(self, op: str, payload, caller: "WorkerHandle" = None):
        """Dispatch shard: the internal KV table (own subsystem lock: controller.kv)."""
        if op == "kv_put":
            ns, key, value = payload
            with self._kv_lock:
                self.kv[(ns, key)] = value
            self._journal("kv_put", (ns, key, value))
            self._persist_kv()
            return None
        if op == "kv_get":
            ns, key = payload
            with self._kv_lock:
                return self.kv.get((ns, key))
        if op == "kv_del":
            ns, key = payload
            with self._kv_lock:
                existed = self.kv.pop((ns, key), None) is not None
            if existed:
                self._journal("kv_del", (ns, key))
                self._persist_kv()
            return existed
        if op == "kv_keys":
            ns, prefix = payload
            with self._kv_lock:
                return [
                    k for (n, k) in self.kv if n == ns and k.startswith(prefix)
                ]
        raise ValueError(f"unknown controller op: {op}")

    def _dispatch_observe_ops(self, op: str, payload, caller: "WorkerHandle" = None):
        """Dispatch shard: logs, pubsub, on-demand profiling, and the
        cluster observability plane (span/metric report ingestion + the
        one-scrape merged metrics / merged-timeline query)."""
        if op == "report_observability":
            # a worker/agent process ships its span ring + util.metrics
            # snapshot; node attribution comes from the payload hint (the
            # agent piggyback stamps its node) or the caller's node table
            # entry (head-process workers land under "head")
            node_hint, entries = payload
            node_label = node_hint
            if node_label is None:
                nid = getattr(caller, "node_id", None)
                node_label = (
                    "head"
                    if nid is None or nid == self.head_node_id
                    else nid.hex()[:12]
                )
            self._apply_observability(node_label, entries)
            return None
        if op == "cluster_metrics":
            # the merged cluster view: {"metrics": node-labeled model} and,
            # when asked, {"spans": shipped + head-local span records} —
            # the state API's timeline()/cluster_metrics() surface
            include = {"metrics"}
            if isinstance(payload, dict) and payload.get("include"):
                include = set(payload["include"])
            out: dict = {}
            if "metrics" in include:
                from ray_tpu.util import metrics as metrics_mod

                self._sync_core_metrics()
                out["metrics"] = metrics_mod.merged_model(
                    self.metrics_agg, local_node="head"
                )
            if "spans" in include:
                from ray_tpu.util import tracing as t
                local = []
                for s in t.get_spans():
                    if s.get("node") is None:
                        s = {**s, "node": "head"}
                    local.append(s)
                with self._span_lock:
                    shipped = list(self._span_store)
                    remote_dropped = self._span_dropped_evicted + sum(
                        self._span_reporter_dropped.values()
                    )
                out["spans"] = shipped + local
                out["dropped_spans"] = (
                    self._span_dropped + t.dropped_spans() + remote_dropped
                )
            return out
        if op == "log_get":
            prefix, source, tail_bytes = payload
            return self._log_fetch(prefix, source, tail_bytes)
        if op == "log_list":
            return self._log_list()
        if op == "log_tail_buffer":
            # most recent captured lines across all workers (state API /
            # dashboard "logs" source)
            n = int(payload or 1000)
            return list(self._log_buffer)[-n:]
        if op == "report_proxy_stats":
            # serve proxies push their admission/shed/byte counters here
            # (one small dict per proxy every ~2 s); ``proxy_stats`` reads
            proxy_id, stats = payload
            with self.lock:
                self._proxy_stats[proxy_id] = {
                    **(stats or {}),
                    "reported_t": time.time(),
                }
            return None
        if op == "proxy_stats":
            # per-proxy ingress counters (accepted/shed/queued/inflight +
            # per-tenant shed); payload optionally filters by proxy-id prefix
            with self.lock:
                return {
                    pid: dict(rec)
                    for pid, rec in self._proxy_stats.items()
                    if payload is None or pid.startswith(payload)
                }
        if op == "recovery_stats":
            # WAL health + recovery phase/counters (ray-tpu recovery CLI)
            return self.recovery_report()
        if op == "pubsub_poll":
            channel, after_seq, timeout = payload
            return self.pubsub_poll(channel, after_seq, min(timeout, 30.0))
        if op == "pubsub_publish":
            channel, event = payload
            self.publish(channel, event)
            return None
        if op == "worker_stacks":
            # on-demand profiling (reference: dashboard reporter py-spy
            # stack dumps): ask worker(s) to dump all thread stacks
            target = payload  # worker id hex prefix, or None = all
            with self.lock:
                handles = [
                    h
                    for h in self.workers.values()
                    if not h.dead
                    and h.conn is not None  # still handshaking: no channel yet
                    and (target is None or h.worker_id.hex().startswith(target))
                ]
            # fan out ALL requests first, then collect with one shared
            # deadline: serial 5s waits would stall this (threaded) handler
            # for 5s x N dead workers. Note the caller itself replies only
            # because this op runs OFF its reader thread.
            pending = []
            out = {}
            for h in handles:
                req_id = next(self._stack_req_counter)
                ev: threading.Event = threading.Event()
                box: list = []
                self._stack_waiters[req_id] = (ev, box)
                try:
                    h.send(P.DumpStacks(req_id))
                    pending.append((h, req_id, ev, box))
                except (OSError, EOFError):
                    self._stack_waiters.pop(req_id, None)
                    out[h.worker_id.hex()] = "<unreachable>"
            deadline = time.monotonic() + 5.0
            for h, req_id, ev, box in pending:
                ev.wait(timeout=max(0.0, deadline - time.monotonic()))
                out[h.worker_id.hex()] = (
                    box[0] if box else "<no response within 5s>"
                )
                self._stack_waiters.pop(req_id, None)
            return out
        raise ValueError(f"unknown controller op: {op}")

    # ------------------------------------------------- observability plane

    def _apply_observability(self, node_label: str, entries) -> None:
        """Fold one node's shipped observability payload into the cluster
        view: metrics snapshots through the aggregator (delta merge,
        replay-idempotent), spans into the bounded store stamped with the
        reporting node."""
        if not entries:
            return
        for entry in entries:
            try:
                reporter = str(entry.get("reporter") or "unknown")
                snap = entry.get("metrics") or []
                if snap:
                    self.metrics_agg.apply(node_label, reporter, snap)
                dropped = entry.get("dropped_spans")
                if isinstance(dropped, (int, float)) and dropped > 0:
                    with self._span_lock:
                        self._span_reporter_dropped.pop(reporter, None)
                        self._span_reporter_dropped[reporter] = float(dropped)
                        while len(self._span_reporter_dropped) > 4096:
                            _, v = self._span_reporter_dropped.popitem(
                                last=False
                            )
                            self._span_dropped_evicted += v
                spans = entry.get("spans") or []
                if spans:
                    with self._span_lock:
                        for s in spans:
                            key = (s.get("span_id"), s.get("start"))
                            if key[0] is not None:
                                if key in self._span_seen:
                                    continue  # replayed report
                                self._span_seen[key] = None
                                while (
                                    self._span_store.maxlen is not None
                                    and len(self._span_seen)
                                    > self._span_store.maxlen
                                ):
                                    self._span_seen.popitem(last=False)
                            if s.get("node") is None:
                                s["node"] = node_label
                            if (
                                self._span_store.maxlen is not None
                                and len(self._span_store)
                                >= self._span_store.maxlen
                            ):
                                self._span_dropped += 1
                            self._span_store.append(s)
            except Exception:  # noqa: BLE001 — a bad entry must not poison the batch
                logger.warning(
                    "malformed observability entry from %s", node_label,
                    exc_info=True,
                )

    def _core_metric_objs(self) -> dict:
        """The util.metrics objects mirroring the controller's ad-hoc stats
        dicts (built lazily so a test's registry clear just re-registers on
        the next scrape)."""
        from ray_tpu.util import metrics as M

        if self._core_metrics is not None and (
            M._registry.get("rtpu_lease_events_total")
            is not self._core_metrics["lease"]
        ):
            # the registry was cleared (test reset) out from under us:
            # rebuild fresh objects and drop the delta baselines so the
            # stats dicts' full cumulative values re-mirror
            self._core_metrics = None
            self._core_metric_last.clear()
        if self._core_metrics is None:
            self._core_metrics = {
                "lease": M.Counter(
                    "rtpu_lease_events_total",
                    "lease-cache / lease-batching counters (lease_stats)",
                    tag_keys=("event",),
                ),
                "transfer": M.Counter(
                    "rtpu_transfer_events_total",
                    "object-transfer plane counters (transfer_stats)",
                    tag_keys=("event",),
                ),
                "actor_creation": M.Counter(
                    "rtpu_actor_creation_events_total",
                    "agent-owned actor-creation lease counters",
                    tag_keys=("event",),
                ),
                "tenant": M.Counter(
                    "rtpu_tenant_events_total",
                    "per-tenant scheduler counters (dispatched, quota_parked, "
                    "preemptions, ...)",
                    tag_keys=("tenant", "event"),
                ),
                "tenant_queued": M.Gauge(
                    "rtpu_tenant_queued",
                    "queued tasks per tenant",
                    tag_keys=("tenant",),
                ),
                "proxy": M.Counter(
                    "rtpu_proxy_events_total",
                    "serve-ingress proxy counters (accepted, shed causes, "
                    "body bytes)",
                    tag_keys=("proxy", "event"),
                ),
                "proxy_gauge": M.Gauge(
                    "rtpu_proxy_gauge",
                    "serve proxy point-in-time values (inflight, queued)",
                    tag_keys=("proxy", "field"),
                ),
                "recovery": M.Counter(
                    "rtpu_recovery_events_total",
                    "head fault-tolerance counters (WAL appends/errors/"
                    "compactions, reconcile asks, leases resumed/replaced, "
                    "actors rebound, orphans reaped)",
                    tag_keys=("event",),
                ),
                "wal_errors": M.Counter(
                    "rtpu_wal_errors",
                    "write-ahead-journal write failures (each one degrades "
                    "durability to snapshot-only — never a silent hole)",
                ),
                "reconstructions": M.Counter(
                    "rtpu_reconstructions_total",
                    "lineage reconstructions: producer tasks resubmitted "
                    "for lost objects",
                ),
                "reconstruction_failures": M.Counter(
                    "rtpu_reconstruction_failures",
                    "lineage reconstructions that could not run (depth cap "
                    "hit, dead producer actor, resubmit raised)",
                ),
                "recovering": M.Gauge(
                    "rtpu_recovering",
                    "1 while the head is in its bounded RECOVERING phase",
                ),
            }
        return self._core_metrics

    def _mirror_counter(self, metric, key: tuple, tags: dict, value: float):
        from ray_tpu.util.metrics import fold_counter_delta

        fold_counter_delta(metric, self._core_metric_last, key, value, tags)

    def _sync_core_metrics(self) -> None:
        """Register the controller's scattered stats counters
        (``lease_stats``, ``transfer_stats``, ``actor_creation_stats``,
        tenant ``dispatched``/``quota_parked``/... + queue depth, serve
        ``proxy_stats``) as REAL util.metrics samples so one ``/metrics``
        scrape carries them. The existing state-API ops stay untouched —
        this mirrors, it does not move."""
        try:
            with self._core_metric_lock:
                self._sync_core_metrics_locked()
        except Exception:  # noqa: BLE001 — a scrape must never take the head down
            logger.warning("core-metrics mirror failed", exc_info=True)

    def _sync_core_metrics_locked(self) -> None:
        m = self._core_metric_objs()
        with self.lock:
            lease = dict(self.lease_stats)
            transfer = dict(self.transfer_stats)
            creation = dict(self.actor_creation_stats)
            tenants = [
                (
                    name,
                    dict(ts.stats),
                    sum(len(q) for q in ts.queues.values()),
                )
                for name, ts in self.tenants.items()
            ]
            proxies = {
                pid: dict(rec) for pid, rec in self._proxy_stats.items()
            }
            recovery = dict(self.recovery_counters)
            recovering = self.recovering
        w = self._wal
        if w is not None:
            recovery["wal_appends"] = w.appends
            recovery["wal_flushes"] = w.flushes
            recovery["wal_bytes_written"] = w.bytes_written
            self._mirror_counter(
                m["wal_errors"], ("wal_errors",), {},
                float(w.errors + recovery.get("wal_errors", 0)),
            )
        elif recovery.get("wal_errors"):
            self._mirror_counter(
                m["wal_errors"], ("wal_errors",), {},
                float(recovery["wal_errors"]),
            )
        m["recovering"].set(1.0 if recovering else 0.0)
        # dedicated reconstruction metrics (the per-event recovery counter
        # carries them too; these are the stable names dashboards key on)
        self._mirror_counter(
            m["reconstructions"], ("reconstructions",), {},
            float(recovery.get("reconstructions", 0)),
        )
        self._mirror_counter(
            m["reconstruction_failures"], ("reconstruction_failures",), {},
            float(recovery.get("reconstruction_failures", 0)),
        )
        for table, mkey in (
            (lease, "lease"),
            (transfer, "transfer"),
            (creation, "actor_creation"),
            (recovery, "recovery"),
        ):
            for ev, v in table.items():
                self._mirror_counter(
                    m[mkey], (mkey, ev), {"event": ev}, float(v)
                )
        for name, stats, queued in tenants:
            for ev, v in stats.items():
                if isinstance(v, (int, float)):
                    self._mirror_counter(
                        m["tenant"], ("tenant", name, ev),
                        {"tenant": name, "event": ev}, float(v),
                    )
            m["tenant_queued"].set(float(queued), tags={"tenant": name})
        for pid, rec in proxies.items():
            for k, v in rec.items():
                if not isinstance(v, (int, float)) or k in ("reported_t", "port"):
                    continue
                if "inflight" in k or "queued" in k:
                    m["proxy_gauge"].set(
                        float(v), tags={"proxy": pid, "field": k}
                    )
                else:
                    self._mirror_counter(
                        m["proxy"], ("proxy", pid, k),
                        {"proxy": pid, "event": k}, float(v),
                    )

    def metrics_text(self) -> str:
        """The one-scrape Prometheus exposition: this process's registry
        (node="head") merged with every shipped node's snapshot (the
        dashboard's /metrics handler)."""
        from ray_tpu.util import metrics as metrics_mod

        self._sync_core_metrics()
        return metrics_mod.export_prometheus_merged(
            self.metrics_agg, local_node="head"
        )

    # ------------------------------------------------------------ dispatching

    def _resolve_args(self, pt: PendingTask):
        """Resolve ref args to transportable payloads. Returns
        (resolved_args, None) or (None, lost_object_id) when a dep is gone
        (the caller must fail the task — resources must NOT be held)."""
        resolved_args = []
        for a in pt.spec.args:
            if a[0] == "ref":
                entry = self.memory_store.get([a[1]], timeout=0)[0]
                if entry is None:
                    return None, a[1]
                kind, payload = entry
                if kind in ("inline", "error"):
                    resolved_args.append((kind, payload.to_bytes()))
                else:
                    resolved_args.append((kind, payload))  # plasma | spilled
            else:
                resolved_args.append(a)
        return resolved_args, None

    def _record_sched_span(self, pt: PendingTask, event: str,
                           node_label: Optional[str] = None) -> None:
        """Head-plane lifecycle span (submit → tenant queue → lease grant /
        dispatch) for a traced spec, recorded into this process's tracing
        ring for SAMPLED tasks (same deterministic verdict as the other
        planes — a sampled task's whole chain exists, head included);
        ``spec.sched_span_id`` is stamped so the downstream plane's span
        parents under this one. Unsampled tasks still get every HEAD EVENT:
        the task_events entries at the dispatch/lease sites carry the
        spec's trace_id, so per-task head history stays trace-joinable at
        zero span-record cost. Deterministic id: ``<task_id>:sched``."""
        spec = pt.spec
        trace_id = getattr(spec, "trace_id", None)
        if trace_id is None:
            return
        from ray_tpu.util import tracing as t
        if not t.sampled(spec.task_id.binary()):
            return
        tid_hex = spec.task_id.hex()
        spec.sched_span_id = f"{tid_hex}:sched"
        t.record_span(
            "head.sched",
            getattr(pt, "submit_t", pt.dispatch_t) or pt.dispatch_t,
            pt.dispatch_t,
            trace_id=trace_id,
            span_id=spec.sched_span_id,
            parent_id=getattr(spec, "parent_span_id", None),
            plane="head",
            task_id=tid_hex,
            node="head",
            task=spec.name,
            event=event,
            target_node=node_label,
        )

    def _dispatch_to_worker(self, worker: WorkerHandle, pt: PendingTask):
        spec = pt.spec
        resolved_args, lost = self._resolve_args(pt)
        if resolved_args is None:
            # Dependency vanished (e.g. freed between restarts and no
            # lineage to rebuild it) — fail rather than crash dispatch.
            from ray_tpu.exceptions import ObjectLostError

            with self.lock:
                self._release_task_resources(pt)
                self._maybe_end_lease_and_idle(worker)
            self._fail_task(pt, ObjectLostError(lost.hex()))
            return
        pt.worker = worker
        pt.dispatch_t = time.time()
        worker.running[spec.task_id] = pt
        self.task_events.append(
            {"task_id": spec.task_id.hex(), "name": spec.name,
             "event": "DISPATCHED", "t": pt.dispatch_t,
             "trace_id": getattr(spec, "trace_id", None),
             "parent_span_id": getattr(spec, "parent_span_id", None),
             "submit_t": pt.submit_t}
        )
        # stamp sched_span_id BEFORE the spec crosses the wire
        self._record_sched_span(pt, "DISPATCHED")
        try:
            worker.send(P.ExecuteTask(spec, resolved_args))
        except (OSError, EOFError):
            self._on_worker_death(worker, reason="send failed")

    def _seal_results(self, results):
        """Seal a completed task's result list (``[(oid, kind, payload)]``)
        into the store — the one sealing loop every completion path shares
        (call OUTSIDE self.lock; store ops take their own locks and
        _on_object_sealed wakes dep-waiters)."""
        for oid, kind, payload in results:
            if kind == "plasma":
                self._seal_plasma(oid, payload[0], payload[1])
            else:
                self.memory_store.put(
                    oid, (kind, SerializedObject.from_buffer(payload))
                )
                self._journal("seal", (oid.binary(), kind, bytes(payload)))
            self._on_object_sealed(oid)

    def _on_agent_task_done(self, agent: AgentHandle, msg: P.AgentTaskDone):
        """Completion of a task the node's agent dispatched locally (the
        head only did placement — two-level scheduling)."""
        with self.lock:
            node = self.nodes.get(agent.node_id)
            pt = node.leased.pop(msg.task_id.binary(), None) if node else None
        if pt is None:
            return
        spec = pt.spec
        failed = any(kind == "error" for _, kind, _ in msg.results)
        if failed and spec.retry_exceptions and pt.retries_left > 0:
            self.task_events.append(
                {"task_id": spec.task_id.hex(), "name": spec.name,
                 "event": "RETRY", "exec_ms": msg.exec_ms, "t": time.time()}
            )
            with self.lock:
                pt.retries_left -= 1
                self._release_task_resources(pt)
                self._enqueue_ready(pt)
                self.sched_cv.notify_all()
            return
        self._seal_results(msg.results)
        self.task_events.append(
            {"task_id": spec.task_id.hex(), "name": spec.name,
             "event": "FAILED" if failed else "FINISHED",
             "exec_ms": msg.exec_ms, "t": time.time()}
        )
        with self.lock:
            if node is not None:
                node.last_task_done_t = time.monotonic()
            self._release_task_resources(pt)
            self.pending_by_id.pop(spec.task_id, None)
            self._unpin_task_deps(pt)
            self._journal("done", spec.task_id.binary())
            # agent lease cache: hand the freed capacity the next queued
            # same-(tenant, shape) spec right here — no scheduler wake, no
            # grant round trip (refused like an over-quota grant when the
            # tenant is capped or another tenant is waiting)
            self._maybe_rearm_locked(node, agent, spec)
            self._flush_lease_outbox_locked()
            self.sched_cv.notify_all()
        self._persist_state()

    def _on_task_spilled(self, agent: AgentHandle, msg: P.TaskSpilled):
        """The agent handed leased tasks back (overload or worker death):
        re-place them, preferring other nodes (spillback, the reference's
        hybrid-policy SPILLBACK lease reply)."""
        failed: list = []
        with self.lock:
            node = self.nodes.get(agent.node_id)
            if node is None:
                return
            for tid_b in msg.task_ids:
                pt = node.leased.pop(tid_b, None)
                if pt is None:
                    continue
                self._journal("unlease", tid_b)
                self._release_task_resources(pt)
                if msg.reason == "worker_died":
                    if pt.retries_left <= 0:
                        failed.append(pt)
                        continue
                    pt.retries_left -= 1
                pt._avoid_node = agent.node_id  # type: ignore[attr-defined]
                self._enqueue_ready(pt)
            self.sched_cv.notify_all()
        for pt in failed:
            self._fail_task(
                pt, WorkerCrashedError("worker died (leased task, no retries left)")
            )

    def _on_task_done(self, worker: WorkerHandle, msg: P.TaskDone):
        with self.lock:
            pt = worker.running.pop(msg.task_id, None)
        if pt is None:
            return
        spec = pt.spec
        failed = any(kind == "error" for _, kind, _ in msg.results)
        if (
            failed
            and spec.retry_exceptions
            and pt.retries_left > 0
            and not spec.is_actor_creation()
        ):
            # application-error retry (reference: retry_exceptions,
            # task_manager.cc): don't seal the error — resubmit the task and
            # let blocked getters keep waiting on the same return ids
            self._retry_failed_task(worker, pt, msg)
            return
        self._seal_results(msg.results)
        self.task_events.append(
            {
                "task_id": spec.task_id.hex(),
                "name": spec.name,
                "event": "FAILED" if failed else "FINISHED",
                "exec_ms": msg.exec_ms,
                "t": time.time(),
            }
        )
        with self.lock:
            if not spec.is_actor_creation() or failed:
                # Actors hold their resources for their lifetime (released on
                # actor death); everything else releases at task completion.
                self._release_task_resources(pt)
            self.pending_by_id.pop(spec.task_id, None)
            self._stream_consumed.pop(spec.task_id, None)
            self._unpin_task_deps(pt)
            self._journal("done", spec.task_id.binary())
            if spec.is_actor_creation():
                actor = self.actors.get(spec.actor_id)
                if actor is not None:
                    if failed:
                        actor.state = "DEAD"
                        actor.death_cause = (
                            "creation task failed"
                            + self._creation_error_text(msg.results)
                        )
                        self._journal("actor_dead", actor.actor_id.binary())
                        self.publish("actors", {"actor_id": actor.actor_id.hex(), "state": "DEAD", "reason": "creation task failed"})
                        self._drain_actor_queue(actor)
                        # the worker survives a raising __init__ — back to
                        # the pool, not a leaked cap slot
                        if not worker.dead and worker.actor_id is None:
                            worker.last_idle_t = time.monotonic()
                            self.idle_workers[worker.node_id].append(worker)
                            self._pool_worker_freed(worker)
                    else:
                        actor.state = "ALIVE"
                        actor.worker = worker
                        self.publish("actors", {"actor_id": actor.actor_id.hex(), "state": "ALIVE"})
                        actor.held = (getattr(pt, "_node", None), getattr(pt, "_pg_bundle", None), dict(spec.resources))
                        worker.actor_id = actor.actor_id
                        # actor workers' log lines carry the class label
                        self._register_log_meta(
                            worker.worker_id,
                            label=(spec.name or "").rsplit(".", 1)[0] or None,
                        )
                        # dedicated to the actor now — no longer a pooled worker
                        self._uncount_pooled(worker)
                        self._pump_actor(actor)
            elif spec.is_actor_task():
                actor = self.actors.get(spec.actor_id)
                if actor is not None:
                    actor.inflight -= 1
                    self._pump_actor(actor)
            else:
                # Normal task: worker returns to the idle pool once its
                # pipelined queue drains (the lease holds until then).
                self._maybe_end_lease_and_idle(worker)
            self.sched_cv.notify_all()
        self._persist_state()

    def _creation_error_text(self, results) -> str:
        """': <the exception __init__ raised>' for an actor's death cause, so
        that callers of a dead actor read why (a TPU grant without the chip,
        say) and not only that it died."""
        try:
            payload = next(p for _, kind, p in results if kind == "error")
            err = self.serialization.deserialize(
                SerializedObject.from_buffer(payload)
            )
            return f": {getattr(err, 'cause', err)!r}"
        except Exception:  # noqa: BLE001 — the cause is best-effort detail
            return ""

    def _retry_failed_task(self, worker: WorkerHandle, pt: PendingTask, msg: P.TaskDone):
        spec = pt.spec
        self.task_events.append(
            {
                "task_id": spec.task_id.hex(),
                "name": spec.name,
                "event": "RETRY",
                "exec_ms": msg.exec_ms,
                "t": time.time(),
            }
        )
        with self.lock:
            pt.retries_left -= 1
            self._release_task_resources(pt)
            if spec.is_actor_task():
                actor = self.actors.get(spec.actor_id)
                if actor is not None:
                    actor.inflight -= 1
                    actor.queue.appendleft(pt)  # preserve ordering
                    self._pump_actor(actor)
            else:
                self._maybe_end_lease_and_idle(worker)
                self._enqueue_ready(pt)
            self.sched_cv.notify_all()
        logger.warning(
            "task %s raised; retrying (%d retries left, retry_exceptions)",
            spec.name, pt.retries_left,
        )

    def _release_task_resources(self, pt: PendingTask):
        node = getattr(pt, "_node", None)
        pg_bundle = getattr(pt, "_pg_bundle", None)
        if pg_bundle is not None:
            # mirror of _try_place: bundle tasks never charged the node
            pg, i = pg_bundle
            for k, v in pt.spec.resources.items():
                pg.bundle_available[i][k] = pg.bundle_available[i].get(k, 0.0) + v
            pt._pg_bundle = None
            pt._node = None
            self._tenant_credit(self._tenant_for(pt.spec), pt.spec.resources)
        elif node is not None:
            node.release(pt.spec.resources)
            pt._node = None
            self._tenant_credit(self._tenant_for(pt.spec), pt.spec.resources)

    def _unpin(self, object_id: ObjectID):
        self.ref_counts[object_id] -= 1
        if self.ref_counts[object_id] <= 0:
            del self.ref_counts[object_id]
            self._free_object(object_id)

    # --------------------------------------------------------------- failures

    def _on_worker_death(self, worker: WorkerHandle, reason: str):
        with self.lock:
            if worker.dead:
                return
            worker.dead = True
            self.workers.pop(worker.worker_id, None)
            # an actor_placed report racing behind this death must not bind
            # an actor to the corpse (bounded ring; see _on_actor_placed)
            self._recently_dead_workers[worker.worker_id] = None
            while len(self._recently_dead_workers) > 512:
                self._recently_dead_workers.popitem(last=False)
            self._uncount_pooled(worker)
            self._end_lease(worker)
            pool = self.idle_workers.get(worker.node_id)
            if pool and worker in pool:
                pool.remove(worker)
            running = list(worker.running.values())
            worker.running.clear()
        requeue: list[PendingTask] = []
        for pt in running:
            with self.lock:
                self._release_task_resources(pt)
            if pt.spec.is_actor_task():
                with self.lock:
                    actor = self.actors.get(pt.spec.actor_id)
                    if actor is not None:
                        actor.inflight = max(0, actor.inflight - 1)
                    retriable = (
                        pt.retries_left > 0
                        and actor is not None
                        and actor.state != "DEAD"
                        and actor.restarts_left != 0
                    )
                if retriable:
                    # max_retries on an actor method survives the worker's
                    # death: re-queue ahead of everything and run after the
                    # actor restarts (reference: max_task_retries,
                    # task_manager.cc actor-task resubmit)
                    pt.retries_left -= 1
                    pt.worker = None
                    requeue.append(pt)
                else:
                    self._fail_task(pt, ActorDiedError(pt.spec.actor_id.hex(), reason))
            elif pt.retries_left > 0:
                pt.retries_left -= 1
                pt.worker = None
                logger.warning(
                    "retrying task %s after worker death (%d retries left)",
                    pt.spec.name,
                    pt.retries_left,
                )
                with self.lock:
                    self._enqueue_ready(pt)
                    self.sched_cv.notify_all()
            else:
                self._fail_task(pt, WorkerCrashedError(f"worker died: {reason}"))
        if requeue:
            with self.lock:
                # reversed appendleft restores dispatch order at the front
                for pt in reversed(requeue):
                    actor = self.actors.get(pt.spec.actor_id)
                    if actor is not None:
                        actor.queue.appendleft(pt)
                self.sched_cv.notify_all()
        if worker.actor_id is not None:
            self._on_actor_worker_death(worker.actor_id, reason)

    def _on_actor_worker_death(self, actor_id: ActorID, reason: str):
        with self.lock:
            actor = self.actors.get(actor_id)
            if actor is None or actor.state == "DEAD":
                return
            actor.worker = None
            actor.inflight = 0
            self._release_actor_resources(actor)
            self._journal("unplaced", actor_id.binary())
            migrating = getattr(actor, "_drain_migrating", False)
            actor._drain_migrating = False
            actor._drain_hold = False
            actor._preempting = False  # a preemption victim completed its kill
            if actor.restarts_left != 0:
                if actor.restarts_left > 0 and not migrating:
                    # a drain-driven migration is a controlled respawn, not a
                    # failure — it must not consume the restart budget
                    actor.restarts_left -= 1
                    # journal the charge: with a healthy WAL the per-mutation
                    # snapshot flusher is off, and a replayed "submit" record
                    # would otherwise refill the budget after a head restart
                    self._journal(
                        "restarts",
                        (actor_id.binary(), actor.restarts_left),
                    )
                actor.state = "RESTARTING"
                self.publish("actors", {"actor_id": actor.actor_id.hex(), "state": "RESTARTING", "reason": reason})
                # Re-pin creation args for the restart run (the original pins
                # were released when the first creation task completed).
                deps = {a[1] for a in actor.creation_spec.args if a[0] == "ref"}
                creation = PendingTask(actor.creation_spec, deps)
                for d in deps:
                    self.ref_counts[d] += 1
                unresolved = {d for d in deps if not self.memory_store.contains(d)}
                creation.unresolved = unresolved
                self.pending_by_id[actor.creation_spec.task_id] = creation
                if unresolved:
                    for d in unresolved:
                        self.waiting_on_deps[d].append(creation)
                else:
                    self._enqueue_ready(creation)
                self.sched_cv.notify_all()
            else:
                actor.state = "DEAD"
                actor.death_cause = reason
                self._journal("actor_dead", actor_id.binary())
                self.publish("actors", {"actor_id": actor.actor_id.hex(), "state": "DEAD", "reason": reason})
                self._drain_actor_queue(actor)
                self._persist_state()

    def _release_actor_resources(self, actor: ActorState):
        if actor.held is None:
            return
        node, pg_bundle, resources = actor.held
        actor.held = None
        if pg_bundle is not None:
            # bundle-scheduled actors never charged the node (see _try_place)
            pg, i = pg_bundle
            for k, v in resources.items():
                pg.bundle_available[i][k] = pg.bundle_available[i].get(k, 0.0) + v
        elif node is not None:
            node.release(resources)
        self._tenant_credit(
            self._tenant_for(actor.creation_spec), resources
        )

    def _drain_actor_queue(self, actor: ActorState):
        while actor.queue:
            pt = actor.queue.popleft()
            self._fail_task(pt, ActorDiedError(actor.actor_id.hex(), actor.death_cause or "actor died"))

    def _fail_pending_for_env(self, fingerprint: tuple, error: Exception):
        """Fail every still-queued task whose runtime env resolves to the
        fingerprint whose worker environment could not be built — the
        RuntimeEnvSetupError-surfaces-on-the-task contract (reference:
        runtime-env agent setup failure handling)."""
        from ray_tpu.exceptions import RuntimeEnvSetupError

        if not isinstance(error, RuntimeEnvSetupError):
            error = RuntimeEnvSetupError(str(error))
        with self.lock:
            doomed = [
                pt
                for pt in self.pending_by_id.values()
                if pt.worker is None
                and self._env_fingerprint(pt.spec) == fingerprint
            ]
            for pt in doomed:
                # cancelled gates the ready queues + dep-wakeup dispatch —
                # without it the queue entry survives _fail_task's
                # pending_by_id pop and the scheduler respawns the doomed
                # env (full venv build) every round, forever
                pt.cancelled = True
        for pt in doomed:
            self._fail_task(pt, error)
        if doomed:
            with self.lock:
                self.sched_cv.notify_all()

    def _fail_task(self, pt: PendingTask, error: Exception):
        sobj = self.serialization.serialize(
            TaskError(pt.spec.name, error) if not isinstance(error, TaskError) else error
        )
        if (
            self._wal is not None
            and not self._wal_suppress
            and self._wal.healthy
        ):
            blob = sobj.to_bytes()
            for oid in pt.spec.return_ids():
                self._journal("seal", (oid.binary(), "error", blob))
        for oid in pt.spec.return_ids():
            self.memory_store.put(oid, ("error", sobj))
            self._on_object_sealed(oid)
        with self.lock:
            self.pending_by_id.pop(pt.spec.task_id, None)
            # a resubmitted producer failing TERMINALLY must leave the
            # recovery set even when no return-id seal reached
            # _on_object_sealed (zero-return specs, seal races) — a leaked
            # entry blocks every future reconstruction of its objects
            self._recovering.discard(pt.spec.task_id)
            self._recon_depth.pop(pt.spec.task_id, None)
            self._unpin_task_deps(pt)
            self._journal("done", pt.spec.task_id.binary())

    def _unpin_task_deps(self, pt: PendingTask):
        """Release the submission-time pins on a task's args exactly once."""
        if getattr(pt, "_deps_unpinned", False):
            return
        pt._deps_unpinned = True
        for d in pt.all_deps:
            self._unpin(d)

    # ----------------------------------------------------------------- actors

    def _on_actor_placed(
        self, agent: AgentHandle, actor_id: ActorID, worker_id: WorkerID,
        direct_address, results, exec_ms,
    ):
        """An agent finished a creation lease: the worker spawned,
        registered (its RegisterWorker relay precedes this report on the
        agent's FIFO connection, so the head already tracks its identity +
        direct-call address), and ran the creation task successfully. The
        lease's resource charge transfers to ``actor.held``."""
        tid = TaskID.for_actor_creation(actor_id)
        with self.lock:
            node = self.nodes.get(agent.node_id)
            actor = self.actors.get(actor_id)
            pt = node.actor_leases.pop(tid.binary(), None) if node else None
            if actor is None or actor.state == "DEAD":
                # killed mid-creation: reclaim the grant charge; the agent
                # reaps the just-created worker
                if pt is not None:
                    self._release_task_resources(pt)
                    self.pending_by_id.pop(tid, None)
                    self._unpin_task_deps(pt)
                return "dead"
            if pt is None:
                # duplicate report (the agent retried after a transport
                # error that lost only our reply): idempotent
                w = actor.worker
                if (
                    actor.state == "ALIVE"
                    and w is not None
                    and w.worker_id == worker_id
                ):
                    return "ok"
                return "dead"  # superseded: the lease was re-placed
            if worker_id in self._recently_dead_workers:
                # the worker died before this report was processed: the
                # actor never went ALIVE, so re-place WITHOUT charging the
                # restart budget
                self._release_task_resources(pt)
                pt._avoid_node = agent.node_id  # type: ignore[attr-defined]
                self._enqueue_ready(pt)
                self.actor_creation_stats["lease_retries"] += 1
                self.sched_cv.notify_all()
                return "dead"
            handle = self.workers.get(worker_id)
            if handle is None:
                # registration relay raced behind / handle already reaped:
                # recreate the identity-tracking handle (relay transport)
                handle = WorkerHandle(
                    worker_id, agent.node_id,
                    conn=_RelayConn(agent, worker_id),
                )
                handle.agent = agent
                handle.agent_owned = True
                handle.registered.set()
                self.workers[worker_id] = handle
            if direct_address and not handle.direct_address:
                handle.direct_address = direct_address
        # seal the creation task's results outside the lock (store ops take
        # their own locks; mirrors _on_agent_task_done)
        self._seal_results(results)
        spec = pt.spec
        self.task_events.append(
            {"task_id": spec.task_id.hex(), "name": spec.name,
             "event": "FINISHED", "exec_ms": exec_ms, "t": time.time()}
        )
        with self.lock:
            # re-validate: a kill or the worker's death may have landed in
            # the unlocked sealing window — binding ALIVE over either would
            # resurrect a killed actor or marry it to a corpse forever
            if actor.state == "DEAD":
                self._release_task_resources(pt)
                self.pending_by_id.pop(spec.task_id, None)
                self._unpin_task_deps(pt)
                return "dead"
            if handle.dead or worker_id in self._recently_dead_workers:
                # worker died before the bind: re-place, budget untouched
                self._release_task_resources(pt)
                pt._avoid_node = agent.node_id  # type: ignore[attr-defined]
                self._enqueue_ready(pt)
                self.actor_creation_stats["lease_retries"] += 1
                self.sched_cv.notify_all()
                return "dead"
            self.pending_by_id.pop(spec.task_id, None)
            self._unpin_task_deps(pt)
            actor.state = "ALIVE"
            actor.worker = handle
            handle.actor_id = actor_id
            # the charge made at grant time is now held for the actor's
            # lifetime (released by _release_actor_resources on death)
            actor.held = (
                getattr(pt, "_node", None),
                getattr(pt, "_pg_bundle", None),
                dict(spec.resources),
            )
            pt._node = None  # type: ignore[attr-defined]
            pt._pg_bundle = None  # type: ignore[attr-defined]
            self.actor_creation_stats["placed"] += 1
            self.publish(
                "actors", {"actor_id": actor_id.hex(), "state": "ALIVE"}
            )
            self._register_log_meta(
                worker_id, label=(spec.name or "").rsplit(".", 1)[0] or None
            )
            self._journal("done", spec.task_id.binary())
            self._journal(
                "placed",
                (
                    actor_id.binary(), agent.node_id.hex(),
                    worker_id.binary(), handle.direct_address,
                ),
            )
            self._pump_actor(actor)
            self.sched_cv.notify_all()
        self._persist_state()
        return "ok"

    def _on_actor_creation_failed(
        self, agent: AgentHandle, actor_id: ActorID, reason: str,
        retryable: bool, results, exec_ms,
    ):
        """An agent could not place a leased actor. Budget policy:

        - drain race (``reason == "draining"``): free re-place — a
          controlled migration, never charged;
        - other retryable infra failures (worker died mid-creation, spawn
          or registration failed): consume the restart budget like any
          post-ALIVE death, then re-place; budget exhausted → DEAD;
        - non-retryable (the creation task itself raised): terminal — the
          error seals into the creation returns and the actor dies.
        """
        tid = TaskID.for_actor_creation(actor_id)
        with self.lock:
            node = self.nodes.get(agent.node_id)
            actor = self.actors.get(actor_id)
            pt = node.actor_leases.pop(tid.binary(), None) if node else None
            if pt is None:
                return  # duplicate, or the lease was reclaimed (kill/node death)
            self._release_task_resources(pt)
            if actor is None or actor.state == "DEAD":
                self.pending_by_id.pop(tid, None)
                self._unpin_task_deps(pt)
                return
            requeue = retryable and (
                reason == "draining" or actor.restarts_left != 0
            )
            self._journal("unlease", tid.binary())
            if requeue:
                if reason != "draining" and actor.restarts_left > 0:
                    actor.restarts_left -= 1
                    self._journal(
                        "restarts",
                        (actor_id.binary(), actor.restarts_left),
                    )
                pt._avoid_node = agent.node_id  # type: ignore[attr-defined]
                self._enqueue_ready(pt)
                self.actor_creation_stats["lease_retries"] += 1
                self.task_events.append(
                    {"task_id": pt.spec.task_id.hex(), "name": pt.spec.name,
                     "event": "RETRY", "exec_ms": exec_ms, "t": time.time()}
                )
                self.sched_cv.notify_all()
                return
        # terminal: seal the failure into the creation returns (the agent
        # forwards the raising __init__'s error payloads when it has them)
        if results:
            self._seal_results(results)
        else:
            err = self.serialization.serialize(
                TaskError(
                    pt.spec.name, ActorDiedError(actor_id.hex(), reason)
                )
            )
            for oid in pt.spec.return_ids():
                self.memory_store.put(oid, ("error", err))
                self._on_object_sealed(oid)
        self.task_events.append(
            {"task_id": pt.spec.task_id.hex(), "name": pt.spec.name,
             "event": "FAILED", "exec_ms": exec_ms, "t": time.time()}
        )
        with self.lock:
            self.pending_by_id.pop(tid, None)
            self._unpin_task_deps(pt)
            actor.state = "DEAD"
            actor.death_cause = reason
            self._journal("done", tid.binary())
            self._journal("actor_dead", actor_id.binary())
            self.actor_creation_stats["failed"] += 1
            self.publish(
                "actors",
                {"actor_id": actor_id.hex(), "state": "DEAD",
                 "reason": reason},
            )
            self._drain_actor_queue(actor)
            self.sched_cv.notify_all()
        self._persist_state()

    def register_actor(self, spec: TaskSpec, name: Optional[str] = None) -> ActorState:
        """Register + submit an actor creation under ONE lock hold (the old
        register-then-submit_task path took the controller lock twice per
        creation — measurable at the 1000-actor envelope). Idempotent on a
        replayed creation (coalesced-batch retry): returns the existing
        state. Validation runs BEFORE registration so a rejected runtime
        env doesn't leave a phantom DEAD-less actor behind."""
        self._validate_runtime_env(spec)
        with self.lock:
            existing = self.actors.get(spec.actor_id)
            if existing is not None:
                return existing
            if name and name in self.named_actors:
                raise ValueError(f"actor name {name!r} already taken")
            actor = ActorState(spec.actor_id, spec)
            actor.name = name
            self.actors[spec.actor_id] = actor
            if name:
                self.named_actors[name] = spec.actor_id
            self._submit_one_locked(spec)
            self.sched_cv.notify_all()
        self._journal("submit", (spec, name))
        self._persist_state()
        return actor

    def get_named_actor(self, name: str) -> Optional[ActorID]:
        with self.lock:
            return self.named_actors.get(name)

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        with self.lock:
            actor = self.actors.get(actor_id)
            if actor is None:
                return
            if no_restart:
                actor.restarts_left = 0
            worker = actor.worker
        if worker is not None:
            try:
                worker.send(P.KillActor(actor_id))
            except (OSError, EOFError):
                pass
            # Process-mode: terminate outright (SIGKILL analog of ray.kill).
            if worker.proc is not None:
                worker.proc.terminate()
            elif worker.agent is not None:
                try:
                    worker.agent.send(P.KillWorker(worker.worker_id))
                except (OSError, EOFError):
                    pass
        with self.lock:
            if no_restart:
                actor = self.actors.get(actor_id)
                if actor is not None:
                    actor.state = "DEAD"
                    actor.death_cause = "killed via ray_tpu.kill"
                    self._journal("actor_dead", actor_id.binary())
                    self.publish("actors", {"actor_id": actor_id.hex(), "state": "DEAD", "reason": "killed via ray_tpu.kill"})
                    self._release_actor_resources(actor)
                    self._drain_actor_queue(actor)
                    if actor.name:
                        self.named_actors.pop(actor.name, None)
                    # a creation lease still in flight holds the grant
                    # charge: reclaim it now; when the agent's report
                    # arrives the "dead" verdict reaps the orphan worker
                    tid_b = TaskID.for_actor_creation(actor_id).binary()
                    for n in self.nodes.values():
                        pt = n.actor_leases.pop(tid_b, None)
                        if pt is not None:
                            self._release_task_resources(pt)
                            self.pending_by_id.pop(pt.spec.task_id, None)
                            self._unpin_task_deps(pt)
        self._persist_state()

    def cancel_task(self, object_id: ObjectID):
        task_id = object_id.task_id()
        with self.lock:
            pt = self.pending_by_id.get(task_id)
            if pt is None:
                return
            pt.cancelled = True
            if pt.worker is None:
                from ray_tpu.exceptions import TaskCancelledError

                self._fail_task(pt, TaskCancelledError(f"task {pt.spec.name} cancelled"))

    # ------------------------------------------------------- placement groups

    def create_placement_group(
        self, bundles: list[dict], strategy: str, name: str = ""
    ) -> PlacementGroupID:
        pg_id = PlacementGroupID.from_random()
        pg = PlacementGroupState(pg_id, bundles, strategy)
        with self.lock:
            self.placement_groups[pg_id] = pg
            self._try_place_pg(pg)
        self._journal("pg", (pg_id, list(bundles), strategy))
        self._persist_state()
        return pg_id

    def _try_place_pg(self, pg: PlacementGroupState):
        """All-or-nothing bundle reservation (2-phase commit analog;
        reference: ``gcs_placement_group_scheduler.h`` PACK/SPREAD/STRICT_*)."""
        alive = [n for n in self.nodes.values() if n.schedulable]
        assignment: list[Optional[NodeState]] = [None] * len(pg.bundles)
        scratch = {n.node_id: dict(n.available) for n in alive}

        def fits(nid, demand):
            a = scratch[nid]
            return all(a.get(k, 0.0) + 1e-9 >= v for k, v in demand.items())

        def take(nid, demand):
            a = scratch[nid]
            for k, v in demand.items():
                a[k] = a.get(k, 0.0) - v

        strategy = pg.strategy
        if strategy in ("STRICT_PACK", "PACK"):
            # Try to land all bundles on one node first.
            total: dict[str, float] = {}
            for b in pg.bundles:
                for k, v in b.items():
                    total[k] = total.get(k, 0.0) + v
            for n in sorted(alive, key=lambda n: -n.utilization()):
                if n.fits(total):
                    assignment = [n] * len(pg.bundles)
                    take(n.node_id, total)
                    break
            if assignment[0] is None and strategy == "STRICT_PACK":
                return False
        if assignment[0] is None:
            # Greedy per-bundle placement.
            used_nodes: set[NodeID] = set()
            for i, b in enumerate(pg.bundles):
                candidates = [n for n in alive if fits(n.node_id, b)]
                if strategy == "STRICT_SPREAD":
                    candidates = [n for n in candidates if n.node_id not in used_nodes]
                if not candidates:
                    return False
                if strategy in ("SPREAD", "STRICT_SPREAD"):
                    pick = min(candidates, key=lambda n: (n.node_id in used_nodes, n.utilization()))
                else:
                    pick = max(candidates, key=lambda n: n.utilization())
                assignment[i] = pick
                used_nodes.add(pick.node_id)
                take(pick.node_id, b)
        # Commit.
        for i, (node, b) in enumerate(zip(assignment, pg.bundles)):
            node.allocate(b)
            pg.bundle_nodes[i] = node.node_id
            pg.bundle_available[i] = dict(b)
        pg.ready.set()
        return True

    def remove_placement_group(self, pg_id: PlacementGroupID):
        with self.lock:
            pg = self.placement_groups.get(pg_id)
            if pg is None or pg.removed:
                return
            pg.removed = True
            for i, nid in enumerate(pg.bundle_nodes):
                if nid is None:
                    continue
                node = self.nodes.get(nid)
                if node is not None:
                    node.release(pg.bundles[i])
        self._journal("pg_remove", pg_id)
        self._persist_state()

    def pg_ready(self, pg_id: PlacementGroupID, timeout=None) -> bool:
        with self.lock:
            pg = self.placement_groups.get(pg_id)
            if pg is None:
                raise PlacementGroupSchedulingError("unknown placement group")
            if not pg.ready.is_set():
                self._try_place_pg(pg)
        return pg.ready.wait(timeout=timeout if timeout is not None else 1e9)

    # ------------------------------------------------------------------ state

    def cluster_resources(self) -> dict[str, float]:
        with self.lock:
            out: dict[str, float] = {}
            for n in self.nodes.values():
                if not n.alive:
                    continue
                for k, v in n.total.items():
                    out[k] = out.get(k, 0.0) + v
            return out

    def available_resources(self) -> dict[str, float]:
        with self.lock:
            out: dict[str, float] = {}
            for n in self.nodes.values():
                if not n.alive:
                    continue
                for k, v in n.available.items():
                    out[k] = out.get(k, 0.0) + v
            return out

    def node_infos(self) -> list[dict]:
        with self.lock:
            return [
                {
                    "NodeID": n.node_id.hex(),
                    "Alive": n.alive,
                    "Resources": dict(n.total),
                    "Available": dict(n.available),
                    "Labels": dict(n.labels),
                    "Draining": n.draining,
                    "DrainState": (
                        self.drains[n.node_id]["state"]
                        if n.node_id in self.drains
                        else None
                    ),
                }
                for n in self.nodes.values()
            ]

    # -------------------------------------------------------------- lifecycle

    def shutdown(self):
        with self.lock:
            if self.shutting_down:
                return
            self.shutting_down = True
            workers = list(self.workers.values())
            drivers = list(self.driver_conns.values())
            agents = list(self.agents.values())
            self.agents.clear()
            self.sched_cv.notify_all()
        for a in agents:
            try:
                a.send(P.Shutdown())
            except (OSError, EOFError):
                pass
            try:
                a.conn.close()
            except (OSError, EOFError):
                pass
        self._data_pool.close()
        if self.memory_monitor is not None:
            self.memory_monitor.stop()
        # stop the background KV flusher BEFORE the final synchronous flush —
        # a flusher mid-write could otherwise land its (now stale) snapshot
        # after the final one. Its dirty-wait is bounded at 1 s and the loop
        # re-checks shutting_down right after it, so this join is bounded too
        # (waking it via _kv_dirty would instead force one more full —
        # redundant — snapshot write before the loop notices shutdown).
        locktrace.join_if_alive(self._kv_flusher, timeout=2.0)
        self.flush_kv_now()
        self._remove_session_file()
        # attached clients must not hang in _await_reply forever
        for d in drivers:
            try:
                d.send(P.Shutdown())
                d.conn.close()
            except (OSError, EOFError):
                pass
        for w in workers:
            try:
                if w.conn is not None:
                    w.send(P.Shutdown())
            except (OSError, EOFError):
                pass
        deadline = time.monotonic() + 2.0
        for w in workers:
            if w.proc is not None:
                try:
                    w.proc.wait(timeout=max(0.05, deadline - time.monotonic()))
                except Exception:
                    w.proc.kill()
        # chip holders, the already-killed ones included, are waited out:
        # the chips must be free for whatever this host runs next
        self._chips.drain()
        if self.listener is not None:
            try:
                self.listener.close()
            except OSError:
                pass
            try:
                os.unlink(self.address)
            except OSError:
                pass
        if self._tcp_listener is not None:
            try:
                self._tcp_listener.close()
            except OSError:
                pass
        for store in {id(s): s for s in self.node_stores.values()}.values():
            try:
                store.shutdown()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        # reclaim the session's spill files (objects die with the cluster)
        import shutil as _shutil

        _shutil.rmtree(self.spill_dir, ignore_errors=True)
        self.plasma_client.close()
        self._reply_pool.shutdown(wait=False)


