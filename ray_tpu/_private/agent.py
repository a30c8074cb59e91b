"""Node agent: the per-host daemon that makes multi-host real.

Analog of the reference's raylet (``src/ray/raylet/node_manager.h:124``) +
per-node plasma store + object manager (``object_manager.h:119``), started
with ``ray-tpu start --address=<head>`` (reference:
``python/ray/scripts/scripts.py:226`` ``ray start``). One agent per host:

- registers its host's resources with the head controller as a REAL node
  over the TCP control plane;
- owns a local plasma arena (C++ store) — the node's data plane. Workers on
  this host attach ONLY this arena; objects cross hosts via the chunked
  pull protocol, never shared memory;
- spawns/supervises worker processes on demand (remote half of
  ``WorkerPool::StartWorkerProcess``, ``worker_pool.h:283``) and relays
  their control-plane traffic to the head through ``FromWorker``/``ToWorker``
  envelopes;
- serves chunk reads of its resident objects to peers (controller, client
  drivers, other agents) over a TCP data listener (``ObjectManager::Push``
  analog, chunked as in ``object_buffer_pool.h``);
- heartbeats; on head-connection loss it tears down its workers.

Worker processes are completely unaware of the agent: they speak the same
unix-socket protocol as head-local workers. The agent intercepts only the
node-local data-plane ops (``shm_create`` allocation, plasma seals inside
``PutObject``/``TaskDone``, ``pull_object_chunk``) and forwards the rest.
"""

from __future__ import annotations

import argparse
import itertools
import json
import logging
import os
import socket
import subprocess
import sys
import threading
import time
import traceback
import zipfile
from collections import defaultdict
from io import BytesIO
from multiprocessing.connection import Client, Listener
from typing import Any, Optional

from ray_tpu._private import locktrace
from ray_tpu._private import protocol as P
from ray_tpu._private.ids import NodeID, ObjectID, WorkerID
from ray_tpu.tpu.accelerator import ChipPool, chip_worker_env, chips_requested

logger = logging.getLogger("ray_tpu.agent")

_CHUNK = 4 * 1024**2


class AgentError(RuntimeError):
    pass


class NodeAgent:
    def __init__(
        self,
        address: str,
        authkey: bytes,
        resources: Optional[dict] = None,
        labels: Optional[dict] = None,
        base_dir: Optional[str] = None,
        object_store_memory: int = 1 * 1024**3,
        data_port: int = 0,
        node_ip: Optional[str] = None,
    ):
        self.node_id = NodeID.from_random()
        self.authkey = authkey
        self.head_address = address
        self.resources = dict(resources or {"CPU": float(os.cpu_count() or 1)})
        self._chips = ChipPool(chips_requested(self.resources))
        self.labels = dict(labels or {})
        self.base_dir = base_dir or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), f"rtpu-agent-{os.getpid()}"
        )
        os.makedirs(self.base_dir, mode=0o700, exist_ok=True)
        self.node_ip = node_ip or P.routable_host()
        self.shutting_down = False
        # Quiesce handshake (reference: DrainRaylet): while True, new leases
        # are spilled back instead of queued and a watcher reports
        # AgentDrained once local work is finished and logs are flushed.
        self.draining = False

        # Local data plane: this node's arena (native C++ store required —
        # cross-host pulls need arena-format locations).
        from ray_tpu._native import plasma as native_plasma
        from ray_tpu._private.object_store import NativePlasmaStore

        if not native_plasma.available():
            raise AgentError(
                "node agents require the native plasma store (g++ build); "
                "the Python fallback store cannot serve cross-host pulls"
            )
        self.arena_name = f"/rtpu-a{os.getpid():x}-{time.time_ns() & 0xFFFFFF:x}"
        self._store_capacity = object_store_memory
        self.store = NativePlasmaStore(object_store_memory, self.arena_name)

        # Workers on this host.
        self.workers: dict[WorkerID, dict] = {}  # wid -> {conn, proc, lock}
        self.workers_lock = locktrace.register_lock(
            "agent.workers_lock", threading.Lock()
        )
        # kills that arrived before their spawn finished
        self._pending_kills: set[WorkerID] = set()
        # agent-side rpc chaos for our own controller calls (the lease
        # report channel rides these) — lazily parsed from
        # RAY_TPU_WORKER_RPC_FAILURE, catalog-validated like the worker's
        self._chaos_table: Optional[dict] = None
        import random as _random

        self._chaos_rng = _random.Random(
            int.from_bytes(self.node_id.binary()[:4], "little")
        )

        # ---- head fault tolerance (PR 15) ----
        # Placed actors living on this node: actor_id binary -> {worker_id,
        # direct_address, pid}. This is the node's half of the head's actor
        # directory — a RESTARTED head rebuilds bindings from it via the
        # reconcile_report op (reference: raylet resubscribe after
        # NotifyGCSRestart). Guarded by workers_lock (same lifecycle).
        self._placed_actors: dict[bytes, dict] = {}
        # Recently queued completion reports (bounded ring): the crashed
        # head may have processed a report without journaling it — the
        # reconcile report re-offers these and the head applies the ones it
        # lost, closing the fsync window without double execution.
        from collections import OrderedDict as _OD

        self._done_ring: "_OD[bytes, Any]" = _OD()
        self._done_ring_cap = 256
        # Gate on outbound lease/placement reports while a resumed
        # re-registration awaits its reconcile verdict: a report racing
        # ahead of the reconcile would hit a head that has not rebuilt this
        # node's lease table yet. The hold is DEADLINE-bounded
        # (_reports_hold_deadline, set at resume): if the head's reconcile
        # ask never arrives (both ask pushes lost), the gate reopens on its
        # own — a permanently closed gate would silently stop every
        # completion report this node ever sends.
        self._reports_open = threading.Event()
        self._reports_open.set()
        self._reports_hold_deadline = 0.0
        # bumped on every successful RESUME (head restart survived); local
        # workers learn via P.HeadRestarted so their in-flight controller
        # calls unblock and retry per idempotency class
        self.head_epoch = 0

        # Batched completion reports (PR 12): AgentTaskDone frames queue
        # here and coalesce per flush tick into ONE AgentReportBatch — a
        # steady-state node completing hundreds of short leases per second
        # pays one wire frame per tick, not one per task. Window knob:
        # RAY_TPU_AGENT_REPORT_FLUSH_MS (config agent_report_flush_ms);
        # 0 restores a frame per completion. Resolved BEFORE the spawner
        # (its actor_placed_batch coalescer shares the window).
        from ray_tpu._private.config import get_config as _get_config

        try:
            _report_ms = float(
                os.environ.get(
                    "RAY_TPU_AGENT_REPORT_FLUSH_MS",
                    _get_config().agent_report_flush_ms,
                )
            )
        except (TypeError, ValueError):
            _report_ms = 2.0
        self._report_window_s = max(0.0, _report_ms) / 1000.0
        self._report_queue: list = []
        self._report_lock = threading.Lock()
        self._report_wake = threading.Event()

        # Observability plane (PR 14): worker processes on this node push
        # their span-ring drains + metrics snapshots to US (the agent
        # intercepts report_observability on the worker socket); the node's
        # merged payload — workers' entries plus this agent's own spans and
        # registry snapshot — piggybacks on the report-batch flush tick, so
        # shipping costs ZERO extra head round trips. Cadence: config
        # metrics_report_interval_ms / RAY_TPU_METRICS_REPORT_INTERVAL_MS.
        try:
            _obs_ms = float(
                os.environ.get(
                    "RAY_TPU_METRICS_REPORT_INTERVAL_MS",
                    _get_config().metrics_report_interval_ms,
                )
            )
        except (TypeError, ValueError):
            _obs_ms = 2000.0
        self._obs_interval_s = max(0.05, _obs_ms / 1000.0)
        self._obs_pending: list = []  # worker reporter entries, bounded
        self._obs_pending_cap = 256
        self._obs_lock = threading.Lock()
        self._obs_last_ship = 0.0
        self._obs_metric = None  # lazy transfer_stats → Counter mirror
        self._obs_metric_last: dict[str, float] = {}

        # Actor creation leases (reference: the raylet side of
        # GcsActorScheduler's lease protocol): the spawner owns worker
        # acquisition, the registration handshake, creation dispatch, and
        # the actor_placed / actor_creation_failed report back to the head.
        from ray_tpu._private.actor_spawner import ActorSpawner

        self.actor_spawner = ActorSpawner(self)

        # ---- local task dispatch (LocalTaskManager analog) ----
        # The head leases normal tasks to this node; the agent owns worker
        # pop/spawn and a local queue (two-level scheduling,
        # local_task_manager.h:60). Keyed by env fingerprint so workers are
        # only reused by compatible tasks.
        self._lease_lock = locktrace.register_lock(
            "agent.lease_lock", threading.RLock()
        )
        self._leased: dict[bytes, P.LeaseTask] = {}  # task_id -> lease msg
        # workers THIS agent spawned for leased tasks (vs head-managed
        # spawns): wid -> env fingerprint, set at spawn time
        self._agent_owned: dict[WorkerID, tuple] = {}
        self._fp_idle: dict[tuple, list[WorkerID]] = {}
        self._wid_fp: dict[WorkerID, tuple] = {}
        self._busy: dict[WorkerID, set[bytes]] = {}  # wid -> running task_ids
        self._local_queue: "list[P.LeaseTask]" = []
        self._spawning = 0
        # same knobs that govern the head's pool (RAY_TPU_* env-overridable
        # on this host): soft cap, blocked-growth window, register timeout
        from ray_tpu._private.config import get_config

        cfg = get_config()
        self._pool_cap = cfg.worker_pool_soft_limit or (
            int(self.resources.get("CPU", 0)) + 4
        )
        self._growth_idle_s = max(cfg.worker_pool_growth_idle_s, 0.05)
        self._register_timeout_s = cfg.worker_register_timeout_s
        self._last_local_done = 0.0
        # local queue beyond this spills back to the head for re-placement
        # (the head caps its outstanding leases to the same bound)
        self._spill_threshold = max(4 * (int(self.resources.get("CPU", 0)) + 4), 64)

        # Own-request plumbing (agent → controller RPCs).
        self._req_counter = itertools.count(1)
        self._replies: dict[int, Any] = {}
        self._reply_cv = locktrace.register_lock(
            "agent.reply_cv", threading.Condition()
        )


        # Node-local object lifecycle: seal order for LRU spilling when the
        # arena fills (the agent owns its data plane's spilling the way the
        # raylet's LocalObjectManager does, local_object_manager.h:43), and
        # the spill table for serving spilled objects to readers.
        self._resident: "dict[bytes, tuple[str, int]]" = {}
        self._resident_order: list[bytes] = []
        self._resident_lock = locktrace.register_lock(
            "agent.resident_lock", threading.Lock()
        )
        self._spilled: dict[bytes, tuple[str, int]] = {}
        self.spill_dir = os.path.join(self.base_dir, "spill")

        # Peer data connections (agent/controller chunk pulls); per-peer
        # conn cap matches the transfer window so one windowed pull can
        # keep that many chunks in flight to a single source.
        self._transfer_chunk_bytes = max(64 * 1024, cfg.object_transfer_chunk_bytes)
        self._transfer_window = max(1, cfg.object_transfer_window)
        self._peers = P.ChunkConnPool(
            authkey, max_conns_per_peer=self._transfer_window
        )
        # replica-set lookup cache: oid -> (list[data_address], expiry).
        # Entries are invalidated eagerly on FreeLocal and on per-source
        # pull failures (a freed-then-recreated object id must not route
        # pulls to the old node) — the TTL is only the staleness bound for
        # the happy path.
        self._location_cache: dict[bytes, tuple] = {}
        # oids sealed locally as REPLICAS by pull-into-arena (vs primaries
        # produced here): under arena pressure these are evicted outright
        # (the primary serves re-pulls) instead of spilled to disk.
        self._replica_resident: set[bytes] = set()
        # per-object single-flight for pull-into-arena: concurrent readers
        # on this node coalesce into one cross-node transfer
        self._pulls: dict[bytes, threading.Event] = {}
        self._pulls_lock = locktrace.register_lock(
            "agent.pulls_lock", threading.Lock()
        )
        # transfer observability (peer vs head chunk counts, replica hits)
        self.transfer_stats: dict[str, int] = defaultdict(int)
        self._stats_lock = threading.Lock()

        # Data listener: serve chunk reads of local objects to peers. The
        # backlog must absorb a windowed burst of concurrent dials (every
        # puller opens up to object_transfer_window connections at once;
        # the multiprocessing default of 1 overflows the accept queue and
        # the kernel's dropped-ACK recovery stalls the dialer for seconds).
        self._data_listener = Listener(
            ("0.0.0.0", data_port), family="AF_INET", authkey=authkey,
            backlog=max(64, 4 * self._transfer_window),
        )
        self.data_address = f"{self.node_ip}:{self._data_listener.address[1]}"
        threading.Thread(
            target=self._data_accept_loop, daemon=True, name="agent-data"
        ).start()

        # Worker listener (unix socket, same protocol the head controller
        # speaks to its local workers).
        self.worker_sock = os.path.join(self.base_dir, "agent.sock")
        self._worker_listener = Listener(
            self.worker_sock, family="AF_UNIX", authkey=authkey
        )
        threading.Thread(
            target=self._worker_accept_loop, daemon=True, name="agent-accept"
        ).start()

        # Control channel to the head.
        host, _, port = address.rpartition(":")
        self.conn = Client((host, int(port)), authkey=authkey)
        self._send_lock = threading.Lock()
        self._send(
            P.RegisterAgent(
                self.node_id,
                self.resources,
                self.labels,
                self.arena_name,
                self.data_address,
                pid=os.getpid(),
                hostname=socket.gethostname(),
            )
        )
        ack = self.conn.recv()
        if not isinstance(ack, P.AgentAck):
            raise AgentError(f"unexpected registration reply: {ack!r}")
        logger.info(
            "agent registered: node=%s head=%s data=%s arena=%s",
            self.node_id.hex()[:8], address, self.data_address, self.arena_name,
        )
        threading.Thread(
            target=self._heartbeat_loop, daemon=True, name="agent-hb"
        ).start()
        threading.Thread(
            target=self._pump_loop, daemon=True, name="agent-pump"
        ).start()
        threading.Thread(
            target=self._report_flush_loop, daemon=True, name="agent-report"
        ).start()
        # Worker log capture: spawned workers write per-worker files under
        # logs/; this monitor tails them and streams new lines to the head,
        # which prefixes them onto the driver's console (the remote half of
        # the reference's log_monitor.py).
        self.log_dir = os.path.join(self.base_dir, "logs")
        os.makedirs(self.log_dir, exist_ok=True)
        self._log_offsets: dict[str, int] = {}
        threading.Thread(
            target=self._log_monitor_loop, daemon=True, name="agent-logmon"
        ).start()

    # ------------------------------------------------------------- log plane

    def _log_monitor_loop(self):
        while not self.shutting_down:
            try:
                self._log_monitor_scan()
            except Exception:  # noqa: BLE001 — the monitor must never die
                pass
            time.sleep(0.2)

    def _log_monitor_scan(self):
        from ray_tpu._private.log_tail import scan_log_dir

        def forward(wid_hex, source, lines):
            try:
                self._send(P.WorkerLogLines(wid_hex, source, lines))
            except (OSError, EOFError):
                pass

        scan_log_dir(self.log_dir, self._log_offsets, forward)

    def _handle_fetch_logs(self, msg: "P.FetchLogs"):
        from ray_tpu._private.log_tail import tail_file

        text = tail_file(
            os.path.join(self.log_dir, f"worker-{msg.worker_id_hex}.{msg.source}"),
            msg.tail_bytes,
        )
        try:
            self._send(P.LogsReply(msg.req_id, text))
        except (OSError, EOFError):
            pass

    # ------------------------------------------------------------- transport

    def _send(self, msg):
        with self._send_lock:
            self.conn.send(msg)

    def _maybe_inject_failure(self, op: str):
        """Agent-side RPC chaos for our own controller calls (the same env
        table the worker runtime reads, ``RAY_TPU_WORKER_RPC_FAILURE`` —
        keys are catalog-validated so a typo fails loud, per PR 9). The
        lease report ops (``actor_placed``/``actor_creation_failed``) ride
        this channel; injections exercise the spawner's retry path."""
        spec = os.environ.get("RAY_TPU_WORKER_RPC_FAILURE")
        if not spec:
            return
        if self._chaos_table is None:
            self._chaos_table = P.parse_worker_chaos_table(spec)
        prob = self._chaos_table.get(op)
        if prob and self._chaos_rng.random() < prob:
            raise OSError(
                f"injected agent rpc failure for {op!r} "
                f"(RAY_TPU_WORKER_RPC_FAILURE)"
            )

    def call_controller(self, op: str, payload=None, timeout: float = 60.0):
        self._maybe_inject_failure(op)
        req_id = next(self._req_counter)
        self._send(P.Request(req_id, op, payload))
        deadline = time.monotonic() + timeout
        with self._reply_cv:
            while req_id not in self._replies:
                remaining = deadline - time.monotonic()
                if self.shutting_down:
                    raise AgentError("agent shutting down")
                if remaining <= 0:
                    raise TimeoutError(f"controller call {op} timed out")
                self._reply_cv.wait(remaining)
            reply = self._replies.pop(req_id)
        if reply.error is not None:
            raise RuntimeError(f"controller call {op} failed: {reply.error}")
        return reply.payload

    def serve_forever(self, reconnect_window_s: float = 60.0):
        """Main loop: dispatch controller → agent traffic until shutdown.

        On head-connection loss the agent RECONNECTS (reference: raylet
        ``NotifyGCSRestart`` reconnect + resubscribe, ``node_manager.cc:947``).
        It first tries to RESUME: workers, arena, and held leases are
        preserved and re-offered to the head (``RegisterAgent(resume=True)``
        → ``AgentReconcile`` ask → ``reconcile_report``), so a restarted
        head rebuilds this node's truth and pre-crash work completes
        exactly once. Only if the head refuses (it never died — its reader
        EOF already re-placed everything — or the recovery window closed)
        does the agent fall back to the old reset: tear down workers,
        recycle the arena, and re-register as a fresh node."""
        while not self.shutting_down:
            try:
                msg = self.conn.recv()
            except (EOFError, OSError):
                if self.shutting_down:
                    break
                logger.warning("lost connection to head; reconnecting")
                if not self._reconnect(reconnect_window_s):
                    logger.warning("could not re-reach head; shutting down")
                    break
                continue
            try:
                self._dispatch_head_msg(msg)
            except Exception:  # noqa: BLE001 — the loop must survive
                logger.error("agent dispatch failed:\n%s", traceback.format_exc())
        self.shutdown()

    def _register_msg(self, resume: bool) -> "P.RegisterAgent":
        return P.RegisterAgent(
            self.node_id,
            self.resources,
            self.labels,
            self.arena_name,
            self.data_address,
            pid=os.getpid(),
            hostname=socket.gethostname(),
            resume=resume,
        )

    def _reconnect(self, window_s: float) -> bool:
        deadline = time.monotonic() + window_s
        # Phase 1 — RESUME: keep local state and offer it for reconcile.
        # Reports are gated until the reconcile verdict lands (a placement
        # report racing ahead would hit a head that has not rebuilt this
        # node's lease table yet).
        host, _, port = self.head_address.rpartition(":")
        self._reports_open.clear()
        # bounded hold mirroring the head's recovery window (+ its single
        # re-ask allowance): past this, reports reopen even if no
        # AgentReconcile ever arrived
        from ray_tpu._private.config import get_config as _gc

        try:
            _cfg = _gc()
            hold_s = _cfg.recovery_grace_s + _cfg.recovery_reconcile_resend_s + 5.0
        except Exception:  # noqa: BLE001 — env-only processes
            hold_s = 20.0
        self._reports_hold_deadline = time.monotonic() + hold_s
        while time.monotonic() < deadline and not self.shutting_down:
            try:
                conn = Client((host, int(port)), authkey=self.authkey)
                # swap + register atomically: the heartbeat thread must
                # not slip a Heartbeat in as the new connection's first
                # message (the head closes conns whose first message
                # isn't a Register*)
                with self._send_lock:
                    self.conn = conn
                    conn.send(self._register_msg(resume=True))
                ack = conn.recv()
                if (
                    isinstance(ack, P.AgentAck)
                    and getattr(ack, "resume_verdict", "fresh")
                    == "reconcile"
                ):
                    self.head_epoch += 1
                    # re-arm the hold from the ACK, not from disconnect
                    # detection: a long head outage inside the reconnect
                    # window would otherwise burn the whole hold budget
                    # dialing, and reports would escape before the
                    # reconcile report is applied
                    self._reports_hold_deadline = time.monotonic() + hold_s
                    logger.info(
                        "resumed with restarted head (epoch %d): "
                        "awaiting reconcile ask", self.head_epoch,
                    )
                    return True
                # verdict "reset" (or a pre-resume head): preserved
                # state refused — fall through to the fresh path
                try:
                    conn.close()
                except OSError:
                    pass
                break
            except (OSError, EOFError, ConnectionError):
                time.sleep(1.0)
        # Phase 2 — RESET: the old incarnation's work was (or will be)
        # re-placed by the head; executing any of it here would double it.
        self._reset_local_state()
        self._reports_open.set()
        while time.monotonic() < deadline and not self.shutting_down:
            try:
                conn = Client((host, int(port)), authkey=self.authkey)
                with self._send_lock:
                    self.conn = conn
                    conn.send(self._register_msg(resume=False))
                ack = conn.recv()
                if isinstance(ack, P.AgentAck):
                    logger.info("re-registered with restarted head (fresh)")
                    return True
                conn.close()
            except (OSError, EOFError, ConnectionError):
                pass
            time.sleep(1.0)
        return False

    # ------------------------------------------- head-recovery reconcile

    def wait_reports_open(self) -> None:
        """Block an outbound lease/placement report while a resumed
        re-registration awaits its reconcile verdict — until the gate opens
        or the bounded hold deadline lapses (mirrors _flush_reports; a
        report escaping EARLY would hit a still-RECOVERING head whose lease
        table is parked, be answered 'dead', and kill a healthy worker)."""
        while (
            not self._reports_open.is_set()
            and not self.shutting_down
            and time.monotonic() < self._reports_hold_deadline
        ):
            self._reports_open.wait(timeout=0.2)

    def note_actor_placed(self, aid_bin: bytes, worker_id, direct_address):
        """The spawner finished a creation: remember the binding so a
        restarted head can rebuild it from our reconcile report."""
        with self.workers_lock:
            w = self.workers.get(worker_id)
            pid = getattr(w.get("proc"), "pid", 0) if w else 0
            self._placed_actors[aid_bin] = {
                "worker_id": worker_id,
                "direct_address": direct_address,
                "pid": pid or 0,
            }

    def _note_actor_gone(self, worker_id) -> None:
        with self.workers_lock:
            for aid, rec in list(self._placed_actors.items()):
                if rec["worker_id"] == worker_id:
                    del self._placed_actors[aid]

    def _build_reconcile_report(self) -> dict:
        """This node's truth for a recovering head: held task leases,
        creation leases still in the spawner, placed actors (with pids as
        incarnations), recently-queued completion reports, and the arena's
        object inventory."""
        with self._lease_lock:
            task_leases = list(self._leased.keys())
        with self.workers_lock:
            actors = [
                (aid, rec["worker_id"].binary(), rec["direct_address"],
                 rec["pid"])
                for aid, rec in self._placed_actors.items()
            ]
            workers = [
                (wid.binary(), getattr(w.get("proc"), "pid", 0) or 0)
                for wid, w in self.workers.items()
            ]
        with self._report_lock:
            completed = [
                (r.task_id.binary(), r.results, r.exec_ms)
                for r in self._done_ring.values()
            ]
        with self._resident_lock:
            objects = [
                (key, name, size, key in self._replica_resident)
                for key, (name, size) in self._resident.items()
            ]
        return {
            "task_leases": task_leases,
            "actor_leases": self.actor_spawner.held_creation_task_ids(),
            "actors": actors,
            "workers": workers,
            "completed": completed,
            "objects": objects,
        }

    def _send_reconcile_report(self, msg: "P.AgentReconcile"):
        """Answer one AgentReconcile ask: ship the report (bounded retries
        — the head's apply is idempotent and it re-asks once on a dropped
        report), apply the orphan verdicts, then reopen reports and tell
        local workers the head restarted (their in-flight controller calls
        lost their replies)."""
        report = self._build_reconcile_report()
        verdict = None
        # the ask carries the head's remaining recovery window: retrying
        # past it is pointless (a late report gets the 'closed' verdict)
        deadline = time.monotonic() + max(1.0, float(msg.deadline_s))
        try:
            for attempt in range(5):
                if self.shutting_down or time.monotonic() >= deadline:
                    return
                try:
                    verdict = self.call_controller(
                        "reconcile_report",
                        (self.node_id.hex(), report),
                        timeout=30.0,
                    )
                    break
                except Exception as e:  # noqa: BLE001 — chaos/transport
                    logger.warning(
                        "reconcile_report failed (attempt %d/5): %s",
                        attempt + 1, e,
                    )
                    time.sleep(min(0.2 * (attempt + 1), 1.0))
            if isinstance(verdict, dict) and verdict.get("status") == "ok":
                self._apply_reconcile_verdict(verdict)
            elif isinstance(verdict, dict) and verdict.get("status") == "closed":
                # the head's recovery window closed before our report
                # landed: our held work was already re-placed/re-created —
                # keeping it would execute everything twice. Tear down and
                # re-register fresh (closing the conn routes serve_forever
                # through the normal reconnect path, whose resume attempt
                # the non-recovering head answers with 'reset').
                logger.warning(
                    "reconcile arrived after the head's recovery window "
                    "closed: resetting local state (held work was re-placed)"
                )
                try:
                    self.conn.close()
                except OSError:
                    pass
        finally:
            # bounded hold: even a lost reconcile must not gate reports
            # forever (the head re-places at its grace deadline and the
            # normal idempotent report paths take over)
            self._reports_open.set()
            self._report_wake.set()
        self._notify_workers_head_restarted()

    def _apply_reconcile_verdict(self, verdict: dict):
        """Reap what the journal never granted: orphan leases pop from the
        local queue maps, orphan actors' workers die, orphan objects free."""
        drop_tasks = set(verdict.get("drop_tasks") or ())
        if drop_tasks:
            with self._lease_lock:
                for tid in drop_tasks:
                    self._leased.pop(tid, None)
                self._local_queue = [
                    lt for lt in self._local_queue
                    if lt.spec.task_id.binary() not in drop_tasks
                ]
            self.actor_spawner.drop_creation_leases(drop_tasks)
        for aid in verdict.get("drop_actors") or ():
            with self.workers_lock:
                rec = self._placed_actors.pop(aid, None)
            if rec is None:
                continue
            with self.workers_lock:
                w = self.workers.get(rec["worker_id"])
            proc = w.get("proc") if w else None
            if proc is not None:
                try:
                    proc.terminate()
                except OSError:
                    pass
        for oid_bin in verdict.get("drop_objects") or ():
            oid = ObjectID(oid_bin)
            self._invalidate_location(oid)
            self._replica_resident.discard(oid_bin)
            with self._resident_lock:
                if self._resident.pop(oid_bin, None) is not None:
                    try:
                        self._resident_order.remove(oid_bin)
                    except ValueError:
                        pass
            try:
                self.store.delete(oid)
            except Exception:  # noqa: BLE001
                pass

    def _notify_workers_head_restarted(self):
        """Local workers' in-flight controller calls (relayed through us)
        lost their replies with the crashed head: bump their connection
        epoch so blocked waiters retry per idempotency class."""
        note = P.HeadRestarted(epoch=self.head_epoch)
        with self.workers_lock:
            targets = [
                w for w in self.workers.values()
                if w.get("conn") is not None
            ]
        for w in targets:
            try:
                with w["lock"]:
                    w["conn"].send(note)
            except (OSError, EOFError):
                pass

    def _drop_queued_reports(self):
        """Reconnect reset: queued reports reference the old head's lease
        state — the new incarnation re-places everything, so they must not
        be delivered."""
        with self._report_lock:
            self._report_queue.clear()

    def _reset_local_state(self):
        """Tear down workers + data plane for a clean re-registration."""
        from ray_tpu._private.object_store import NativePlasmaStore

        self.draining = False  # fresh incarnation accepts leases again

        # head-side lease state died with the old head: no stale report
        # must reach the new incarnation (it re-places restorable actors)
        self.actor_spawner.reset()
        self._drop_queued_reports()
        with self._report_lock:
            self._done_ring.clear()
        with self.workers_lock:
            workers = list(self.workers.values())
            self.workers.clear()
            self._placed_actors.clear()
            self._pending_kills.clear()
        with self._lease_lock:
            self._leased.clear()
            self._local_queue.clear()
            self._agent_owned.clear()
            self._fp_idle.clear()
            self._wid_fp.clear()
            self._busy.clear()
            self._spawning = 0
        for w in workers:
            proc = w.get("proc")
            if proc is not None:
                try:
                    proc.terminate()
                except OSError:
                    pass
        with self._resident_lock:
            self._resident.clear()
            self._resident_order.clear()
        for path, _ in self._spilled.values():
            try:
                os.unlink(path)
            except OSError:
                pass
        self._spilled.clear()
        self._location_cache.clear()
        self._replica_resident.clear()
        # wake pull-into-arena followers parked on the old incarnation
        with self._pulls_lock:
            pulls, self._pulls = self._pulls, {}
        for ev in pulls.values():
            ev.set()
        try:
            self.store.shutdown()
        except Exception:  # noqa: BLE001
            pass
        self.arena_name = f"/rtpu-a{os.getpid():x}-{time.time_ns() & 0xFFFFFF:x}"
        self.store = NativePlasmaStore(self._store_capacity, self.arena_name)

    def _dispatch_head_msg(self, msg):
        if isinstance(msg, P.ToWorker):
            with self.workers_lock:
                w = self.workers.get(msg.worker_id)
            # conn is None until the worker process handshakes
            if w is not None and w.get("conn") is not None:
                try:
                    with w["lock"]:
                        w["conn"].send(msg.msg)
                except (OSError, EOFError):
                    pass
        elif isinstance(msg, P.Reply):
            with self._reply_cv:
                self._replies[msg.req_id] = msg
                self._reply_cv.notify_all()
        elif isinstance(msg, P.SpawnWorker):
            threading.Thread(
                target=self._spawn_worker, args=(msg,), daemon=True
            ).start()
        elif isinstance(msg, P.LeaseTask):
            self._on_lease_task(msg)
        elif isinstance(msg, P.LeaseBatch):
            # one frame, N grants (the head's per-round outbox): unpack
            # FIFO so per-agent grant ordering matches N single pushes
            for lease in msg.leases:
                if isinstance(lease, P.LeaseActor):
                    self.actor_spawner.on_lease(lease)
                else:
                    self._on_lease_task(lease)
        elif isinstance(msg, P.LeaseActor):
            # actor creation lease: the spawner owns the whole local
            # lifecycle (runs on its own thread — never block this loop,
            # which also delivers our call_controller replies)
            self.actor_spawner.on_lease(msg)
        elif isinstance(msg, P.FetchLogs):
            threading.Thread(
                target=self._handle_fetch_logs, args=(msg,), daemon=True
            ).start()
        elif isinstance(msg, P.KillWorker):
            with self.workers_lock:
                w = self.workers.get(msg.worker_id)
                if w is None:
                    # spawn still in flight (runtime-env staging): leave a
                    # tombstone so _spawn_worker kills the process on arrival
                    self._pending_kills.add(msg.worker_id)
            if w is not None and w.get("proc") is not None:
                try:
                    w["proc"].terminate()
                except OSError:
                    pass
        elif isinstance(msg, P.FreeLocal):
            for oid in msg.object_ids:
                key = oid.binary()
                # eager invalidation (never wait out the TTL): a freed-
                # then-recreated object id must not route pulls to the old
                # holder, and this node stops advertising its dead replica
                self._invalidate_location(oid)
                self._replica_resident.discard(key)
                with self._resident_lock:
                    if self._resident.pop(key, None) is not None:
                        try:
                            self._resident_order.remove(key)
                        except ValueError:
                            pass
                spilled = self._spilled.pop(key, None)
                if spilled is not None:
                    try:
                        os.unlink(spilled[0])
                    except OSError:
                        pass
                try:
                    self.store.delete(oid)
                except Exception:  # noqa: BLE001
                    pass
        elif isinstance(msg, P.AgentReconcile):
            # the restarted head asks for our truth; answer OFF this loop
            # (call_controller blocks on a reply that arrives HERE)
            threading.Thread(
                target=self._send_reconcile_report, args=(msg,),
                daemon=True, name="agent-reconcile",
            ).start()
        elif isinstance(msg, P.ReplicateObjects):
            # preempt evacuation: pull each object into OUR arena off this
            # loop (the pull's register_replica reply arrives HERE) — the
            # single-flight pull machinery coalesces with any concurrent
            # reader, and registration tells the head the copy survives
            threading.Thread(
                target=self._replicate_objects, args=(list(msg.objects),),
                daemon=True, name="agent-replicate",
            ).start()
        elif isinstance(msg, P.DrainAgent):
            self._on_drain(msg)
        elif isinstance(msg, P.Shutdown):
            self.shutting_down = True

    def _replicate_objects(self, objects):
        for oid, size in objects:
            if self.shutting_down:
                return
            try:
                self._pull_into_arena((oid, int(size)))
            except Exception:  # noqa: BLE001 — per-object best effort: the
                # head's drain loop falls back to a pull-to-head for
                # anything that never registers
                logger.warning(
                    "replicate pull of %s failed", oid.hex(), exc_info=True
                )

    def announce_preemption(self, notice_s: float, reason: str = "SIGTERM"):
        """The platform told THIS process it is being reclaimed (SIGTERM on
        a spot/maintenance host): tell the head so it starts a preempt
        drain with ``notice_s`` of runway, and begin quiescing locally
        without waiting for the head's DrainAgent push (idempotent — the
        push lands on an already-draining agent and early-returns). Never
        raises: with the head unreachable the local quiesce still runs, and
        heartbeat loss covers the rest."""
        logger.warning(
            "termination notice (%s): announcing %.0fs preempt drain",
            reason, notice_s,
        )
        try:
            self.call_controller(
                "node_preempt_notice",
                (self.node_id.hex(), float(notice_s), reason),
                timeout=min(notice_s, 10.0) if notice_s > 0 else 10.0,
            )
        except Exception:  # noqa: BLE001
            logger.warning(
                "could not deliver preempt notice to head", exc_info=True
            )
        self._on_drain(P.DrainAgent(float(notice_s), f"preempt-notice:{reason}"))

    def _on_drain(self, msg: P.DrainAgent):
        """Quiesce for graceful release (the raylet half of the drain
        protocol): stop accepting leases, let local work finish within the
        deadline, flush captured logs, report back."""
        if self.draining:
            return
        self.draining = True
        logger.info(
            "drain requested (deadline %.0fs): %s", msg.deadline_s, msg.reason
        )
        threading.Thread(
            target=self._drain_quiesce, args=(msg.deadline_s,),
            daemon=True, name="agent-drain",
        ).start()

    def _drain_quiesce(self, deadline_s: float):
        deadline = time.monotonic() + max(deadline_s, 0.0)
        remaining = 0
        while not self.shutting_down:
            with self._lease_lock:
                remaining = len(self._leased) + len(self._local_queue)
            remaining += self.actor_spawner.outstanding()
            if remaining == 0 or time.monotonic() > deadline:
                break
            time.sleep(0.1)
        # flush: coalesced completion reports and captured worker output
        # must reach the head before release
        self._flush_reports()
        try:
            self._log_monitor_scan()
        except Exception:  # noqa: BLE001
            pass
        try:
            self._send(P.AgentDrained(self.node_id, remaining=remaining))
        except (OSError, EOFError):
            pass

    def _heartbeat_loop(self):
        while not self.shutting_down:
            try:
                self._send(
                    P.Heartbeat(
                        self.node_id,
                        {
                            "arena_used_bytes": self.store.used_bytes(),
                            "num_workers": len(self.workers),
                            "draining": self.draining,
                        },
                    )
                )
            except (OSError, EOFError):
                # conn mid-reconnect: keep the loop alive, the main loop
                # swaps self.conn in after re-registration
                pass
            time.sleep(2.0)

    # ------------------------------------------------- local task dispatch

    @staticmethod
    def _lease_fp(lease: P.LeaseTask) -> tuple:
        return (lease.tpu_chips, tuple(sorted(lease.env_vars.items())))

    def _trace_gate(self, spec) -> bool:
        """Record agent-plane spans for this lease? Same deterministic
        per-task sampling verdict every plane computes."""
        if getattr(spec, "trace_id", None) is None:
            return False
        from ray_tpu.util import tracing

        return tracing.sampled(spec.task_id.binary())

    def _stamp_lease_trace(self, lease) -> None:
        """First dispatch of a traced lease: remember the head's sched span
        as OUR parent and re-point ``spec.sched_span_id`` at the agent span
        (``<task_id>:agent``), so the worker's exec span parents under the
        plane that actually handed it the task."""
        spec = lease.spec
        if getattr(lease, "_obs_span", None) is None and self._trace_gate(spec):
            lease._obs_parent = getattr(spec, "sched_span_id", None)
            lease._obs_span = f"{spec.task_id.hex()}:agent"
            spec.sched_span_id = lease._obs_span

    def _on_lease_task(self, lease: P.LeaseTask):
        """Second-level dispatch: the head picked this node; the agent picks
        (or spawns) the worker (reference: LocalTaskManager dispatch,
        local_task_manager.h:60)."""
        lease._obs_recv = time.time()  # agent-plane span start
        if self.draining:
            # quiesce: reject new leases outright — the head re-places them
            # elsewhere (the drain window race: the head marked us DRAINING
            # after this lease was already on the wire)
            try:
                self._send(
                    P.TaskSpilled(
                        [lease.spec.task_id.binary()], reason="draining"
                    )
                )
            except (OSError, EOFError):
                pass
            return
        spill = None
        with self._lease_lock:
            self._leased[lease.spec.task_id.binary()] = lease
            if not self._try_dispatch_local(lease):
                self._local_queue.append(lease)
                if len(self._local_queue) > self._spill_threshold:
                    # overload spillback: hand the newest tasks back for
                    # re-placement on another node
                    excess = self._local_queue[self._spill_threshold :]
                    del self._local_queue[self._spill_threshold :]
                    spill = []
                    for lt in excess:
                        k = lt.spec.task_id.binary()
                        self._leased.pop(k, None)
                        spill.append(k)
        if spill:
            try:
                self._send(P.TaskSpilled(spill, reason="overload"))
            except (OSError, EOFError):
                pass

    def _try_dispatch_local(self, lease: P.LeaseTask) -> bool:
        """Pop an idle compatible worker or start one (call under
        _lease_lock). Returns True when the task went to a worker."""
        fp = self._lease_fp(lease)
        self._stamp_lease_trace(lease)  # before the spec crosses the wire
        idle = self._fp_idle.get(fp)
        while idle:
            wid = idle.pop()
            if wid not in self._wid_fp:
                continue  # retired
            if self._send_to_worker(wid, P.ExecuteTask(lease.spec, lease.resolved_args)):
                self._busy.setdefault(wid, set()).add(lease.spec.task_id.binary())
                if getattr(lease, "_obs_span", None) is not None:
                    tid_hex = lease.spec.task_id.hex()
                    from ray_tpu.util import tracing

                    tracing.record_span(
                        "agent.dispatch",
                        getattr(lease, "_obs_recv", time.time()),
                        time.time(),
                        trace_id=lease.spec.trace_id,
                        span_id=f"{tid_hex}:agent:dispatch",
                        parent_id=lease._obs_span,
                        plane="agent",
                        task_id=tid_hex,
                    )
                return True
            self._retire_local_worker(wid)
        n = len(self._wid_fp) + self._spawning
        # grow: under cap freely; past cap only while the pool is blocked
        # (nothing completed locally — e.g. every worker waits on a nested
        # task), mirroring the head's churn-aware growth rule
        blocked = (time.monotonic() - self._last_local_done) > self._growth_idle_s
        if self.shutting_down:
            return False
        if n < self._pool_cap or (blocked and self._spawning == 0):
            self._spawning += 1
            wid = WorkerID.from_random()
            self._agent_owned[wid] = fp
            threading.Thread(
                target=self._spawn_worker,
                args=(
                    P.SpawnWorker(
                        wid, dict(lease.env_vars), lease.tpu_chips, fp, packages=[]
                    ),
                ),
                daemon=True,
            ).start()
        return False

    def _send_to_worker(self, wid: WorkerID, msg) -> bool:
        with self.workers_lock:
            w = self.workers.get(wid)
        if w is None or w.get("conn") is None:
            return False
        try:
            with w["lock"]:
                w["conn"].send(msg)
            return True
        except (OSError, EOFError):
            return False

    def _retire_local_worker(self, wid: WorkerID):
        """Drop a worker from the local pool maps (under _lease_lock)."""
        fp = self._wid_fp.pop(wid, None)
        if fp is not None:
            idle = self._fp_idle.get(fp)
            if idle and wid in idle:
                idle.remove(wid)
        self._busy.pop(wid, None)

    def _evict_idle_chip_workers(self):
        """Retire this agent's idle pool workers that were spawned for a TPU
        grant: they keep the device library loaded, and the chips are about
        to go to another process."""
        with self._lease_lock:
            wids = [
                wid
                for fp, idle in self._fp_idle.items()
                if fp[0]
                for wid in idle
            ]
            for wid in wids:
                self._retire_local_worker(wid)
                self._agent_owned.pop(wid, None)
        for wid in wids:
            with self.workers_lock:
                w = self.workers.get(wid)
            if w is not None and w.get("proc") is not None:
                try:
                    w["proc"].terminate()
                except OSError:
                    pass

    def pop_idle_worker(self, fp: tuple) -> Optional[WorkerID]:
        """Dedicate an idle agent-owned pool worker to an actor (the
        spawner's pool-pop path): removed from EVERY pool map so local task
        dispatch never reuses it — it belongs to the actor now."""
        with self._lease_lock:
            idle = self._fp_idle.get(fp)
            while idle:
                wid = idle.pop()
                if wid not in self._wid_fp:
                    continue  # retired
                del self._wid_fp[wid]
                self._agent_owned.pop(wid, None)
                self._busy.pop(wid, None)
                return wid
        return None

    def adopt_idle_worker(self, wid: WorkerID, fp: tuple):
        """A creation worker that survived a raising ``__init__`` joins the
        local task pool (parity with the head, which returns such workers
        to its pool instead of leaking the slot)."""
        with self.workers_lock:
            w = self.workers.get(wid)
        if w is None or w.get("conn") is None:
            return  # died meanwhile: the reader teardown owns cleanup
        with self._lease_lock:
            self._agent_owned[wid] = fp
            self._wid_fp[wid] = fp
            self._fp_idle.setdefault(fp, []).append(wid)
            self._pump_local_locked()

    def _on_local_worker_ready(self, wid: WorkerID, fp: tuple):
        """An agent-owned worker finished handshaking: join the pool and
        drain the local queue."""
        with self._lease_lock:
            self._spawning = max(0, self._spawning - 1)
            self._wid_fp[wid] = fp
            self._fp_idle.setdefault(fp, []).append(wid)
            self._pump_local_locked()

    def _pump_local_locked(self):
        i = 0
        while i < len(self._local_queue):
            if self._try_dispatch_local(self._local_queue[i]):
                self._local_queue.pop(i)
            else:
                i += 1

    def _pump_loop(self):
        """Periodic local pump: retries queued leases (covers the blocked-
        pool growth window where no completion/handshake event fires)."""
        while not self.shutting_down:
            time.sleep(0.25)
            with self._lease_lock:
                if self._local_queue:
                    self._pump_local_locked()

    def _on_leased_task_done(self, wid: WorkerID, msg: P.TaskDone) -> bool:
        """Intercept TaskDone for tasks THIS agent dispatched: report
        AgentTaskDone to the head and reuse the worker immediately. Returns
        False when the task wasn't agent-leased (head-managed path)."""
        tid = msg.task_id.binary()
        with self._lease_lock:
            lease = self._leased.pop(tid, None)
            if lease is None:
                return False
            self._last_local_done = time.monotonic()
            running = self._busy.get(wid)
            if running is not None:
                running.discard(tid)
            fp = self._wid_fp.get(wid)
            if fp is not None:
                self._fp_idle.setdefault(fp, []).append(wid)
                self._pump_local_locked()
        if getattr(lease, "_obs_span", None) is not None:
            # agent-plane umbrella span: lease recv → done-report queued
            tid_hex = lease.spec.task_id.hex()
            from ray_tpu.util import tracing

            tracing.record_span(
                "agent.lease",
                getattr(lease, "_obs_recv", time.time()),
                time.time(),
                trace_id=lease.spec.trace_id,
                span_id=lease._obs_span,
                parent_id=getattr(lease, "_obs_parent", None),
                plane="agent",
                task_id=tid_hex,
            )
        self._queue_report(P.AgentTaskDone(msg.task_id, msg.results, msg.exec_ms))
        return True

    def _queue_report(self, report: "P.AgentTaskDone") -> None:
        """Coalesce a completion report into the per-tick batch (0-window
        config sends it immediately — the pre-batching behavior)."""
        # recovery ring: re-offered in reconcile_report so a completion the
        # crashed head processed-but-never-journaled is not re-executed
        with self._report_lock:
            key = report.task_id.binary()
            self._done_ring[key] = report
            self._done_ring.move_to_end(key)
            while len(self._done_ring) > self._done_ring_cap:
                self._done_ring.popitem(last=False)
        if self._report_window_s <= 0:
            try:
                self._send(report)
            except (OSError, EOFError):
                pass
            return
        with self._report_lock:
            self._report_queue.append(report)
        self._report_wake.set()

    def _flush_reports(self) -> None:
        if not self._reports_open.is_set():
            if time.monotonic() < self._reports_hold_deadline:
                # resumed re-registration awaiting its reconcile verdict:
                # hold (don't drop) — the head has not rebuilt our lease
                # table yet
                return
            # the reconcile ask never arrived inside the head's recovery
            # window (both pushes lost): reopen — the head re-placed at
            # its deadline, stale reports land idempotently, and local
            # workers must stop waiting on dead replies
            self._reports_open.set()
            self._notify_workers_head_restarted()
        with self._report_lock:
            batch, self._report_queue = self._report_queue, []
        # the node's observability payload rides THIS tick (zero extra
        # round trips). Chaos (RAY_TPU_WORKER_RPC_FAILURE
        # "report_observability=p") drops ONLY the observability payload —
        # it refolds for the next tick; task-done reports are unaffected.
        obs = self._collect_observability()
        if obs is not None:
            try:
                self._maybe_inject_failure("report_observability")
            except OSError:
                self._requeue_observability(obs)
                obs = None
        if not batch and obs is None:
            return
        try:
            if len(batch) == 1 and obs is None:
                self._send(batch[0])
            else:
                self._send(P.AgentReportBatch(batch, observability=obs))
        except (OSError, EOFError):
            # conn mid-reconnect: these reports reference the OLD head
            # incarnation's lease state — the reconnect reset re-places
            # everything, so dropping them is the correct outcome. The
            # observability payload is incarnation-free: refold it.
            if obs is not None:
                self._requeue_observability(obs)

    # ------------------------------------------------- observability plane

    def _queue_observability(self, payload) -> None:
        """Worker-socket intercept of ``report_observability``: buffer the
        worker's reporter entries for the node's next piggybacked ship
        (bounded — a stalled head drops the oldest entries, whose metrics
        snapshots are superseded by newer cumulative ones anyway)."""
        _node_hint, entries = payload
        with self._obs_lock:
            self._obs_pending.extend(entries or [])
            if len(self._obs_pending) > self._obs_pending_cap:
                del self._obs_pending[: -self._obs_pending_cap]
        self._report_wake.set()
        return None

    def _requeue_observability(self, entries: list) -> None:
        # same drop-OLDEST policy as _queue_observability: under a long
        # head outage the stale requeued entries go first, the freshest
        # worker reports survive
        with self._obs_lock:
            self._obs_pending = (entries + self._obs_pending)[
                -self._obs_pending_cap:
            ]

    def _mirror_stats_metrics(self) -> None:
        """Register this node's transfer counters as real util.metrics
        samples (delta mirror) so they reach the head's one-scrape
        ``/metrics`` under this node's label."""
        from ray_tpu.util import metrics as M

        if self._obs_metric is None:
            self._obs_metric = M.Counter(
                "rtpu_transfer_events_total",
                "object-transfer plane counters (transfer_stats)",
                tag_keys=("event",),
            )
        with self._stats_lock:
            snap = dict(self.transfer_stats)
        for ev, v in snap.items():
            M.fold_counter_delta(
                self._obs_metric, self._obs_metric_last, ev, float(v),
                tags={"event": ev},
            )

    def _collect_observability(self):
        """Build the node's piggyback payload: buffered worker entries
        plus — when the report interval has elapsed — this agent process's
        own span drain and registry snapshot. None when nothing to ship."""
        now = time.monotonic()
        with self._obs_lock:
            entries, self._obs_pending = self._obs_pending, []
        if now - self._obs_last_ship >= self._obs_interval_s:
            self._obs_last_ship = now
            from ray_tpu.util import tracing as t
            spans = t.drain_spans()
            try:
                self._mirror_stats_metrics()
            except Exception:  # noqa: BLE001 — mirror must not block shipping
                pass
            from ray_tpu.util import metrics as M

            snap = M.snapshot()
            if spans or snap:
                entries = entries + [
                    {
                        "reporter": (
                            f"a-{self.node_id.hex()[:12]}-{os.getpid()}"
                        ),
                        "pid": os.getpid(),
                        "spans": spans,
                        "dropped_spans": t.dropped_spans(),
                        "metrics": snap,
                    }
                ]
        return entries or None

    def _report_flush_loop(self):
        while not self.shutting_down:
            self._report_wake.wait(timeout=0.5)
            self._report_wake.clear()
            if self._report_window_s:
                # coalescing beat: completions arrive in bursts on busy
                # nodes; one breath batches the burst into a single frame
                time.sleep(self._report_window_s)
            self._flush_reports()
        self._flush_reports()

    def _on_local_worker_death(self, wid: WorkerID):
        """Spill this worker's in-flight leased tasks back to the head."""
        self._note_actor_gone(wid)
        with self._lease_lock:
            was_spawning = self._agent_owned.pop(wid, None) is not None and wid not in self._wid_fp
            if was_spawning:
                self._spawning = max(0, self._spawning - 1)
            running = self._busy.pop(wid, set())
            self._retire_local_worker(wid)
            ids = []
            for tid in running:
                if self._leased.pop(tid, None) is not None:
                    ids.append(tid)
            self._pump_local_locked()
        if ids:
            try:
                self._send(P.TaskSpilled(ids, reason="worker_died"))
            except (OSError, EOFError):
                pass

    # --------------------------------------------------------- worker plane

    def _spawn_worker(self, msg: P.SpawnWorker) -> Optional[str]:
        """Start one worker process. Returns None on success, else the
        failure reason (the actor spawner turns it into a lease report;
        pool spawns also notify the head via WorkerDied)."""
        env = dict(os.environ)
        env["RAY_TPU_WORKER"] = "1"
        env["RAY_TPU_AUTHKEY"] = self.authkey.hex()
        env["RAY_TPU_ARENA"] = self.arena_name
        # workers advertise direct actor-call listeners at this host's
        # routable IP so cross-host callers can push calls peer-to-peer
        env["RAY_TPU_NODE_IP"] = self.node_ip
        pkg_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        paths = [pkg_root]
        cwd = None
        wheels_dir = None
        for kind, name, blob in msg.packages:
            root = self._stage_package(name, blob)
            if kind == "working_dir":
                cwd = os.path.join(root, name)
                paths.insert(0, cwd)
            elif kind == "pip_wheels":
                wheels_dir = os.path.join(root, name)
            else:
                paths.append(root)
        existing = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = os.pathsep.join(paths + ([existing] if existing else []))
        if not msg.tpu_chips:
            env.setdefault("JAX_PLATFORMS", "cpu")
        env.update({k: str(v) for k, v in msg.env_vars.items()})
        # runtime_env pip: build (or reuse) the offline venv against the
        # staged wheel cache shipped from the driver host; the worker's
        # interpreter is the venv's python (controller local path mirror)
        python_exe = sys.executable
        pip_json = msg.env_vars.get("RAY_TPU_PIP_SPEC")
        if pip_json:
            import json as _json

            from ray_tpu._private.runtime_env_pip import (
                build_spec,
                ensure_pip_env,
            )

            payload = _json.loads(pip_json)
            spec = build_spec(
                payload["packages"],
                wheels_dir,
                tool=payload.get("tool", "pip"),
            )
            try:
                python_exe = ensure_pip_env(
                    spec, base_dir=os.path.join(self.base_dir, "pip_envs")
                )
            except Exception as e:  # noqa: BLE001 — surface, don't wedge
                with self.workers_lock:
                    self._pending_kills.discard(msg.worker_id)
                self._on_local_worker_death(msg.worker_id)
                self._send(
                    P.WorkerDied(msg.worker_id, f"pip env failed: {e}")
                )
                return f"pip env failed: {e}"
        argv = [
            python_exe,
            "-m",
            "ray_tpu._private.worker_main",
            self.worker_sock,
            msg.worker_id.hex(),
        ]
        if msg.tpu_chips:
            argv.append(str(msg.tpu_chips))
        # per-worker log capture (tailed to the head by the log monitor)
        env["PYTHONUNBUFFERED"] = "1"
        out_path = os.path.join(self.log_dir, f"worker-{msg.worker_id.hex()}.out")
        err_path = os.path.join(self.log_dir, f"worker-{msg.worker_id.hex()}.err")
        stdout = stderr = None
        try:
            stdout = open(out_path, "ab", buffering=0)
            stderr = open(err_path, "ab", buffering=0)
        except OSError:
            if stdout is not None:
                stdout.close()
            stdout = stderr = None
        chips: list[int] = []
        proc = None
        try:
            if msg.tpu_chips:
                # a worker sees exactly the chips it was granted, taken only
                # once their previous holder has exited (mirror of the
                # head's spawn); RuntimeError: they are still held
                chips = self._chips.acquire(
                    msg.tpu_chips,
                    self._register_timeout_s,
                    evict=self._evict_idle_chip_workers,
                )
                env.update(
                    chip_worker_env(chips, self._chips.n_chips, msg.env_vars)
                )
            proc = subprocess.Popen(
                argv, env=env, cwd=cwd, stdout=stdout, stderr=stderr
            )
        except (OSError, RuntimeError) as e:
            self._on_local_worker_death(msg.worker_id)
            self._send(P.WorkerDied(msg.worker_id, f"spawn failed: {e}"))
            return f"spawn failed: {e}"
        finally:
            for fh in (stdout, stderr):
                if fh is not None:
                    fh.close()
            self._chips.bind(chips, proc)
        with self.workers_lock:
            self.workers[msg.worker_id] = {
                "conn": None,
                "proc": proc,
                "lock": threading.Lock(),
            }
            killed = msg.worker_id in self._pending_kills
            self._pending_kills.discard(msg.worker_id)
        if killed:
            try:
                proc.terminate()
            except OSError:
                pass
            return "killed before spawn completed"
        if msg.worker_id in self._agent_owned:
            self._watch_agent_spawn(msg.worker_id, proc)
        return None

    def _watch_agent_spawn(self, wid: WorkerID, proc):
        """Reap an agent-owned worker that dies (or hangs) before its
        handshake — without this, _spawning leaks and the blocked-growth
        clause can never fire again (the head path has
        worker_register_timeout_s; this is the agent-side equivalent)."""
        deadline = time.monotonic() + self._register_timeout_s
        while time.monotonic() < deadline and not self.shutting_down:
            with self._lease_lock:
                if wid in self._wid_fp:
                    return  # joined the pool
            if proc.poll() is not None:
                break  # died before handshake
            time.sleep(0.5)
        with self.workers_lock:
            w = self.workers.get(wid)
            if w is not None and w.get("conn") is not None:
                return  # handshake raced in; the reader owns lifecycle now
            self.workers.pop(wid, None)
        try:
            proc.terminate()
        except OSError:
            pass
        self._on_local_worker_death(wid)

    def _stage_package(self, name: str, blob: bytes) -> str:
        """Unpack a shipped runtime-env zip into the agent's staging area,
        content-addressed so repeat spawns reuse it."""
        import hashlib

        tag = hashlib.sha256(blob).hexdigest()[:16]
        root = os.path.join(self.base_dir, "pkgs", tag)
        done = os.path.join(root, ".done")
        if not os.path.exists(done):
            os.makedirs(root, exist_ok=True)
            with zipfile.ZipFile(BytesIO(blob)) as zf:
                zf.extractall(root)
            with open(done, "w"):
                pass
        return root

    def _worker_accept_loop(self):
        import errno

        while not self.shutting_down:
            try:
                conn = self._worker_listener.accept()
            except OSError as e:
                # per-connection handshake failures must NOT kill the loop
                # (see Controller._accept_loop); only a closed listener ends it
                if self.shutting_down or e.errno in (errno.EBADF, errno.EINVAL):
                    return
                time.sleep(0.05)  # persistent errors (EMFILE) must not spin
                continue
            except Exception:  # noqa: BLE001 — failed authkey handshake
                continue
            threading.Thread(
                target=self._worker_handshake, args=(conn,), daemon=True
            ).start()

    def _worker_handshake(self, conn):
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            conn.close()
            return
        if not isinstance(msg, P.RegisterWorker):
            conn.close()
            return
        with self.workers_lock:
            w = self.workers.get(msg.worker_id)
            if w is None:
                conn.close()
                return
            w["conn"] = conn
        # register with the head either way: the head tracks identity (for
        # the worker's own control-plane ops) even when the AGENT schedules
        # onto it (agent-owned pool workers). The relay MUST precede any
        # actor_placed report on this FIFO connection — the head learns the
        # worker's identity + direct-call address before binding an actor.
        try:
            self._send(P.FromWorker(msg.worker_id, msg))
        except (OSError, EOFError):
            # head outage mid-handshake (restart window): the worker still
            # joins the LOCAL pool — a resumed head learns its identity
            # from later relayed traffic / the reconcile report, and
            # killing the handshake here would strand the worker's conn
            # unread forever
            pass
        fp = self._agent_owned.get(msg.worker_id)
        if fp is not None:
            self._on_local_worker_ready(msg.worker_id, fp)
        self.actor_spawner.on_worker_ready(
            msg.worker_id, getattr(msg, "direct_address", None)
        )
        self._worker_reader(msg.worker_id, conn)

    def _worker_reader(self, worker_id: WorkerID, conn):
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break
            try:
                self._route_worker_msg(worker_id, conn, msg)
            except Exception:  # noqa: BLE001
                logger.error(
                    "worker %s message failed:\n%s",
                    worker_id.hex()[:8], traceback.format_exc(),
                )
        with self.workers_lock:
            w = self.workers.pop(worker_id, None)
        self._on_local_worker_death(worker_id)
        # an unfinished creation lease backed by this worker re-places via
        # a retryable actor_creation_failed report
        self.actor_spawner.on_worker_death(worker_id)
        reason = "connection closed"
        if w is not None and w.get("proc") is not None:
            rc = w["proc"].poll()
            if rc is not None:
                reason = f"worker process exited with code {rc}"
        try:
            self._send(P.WorkerDied(worker_id, reason))
        except (OSError, EOFError):
            pass

    def _route_worker_msg(self, worker_id: WorkerID, conn, msg):
        """Intercept node-local data-plane ops; relay the rest to the head."""
        if isinstance(msg, P.Request) and msg.op == "shm_create":
            # Local arena allocation (the plasma CreateRequest; the head
            # controller does the same for ITS node's workers).
            self._reply_worker(conn, worker_id, msg.req_id, self._shm_create, msg.payload)
            return
        if isinstance(msg, P.Request) and msg.op == "pull_object_chunk":
            # Serve locally / pull from a replica-set peer — threaded so a
            # slow remote pull can't stall this worker's other replies.
            threading.Thread(
                target=self._reply_worker,
                args=(conn, worker_id, msg.req_id, self._pull_chunk, msg.payload),
                daemon=True,
            ).start()
            return
        if isinstance(msg, P.Request) and msg.op == "pull_into_arena":
            # node-level materialization of a remote object into THIS arena
            # (single-flight; the worker mmaps the result) — threaded: the
            # transfer can take seconds and must not stall other replies
            threading.Thread(
                target=self._reply_worker,
                args=(
                    conn, worker_id, msg.req_id, self._pull_into_arena,
                    msg.payload,
                ),
                daemon=True,
            ).start()
            return
        if isinstance(msg, P.Request) and msg.op == "transfer_stats":
            # node-local transfer counters (tests assert zero-re-transfer
            # through these; the head has its own under the same op)
            self._reply_worker(
                conn, worker_id, msg.req_id,
                lambda _p: self._snapshot_stats(), msg.payload,
            )
            return
        if isinstance(msg, P.Request) and msg.op == "report_observability":
            # buffer the worker's span/metric report; the node's merged
            # payload piggybacks on the report-batch tick (the head also
            # accepts this op directly — head-node workers have no agent)
            self._reply_worker(
                conn, worker_id, msg.req_id,
                self._queue_observability, msg.payload,
            )
            return
        if isinstance(msg, P.PutObject) and msg.kind == "plasma":
            # Seal locally before the head learns the location: a reader
            # that sees the entry must find the object already sealed.
            name, size = msg.payload
            self.store.seal(msg.object_id, name, size)
            self._track_seal(msg.object_id, name, size)
        elif isinstance(msg, P.TaskDone):
            for oid, kind, payload in msg.results:
                if kind == "plasma":
                    self.store.seal(oid, payload[0], payload[1])
                    self._track_seal(oid, payload[0], payload[1])
            if self._on_leased_task_done(worker_id, msg):
                return  # reported as AgentTaskDone; head never saw a dispatch
            if self.actor_spawner.on_creation_done(worker_id, msg):
                return  # reported as actor_placed / actor_creation_failed
        self._send(P.FromWorker(worker_id, msg))

    def _track_seal(self, object_id: ObjectID, name: str, size: int):
        key = object_id.binary()
        with self._resident_lock:
            if key not in self._resident:
                self._resident_order.append(key)
            self._resident[key] = (name, size)

    def _reply_worker(self, conn, worker_id, req_id, fn, payload):
        try:
            reply = P.Reply(req_id, fn(payload))
        except Exception as e:  # noqa: BLE001
            reply = P.Reply(req_id, None, error=f"{type(e).__name__}: {e}")
        with self.workers_lock:
            w = self.workers.get(worker_id)
        lock = w["lock"] if w is not None else threading.Lock()
        try:
            with lock:
                conn.send(reply)
        except (OSError, EOFError):
            pass

    def _shm_create(self, payload):
        from ray_tpu.exceptions import ObjectStoreFullError
        from ray_tpu._private.object_store import ObjectExistsError

        object_id, size = payload
        deadline = time.monotonic() + 10.0
        while True:
            try:
                return self.store.create_remote(object_id, size)
            except ObjectExistsError:
                entry = self.store.lookup(object_id)
                if entry is not None:
                    return ("exists", entry[0], entry[1])
                raise
            except ObjectStoreFullError:
                if self._spill_for(size):
                    continue
                if time.monotonic() > deadline:
                    raise
                # concurrent producers may seal (→ become spillable) soon
                time.sleep(0.1)

    def _spill_for(self, need_bytes: int) -> bool:
        """Move the coldest sealed residents to this host's disk until
        ``need_bytes`` is freed (the raylet-side half of object spilling,
        ``local_object_manager.h:113``). Readers holding stale arena
        locations re-resolve via validate-after-copy; the head entry is
        repointed through ``report_agent_spill``."""
        os.makedirs(self.spill_dir, exist_ok=True)
        freed = 0
        while freed < need_bytes:
            with self._resident_lock:
                if not self._resident_order:
                    return freed > 0
                key = self._resident_order.pop(0)
                entry = self._resident.pop(key, None)
            if entry is None:
                continue
            object_id = ObjectID(key)
            name, size = entry
            if key in self._replica_resident:
                # replicas are redundant copies: evict outright (no disk
                # write, no spill report — the primary serves re-pulls) and
                # stop advertising this node in the directory. UNLESS the
                # head answers "primary": the copy was promoted after its
                # original primary died — it is the object's LAST copy, so
                # fall through to the normal spill path below. On head
                # unreachability, also spill: losing redundancy is cheap,
                # losing the only copy is not.
                self._replica_resident.discard(key)
                try:
                    verdict = self.call_controller(
                        "unregister_replica", (object_id, self.arena_name)
                    )
                except Exception:  # noqa: BLE001 — can't tell: play safe
                    verdict = "primary"
                if verdict != "primary":
                    try:
                        self.store.delete(object_id)
                    except Exception:  # noqa: BLE001
                        continue
                    freed += size
                    logger.info(
                        "evicted replica %s (%d bytes)", object_id.hex(), size
                    )
                    continue
            try:
                total, data = self._read_local_chunk(object_id, entry, 0, size)
                path = os.path.join(self.spill_dir, f"{object_id.hex()}.bin")
                with open(path, "wb") as f:
                    f.write(data)
            except Exception:  # noqa: BLE001 — skip unreadable victims
                logger.warning("spill failed for %s", object_id.hex(), exc_info=True)
                continue
            self._spilled[key] = (path, size)
            try:
                verdict = self.call_controller(
                    "report_agent_spill", (object_id, path, size)
                )
            except Exception:  # noqa: BLE001
                # head unreachable: keep serving from the spill table; the
                # stale plasma entry still routes pulls here by object id
                verdict = None
                logger.warning("spill report failed for %s", object_id.hex())
            if verdict == "freed":
                # last ref dropped while we spilled: the object is dead
                self._spilled.pop(key, None)
                try:
                    os.unlink(path)
                except OSError:
                    pass
            self.store.delete(object_id)
            freed += size
            logger.info("spilled %s (%d bytes) to disk", object_id.hex(), size)
        return True

    # ----------------------------------------------------------- data plane

    def _bump_stat(self, name: str, n: int = 1):
        with self._stats_lock:
            self.transfer_stats[name] += n

    def _snapshot_stats(self) -> dict:
        with self._stats_lock:
            return dict(self.transfer_stats)

    def _make_fetcher(self, object_id: ObjectID) -> P.ReplicaFetcher:
        """Per-chunk fetch over the object's replica set (owner + every
        registered replica, self excluded), load-spread with mid-pull
        failover; the head relay serves when no peer can (it re-resolves,
        recovers, or raises ObjectLostError)."""
        sources = [
            a
            for a in self._object_locations(object_id)
            if a and a != self.data_address
        ]

        def head_fetch(offset: int, length: int):
            return self.call_controller(
                "pull_object_chunk", (object_id, offset, length)
            )

        def on_fail(address: str, _err):
            # a dead/stale source must not eat the 30 s TTL: drop it from
            # the cached set (and its pooled conns) immediately
            self._invalidate_location(object_id, address)

        return P.ReplicaFetcher(
            self._peers,
            object_id.binary(),
            sources,
            fallback=head_fetch,
            on_source_fail=on_fail,
        )

    def _pull_chunk(self, payload):
        """A local worker wants [offset, offset+length) of an object that is
        not in this node's arena (or was relocated). Resolution order:
        local arena/spill → any replica-set peer (direct) → head relay."""
        object_id, offset, length = payload
        local = self._serve_local(object_id, offset, length)
        if local is not None:
            return local
        fetcher = self._make_fetcher(object_id)
        result = fetcher(offset, length)
        if fetcher.peer_chunks:
            self._bump_stat("peer_chunks_pulled", fetcher.peer_chunks)
        if fetcher.fallback_chunks:
            self._bump_stat("head_chunks_pulled", fetcher.fallback_chunks)
        return result

    def _serve_local(
        self, object_id: ObjectID, offset: int, length: int, spill_files=None
    ):
        """Chunk of a locally resident object (arena or spill), else None.
        ``spill_files`` is an optional per-serve-connection handle cache so
        a chunked read of one spilled object opens its file once, not once
        per chunk (owned — and closed — by the connection loop)."""
        entry = self.store.lookup(object_id)
        if entry is not None:
            try:
                return self._read_local_chunk(object_id, entry, offset, length)
            except Exception:  # noqa: BLE001 — relocated mid-read
                pass
        spilled = self._spilled.get(object_id.binary())
        if spilled is not None:
            path, size = spilled
            try:
                if spill_files is None:
                    with open(path, "rb") as f:
                        f.seek(offset)
                        return (size, f.read(min(length, size - offset)))
                fh = spill_files.get(object_id.binary())
                if fh is None:
                    while len(spill_files) >= 32:  # bound the per-conn cache
                        # evict the OLDEST handle (dict preserves insertion
                        # order; popitem() would churn the newest slot)
                        oldest = next(iter(spill_files))
                        old = spill_files.pop(oldest)
                        try:
                            old.close()
                        except OSError:
                            pass
                    fh = open(path, "rb")
                    spill_files[object_id.binary()] = fh
                fh.seek(offset)
                return (size, fh.read(min(length, size - offset)))
            except OSError:
                return None
        return None

    def _object_locations(self, object_id: ObjectID) -> list:
        """Every data address serving this object (owner + replicas), via
        the controller's location directory; cached with a short TTL and
        invalidated eagerly on free/failure (see _location_cache)."""
        key = object_id.binary()
        now = time.monotonic()
        hit = self._location_cache.get(key)
        if hit is not None and hit[1] > now:
            return list(hit[0])
        locs = list(self.call_controller("object_locations", object_id) or [])
        self._location_cache[key] = (locs, now + 30.0)
        if len(self._location_cache) > 4096:
            self._location_cache = {
                k: v for k, v in self._location_cache.items() if v[1] > now
            }
        return list(locs)

    def _invalidate_location(self, object_id: ObjectID, address: Optional[str] = None):
        """Eager cache invalidation: the whole entry (freed/lost object) or
        one failing source (dead peer) — never wait out the TTL."""
        key = object_id.binary()
        if address is None:
            self._location_cache.pop(key, None)
            return
        hit = self._location_cache.get(key)
        if hit is not None and address in hit[0]:
            try:
                hit[0].remove(address)
            except ValueError:
                pass
        self._peers.drop(address)

    # ------------------------------------------------- pull-into-arena

    def _serve_entry(self, object_id: ObjectID):
        """The locally-materialized (kind, payload) entry for this object,
        else None — what a same-host worker can read without any RPC."""
        entry = self.store.lookup(object_id)
        if entry is not None:
            return ("plasma", (entry[0], entry[1]))
        spilled = self._spilled.get(object_id.binary())
        if spilled is not None:
            return ("spilled", spilled)  # same-host readers open the path
        return None

    def _pull_into_arena(self, payload):
        """Materialize a remote object into THIS node's arena and register
        the node as a replica (reference: pulls land in the local plasma
        store, ``pull_manager.h:49``; the directory registration makes this
        node a broadcast source). Single-flight per object: concurrent
        local readers coalesce into ONE cross-node transfer. Returns the
        local (kind, payload) entry, or None when the caller should fall
        back to a private direct pull."""
        object_id, size = payload
        key = object_id.binary()
        entry = self._serve_entry(object_id)
        if entry is not None:
            self._bump_stat("arena_replica_hits")
            return entry
        with self._pulls_lock:
            ev = self._pulls.get(key)
            leader = ev is None
            if leader:
                ev = self._pulls[key] = threading.Event()
        if not leader:
            # bounded, liveness-aware wait for the in-flight transfer
            deadline = time.monotonic() + 600.0
            while not ev.wait(timeout=1.0):
                if self.shutting_down or time.monotonic() > deadline:
                    return None
            entry = self._serve_entry(object_id)
            if entry is not None:
                self._bump_stat("arena_replica_hits")
            return entry  # None → the leader failed; caller direct-pulls
        try:
            return self._pull_into_arena_leader(object_id, size)
        finally:
            with self._pulls_lock:
                self._pulls.pop(key, None)
            ev.set()

    def _pull_into_arena_leader(self, object_id: ObjectID, size: int):
        from ray_tpu._private.object_store import parse_arena_location

        name = self._shm_create((object_id, size))
        if isinstance(name, tuple) and name[0] == "exists":
            return ("plasma", (name[1], name[2]))  # sealed concurrently
        offset = parse_arena_location(name)[1]
        view = self.store.arena.view(offset, size)
        fetcher = self._make_fetcher(object_id)
        try:
            P.pull_windowed(
                fetcher,
                P._buffer_sink(view),
                size,
                self._transfer_chunk_bytes,
                self._transfer_window,
            )
        except BaseException:
            # reclaim the unsealed allocation — a failed pull must not pin
            # arena space until the next alloc collides with the stale id
            try:
                self.store.arena.delete(object_id.binary())
            except Exception:  # noqa: BLE001
                pass
            raise
        self.store.seal(object_id, name, size)
        self._track_seal(object_id, name, size)
        self._replica_resident.add(object_id.binary())
        self._bump_stat("peer_chunks_pulled", fetcher.peer_chunks)
        self._bump_stat("head_chunks_pulled", fetcher.fallback_chunks)
        self._bump_stat("arena_pulls")
        try:
            verdict = self.call_controller(
                "register_replica", (object_id, name, size)
            )
        except Exception:  # noqa: BLE001 — head unreachable: serve locally;
            verdict = None  # reconnect resets all local state anyway
        if verdict == "freed":
            # the object died while its bytes were in flight: a freed-then-
            # recreated id must not find this stale copy
            self._replica_resident.discard(object_id.binary())
            with self._resident_lock:
                if self._resident.pop(object_id.binary(), None) is not None:
                    try:
                        self._resident_order.remove(object_id.binary())
                    except ValueError:
                        pass
            try:
                self.store.delete(object_id)
            except Exception:  # noqa: BLE001
                pass
            raise AgentError(f"object {object_id.hex()} freed during pull")
        return ("plasma", (name, size))

    def _read_local_chunk(self, object_id: ObjectID, entry, offset: int, length: int):
        from ray_tpu._private.object_store import (
            ObjectRelocatedError,
            parse_arena_location,
        )

        name, size = entry
        loc = parse_arena_location(name)
        chunk = bytes(self.store.arena.view(loc[1] + offset, min(length, size - offset)))
        got = self.store.arena.lookup(object_id.binary())
        if got is None or got[0] != loc[1]:
            raise ObjectRelocatedError(name)
        return (size, chunk)

    def _data_accept_loop(self):
        import errno

        while not self.shutting_down:
            try:
                conn = self._data_listener.accept()
            except OSError as e:
                if self.shutting_down or e.errno in (errno.EBADF, errno.EINVAL):
                    return
                time.sleep(0.05)  # persistent errors (EMFILE) must not spin
                continue
            except Exception:  # noqa: BLE001
                continue
            threading.Thread(
                target=self._data_serve, args=(conn,), daemon=True
            ).start()

    def _data_serve(self, conn):
        """Serve chunk reads of locally resident objects to one peer.
        Spilled-object reads keep an open file handle per (connection,
        object) — a windowed pull of a spilled object costs one open, not
        one per chunk — released with the connection."""
        spill_files: dict[bytes, Any] = {}
        try:
            while not self.shutting_down:
                try:
                    req = conn.recv()
                except (EOFError, OSError):
                    return
                try:
                    kind, oid_bytes, offset, length = req
                    assert kind == "chunk"
                    object_id = ObjectID(oid_bytes)
                    reply = self._serve_local(
                        object_id, offset, length, spill_files=spill_files
                    )
                    if reply is None:
                        reply = ("error", f"object {object_id.hex()} not resident")
                except Exception as e:  # noqa: BLE001
                    reply = ("error", f"{type(e).__name__}: {e}")
                try:
                    conn.send(reply)
                except (EOFError, OSError):
                    return
        finally:
            for fh in spill_files.values():
                try:
                    fh.close()
                except OSError:
                    pass
            try:
                conn.close()
            except OSError:
                pass

    # -------------------------------------------------------------- lifecycle

    def shutdown(self):
        self.shutting_down = True
        # wake lease-spawn waiters; in-flight creations die with the agent
        self.actor_spawner.reset()
        self.actor_spawner.close()
        # release pull-into-arena followers before tearing the store down
        with self._pulls_lock:
            pulls, self._pulls = self._pulls, {}
        for ev in pulls.values():
            ev.set()
        with self.workers_lock:
            workers = list(self.workers.values())
            self.workers.clear()
        for w in workers:
            proc = w.get("proc")
            if proc is not None:
                try:
                    proc.terminate()
                except OSError:
                    pass
        self._chips.drain()  # the chips are free when the agent is gone
        for listener in (self._worker_listener, self._data_listener):
            try:
                listener.close()
            except OSError:
                pass
        try:
            os.unlink(self.worker_sock)
        except OSError:
            pass
        try:
            self.store.shutdown()
        except Exception:  # noqa: BLE001
            pass
        self._peers.close()
        import shutil

        shutil.rmtree(self.spill_dir, ignore_errors=True)
        with self._reply_cv:
            self._reply_cv.notify_all()


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="ray-tpu node agent (raylet analog)"
    )
    parser.add_argument("--address", required=True, help="head host:port")
    parser.add_argument("--authkey", default=None, help="cluster authkey hex")
    parser.add_argument("--resources", default="{}", help="JSON resource dict")
    parser.add_argument("--labels", default="{}", help="JSON label dict")
    parser.add_argument("--base-dir", default=None)
    parser.add_argument("--object-store-memory", type=int, default=1 * 1024**3)
    parser.add_argument("--data-port", type=int, default=0)
    parser.add_argument("--node-ip", default=None)
    args = parser.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    # stack dumps on demand (kill -USR1 <agent-pid>): the debugging analog
    # of the dashboard's worker stack-dump channel, for the agent itself
    import faulthandler
    import signal as _signal

    faulthandler.register(_signal.SIGUSR1)
    authkey_hex = args.authkey or os.environ.get("RAY_TPU_AUTHKEY")
    if not authkey_hex:
        from ray_tpu._private.protocol import token_to_authkey

        token = os.environ.get("RAY_TPU_CLUSTER_TOKEN")
        if not token:
            raise SystemExit(
                "pass --authkey, RAY_TPU_AUTHKEY, or RAY_TPU_CLUSTER_TOKEN"
            )
        authkey_hex = token_to_authkey(token).hex()
    resources = json.loads(args.resources) or None
    agent = NodeAgent(
        args.address,
        bytes.fromhex(authkey_hex),
        resources=resources,
        labels=json.loads(args.labels),
        base_dir=args.base_dir,
        object_store_memory=args.object_store_memory,
        data_port=args.data_port,
        node_ip=args.node_ip,
    )
    # SIGTERM is the preemption channel (spot reclaim / maintenance event /
    # operator kill): announce a termination notice to the head and drain
    # within RAY_TPU_PREEMPT_NOTICE_S instead of dying with leased work and
    # sole-copy objects. Handled off the signal frame — announce_preemption
    # blocks on a controller round-trip, which a signal handler must not.
    notice_s = float(os.environ.get("RAY_TPU_PREEMPT_NOTICE_S", "30.0"))

    def _on_sigterm(signum, frame):  # noqa: ARG001
        threading.Thread(
            target=agent.announce_preemption, args=(notice_s,),
            daemon=True, name="agent-preempt",
        ).start()

    _signal.signal(_signal.SIGTERM, _on_sigterm)
    agent.serve_forever()


if __name__ == "__main__":
    main()
