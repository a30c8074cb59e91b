"""Agent-side actor creation: the node-local half of the creation lease.

The head controller's placement decision for an agent-node actor is a
*creation lease* (``protocol.LeaseActor``) granted to this node's agent —
resources charged at grant, exactly as for task leases. From there the
``ActorSpawner`` owns the WHOLE local lifecycle, the way the reference's
raylet does once ``GcsActorScheduler`` leases a creation to it
(``gcs_actor_scheduler.cc:55``):

- worker acquisition: pop an idle compatible pool worker, or spawn a fresh
  process (runtime-env staging/venv build included);
- the readiness/registration handshake (the worker registers with THIS
  agent; its ``RegisterWorker`` — including the direct actor-call listener
  address — relays to the head on the agent's FIFO connection, so identity
  always precedes the placement report);
- creation-task dispatch and completion interception;
- the placement report back to the head: the ``actor_placed`` /
  ``actor_creation_failed(reason, retryable)`` request ops, retried across
  transient transport/chaos failures (idempotent on the head).

With N agents, N creations pipeline fully in parallel — the head runs zero
spawn threads and zero registration waits for agent-node actors.

Failure matrix (the head applies budget policy; see
``Controller._on_actor_creation_failed``):

==========================  =========  ==================================
local failure               retryable  agent-side action
==========================  =========  ==================================
agent draining              yes        reject immediately (re-place free)
spawn / venv build failed   no/yes     report; no worker to clean up
registration timeout        yes        kill the half-spawned worker
worker died mid-creation    yes        report from the reader teardown
``__init__`` raised         no         report error results; the worker
                                       SURVIVES and rejoins the local
                                       task pool (no leaked slot)
==========================  =========  ==================================
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from ray_tpu._private import locktrace
from ray_tpu._private import protocol as P
from ray_tpu._private.ids import WorkerID

logger = logging.getLogger("ray_tpu.agent")


class _Lease:
    """One in-flight creation lease (guarded by ActorSpawner._lock unless
    noted; ``ready`` is the registration-handshake event)."""

    def __init__(self, lease: "P.LeaseActor"):
        self.lease = lease
        self.key = lease.spec.actor_id.binary()
        self.worker_id: Optional[WorkerID] = None
        self.ready = threading.Event()
        self.direct_address: Optional[str] = None
        self.pooled = False
        self.dispatched = False
        # exactly-once report: every finish path claims this flag first
        self.reported = False
        # set on reset/shutdown: aborts report backoff waits immediately
        self.abort = threading.Event()
        # agent-plane tracing (creation lease recv → placement report)
        self.recv_t = time.time()
        self.trace_span: Optional[str] = None
        self.trace_parent: Optional[str] = None


class ActorSpawner:
    def __init__(self, agent):
        self._agent = agent
        self._lock = locktrace.register_lock(
            "actor_spawner.lock", threading.Lock()
        )
        self._leases: dict[bytes, _Lease] = {}  # actor_id binary -> lease
        self._by_worker: dict[WorkerID, bytes] = {}
        self._by_task: dict[bytes, bytes] = {}  # creation task_id -> actor key
        # Batched placement reports (PR 12): concurrent lease completions
        # coalesce into ONE actor_placed_batch request per flush tick — a
        # gang bring-up of N actors on this node pays one verdict round
        # trip, not N. Window shared with the agent's done-report knob
        # (config agent_report_flush_ms / env RAY_TPU_AGENT_REPORT_FLUSH_MS,
        # resolved once by the agent); 0 restores a request per report.
        self._placed_window_s = getattr(agent, "_report_window_s", 0.002)
        self._placed_queue: list = []  # (payload, verdict box, done event)
        self._placed_lock = threading.Lock()
        self._placed_wake = threading.Event()
        self._placed_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ entry points

    def on_lease(self, lease: "P.LeaseActor"):
        """A creation lease arrived from the head (called on the agent's
        head-dispatch loop — all real work happens on a per-lease thread so
        creations pipeline and reports can await their replies)."""
        st = _Lease(lease)
        with self._lock:
            self._leases[st.key] = st
            self._by_task[lease.spec.task_id.binary()] = st.key
        threading.Thread(
            target=self._run_lease,
            args=(st,),
            daemon=True,
            name=f"actor-spawn-{lease.spec.actor_id.hex()[:8]}",
        ).start()

    def on_worker_ready(self, worker_id: WorkerID, direct_address):
        """A worker this spawner started finished its registration
        handshake (called from the agent's worker-handshake path AFTER the
        RegisterWorker relay to the head)."""
        with self._lock:
            key = self._by_worker.get(worker_id)
            st = self._leases.get(key) if key is not None else None
            if st is None:
                return
            st.direct_address = direct_address
        st.ready.set()

    def on_creation_done(self, worker_id: WorkerID, msg) -> bool:
        """Intercept TaskDone for creation tasks this spawner dispatched
        (plasma results are already sealed locally by the agent's generic
        TaskDone handling). Returns False when the task isn't ours."""
        with self._lock:
            key = self._by_task.get(msg.task_id.binary())
            st = self._leases.get(key) if key is not None else None
        if st is None or st.worker_id != worker_id:
            return False
        if not self._claim(st):
            return True  # another path (death/reset) already reported
        failed = any(kind == "error" for _, kind, _ in msg.results)
        if failed:
            # a raising __init__ does not kill the worker: report the error
            # payloads (the head seals them into the creation returns and
            # marks the actor DEAD), then hand the worker back to the
            # local task pool — parity with the head's own pool behavior
            self._report(
                "actor_creation_failed",
                (st.lease.spec.actor_id, "creation task failed", False,
                 msg.results, msg.exec_ms),
                st,
            )
            self._release_survivor(st)
        else:
            verdict = self._report(
                "actor_placed",
                (st.lease.spec.actor_id, st.worker_id, st.direct_address,
                 msg.results, msg.exec_ms),
                st,
            )
            if verdict == "dead":
                # killed/superseded while we were creating: reap the orphan
                self._kill_worker(st.worker_id)
            else:
                # recovery registry: a restarted head rebuilds this binding
                # from the agent's reconcile report (a None verdict — head
                # unreachable — still registers: the actor IS alive here,
                # and reconcile is exactly how the new head learns it)
                self._agent.note_actor_placed(
                    st.key, st.worker_id, st.direct_address
                )
        if st.trace_span is not None:
            from ray_tpu.util import tracing

            tid_hex = st.lease.spec.task_id.hex()
            tracing.record_span(
                "agent.actor_create",
                st.recv_t,
                time.time(),
                trace_id=st.lease.spec.trace_id,
                span_id=st.trace_span,
                parent_id=st.trace_parent,
                plane="agent",
                task_id=tid_hex,
                pooled=st.pooled,
            )
        self._forget(st)
        return True

    def on_worker_death(self, worker_id: WorkerID):
        """The worker backing an unfinished lease died (reader teardown /
        pre-handshake reap): report a retryable creation failure so the
        head re-places the lease."""
        with self._lock:
            key = self._by_worker.get(worker_id)
            st = self._leases.get(key) if key is not None else None
        if st is None or not self._claim(st):
            return
        st.ready.set()  # unpark a registration waiter
        self._report(
            "actor_creation_failed",
            (st.lease.spec.actor_id, "worker died during actor creation",
             True, [], 0.0),
            st,
        )
        self._forget(st)

    def outstanding(self) -> int:
        """Creation leases not yet reported (drain-quiesce accounting)."""
        with self._lock:
            return sum(1 for st in self._leases.values() if not st.reported)

    def held_creation_task_ids(self) -> list:
        """Creation task ids still owned by this spawner (head-recovery
        reconcile: the restarted head re-parks them under this node and
        our in-flight report binds/fails them through the normal
        idempotent path)."""
        with self._lock:
            return [
                st.lease.spec.task_id.binary()
                for st in self._leases.values()
            ]

    def drop_creation_leases(self, task_id_bins) -> None:
        """Reconcile verdict: these creation leases were never journaled by
        the restarted head (orphans) — kill their workers, report nothing."""
        victims = []
        with self._lock:
            for tid in task_id_bins:
                key = self._by_task.get(tid)
                st = self._leases.get(key) if key is not None else None
                if st is not None:
                    victims.append(st)
        for st in victims:
            if self._claim(st):
                st.abort.set()
                st.ready.set()
                self._kill_worker(st.worker_id)
                self._forget(st)

    def reset(self):
        """Head reconnect / agent shutdown: the head-side lease state died
        with the old incarnation — drop everything, wake waiters, and make
        sure no stale report reaches the NEW head."""
        with self._lock:
            leases = list(self._leases.values())
            self._leases.clear()  # (abort events set below, outside the lock)
            self._by_worker.clear()
            self._by_task.clear()
            for st in leases:
                st.reported = True
        for st in leases:
            st.abort.set()  # cancel in-flight report backoffs
            st.ready.set()
        # queued-but-unsent placement reports reference the dead head
        # incarnation: drop them (their waiters see abort / an empty box)
        with self._placed_lock:
            placed, self._placed_queue = self._placed_queue, []
        for _, _, done in placed:
            done.set()

    def close(self):
        """Agent shutdown: wake and join the placed-report flusher (its
        loop exits on ``agent.shutting_down``; queued reports were already
        dropped by ``reset``)."""
        self._placed_wake.set()
        locktrace.join_if_alive(self._placed_thread, timeout=1.0)

    # ------------------------------------------------------------- lease body

    def _run_lease(self, st: _Lease):
        lease = st.lease
        agent = self._agent
        if agent.draining:
            # quiesce race: the grant crossed the drain — reject so the
            # head re-places elsewhere without charging any budget
            if self._claim(st):
                self._report(
                    "actor_creation_failed",
                    (lease.spec.actor_id, "draining", True, [], 0.0),
                    st,
                )
                self._forget(st)
            return
        pool_fp = (lease.tpu_chips, tuple(sorted(lease.env_vars.items())))
        wid = None
        if self._poolable(lease):
            # pool pop: an idle compatible task worker becomes the actor's
            # dedicated worker (it already registered — skip the handshake)
            wid = agent.pop_idle_worker(pool_fp)
        if wid is not None:
            with self._lock:
                st.worker_id = wid
                st.pooled = True
                self._by_worker[wid] = st.key
            st.ready.set()
        else:
            wid = WorkerID.from_random()
            with self._lock:
                st.worker_id = wid
                self._by_worker[wid] = st.key
            fail = agent._spawn_worker(
                P.SpawnWorker(
                    wid,
                    dict(lease.env_vars),
                    lease.tpu_chips,
                    lease.fingerprint,
                    lease.packages,
                )
            )
            if fail is not None:
                if self._claim(st):
                    # a broken runtime env is NOT retryable (re-placing
                    # would rebuild the same doomed venv forever); a plain
                    # exec failure is
                    retryable = not fail.startswith("pip env failed")
                    self._report(
                        "actor_creation_failed",
                        (lease.spec.actor_id, fail, retryable, [], 0.0),
                        st,
                    )
                    self._forget(st)
                return
            if not self._await_registration(st):
                return
        # dispatch the creation task; completion (or the worker's death)
        # continues on the worker's reader thread
        st.dispatched = True
        if agent._trace_gate(lease.spec):
            # re-point the spec's dispatch parent at the agent span (the
            # head's sched span becomes OUR parent) before the wire
            st.trace_parent = getattr(lease.spec, "sched_span_id", None)
            st.trace_span = f"{lease.spec.task_id.hex()}:agent"
            lease.spec.sched_span_id = st.trace_span
        if not agent._send_to_worker(
            wid, P.ExecuteTask(lease.spec, lease.resolved_args)
        ):
            if self._claim(st):
                self._report(
                    "actor_creation_failed",
                    (lease.spec.actor_id,
                     "worker died during actor creation", True, [], 0.0),
                    st,
                )
                self._forget(st)

    def _await_registration(self, st: _Lease) -> bool:
        """Bounded wait for the spawned worker's handshake, polling process
        liveness (a worker that dies before connecting has no reader thread
        to notice). Reports and returns False on timeout/death."""
        agent = self._agent
        deadline = time.monotonic() + agent._register_timeout_s
        while not st.ready.wait(timeout=0.5):
            if st.reported:
                return False  # death path won the race
            if agent.shutting_down:
                return False
            with agent.workers_lock:
                w = agent.workers.get(st.worker_id)
            proc = w.get("proc") if w is not None else None
            if w is None or (proc is not None and proc.poll() is not None):
                reason = "worker died before registering"
            elif time.monotonic() > deadline:
                reason = "worker failed to register in time"
            else:
                continue
            if not self._claim(st):
                return False
            with agent.workers_lock:
                w = agent.workers.get(st.worker_id)
                if w is not None and w.get("conn") is None:
                    agent.workers.pop(st.worker_id, None)
            if proc is not None:
                try:
                    proc.terminate()
                except OSError:
                    pass
            self._report(
                "actor_creation_failed",
                (st.lease.spec.actor_id, reason, True, [], 0.0),
                st,
            )
            self._forget(st)
            return False
        return not st.reported

    # --------------------------------------------------------------- plumbing

    def _claim(self, st: _Lease) -> bool:
        """Exactly-once report election across the racing finish paths
        (creation done / worker death / registration timeout / reset)."""
        with self._lock:
            if st.reported:
                return False
            st.reported = True
            return True

    def _report(self, op: str, payload, st: _Lease, attempts: int = 8):
        """Deliver a lease outcome to the head, retrying transient
        transport/chaos failures with backoff (bounded waits on the lease's
        abort event so reset/shutdown cancels instantly). The head's
        handlers are idempotent (duplicate ``actor_placed`` answers
        "ok"/"dead"), so a lost REPLY is safe to re-send. Returns the
        head's verdict, or None when the head stayed unreachable — node
        removal or the reconnect reset re-places the lease in that case.

        Successful placements ride the COALESCED channel (one
        ``actor_placed_batch`` round trip per flush tick, N verdicts);
        failure reports stay per-lease — they are rare and their payloads
        carry case-specific retryability."""
        if op == "actor_placed" and self._placed_window_s > 0:
            return self._report_placed(payload, st)
        for attempt in range(attempts):
            if self._agent.shutting_down:
                return None
            # resumed re-registration awaiting its reconcile verdict: hold
            # the report until the gate opens or its bounded deadline lapses
            # (escaping early would hit a still-RECOVERING head and get a
            # spurious "dead" verdict for a healthy worker)
            self._agent.wait_reports_open()
            try:
                return self._agent.call_controller(op, payload, timeout=30.0)
            except Exception as e:  # noqa: BLE001 — retried, then reconciled
                logger.warning(
                    "%s report failed (attempt %d/%d): %s",
                    op, attempt + 1, attempts, e,
                )
                if st.abort.wait(timeout=min(0.2 * 2 ** attempt, 2.0)):
                    return None  # reset/shutdown: this state died
        return None

    # ------------------------------------------- batched placement reports

    def _report_placed(self, payload, st: _Lease):
        """Queue one placement for the coalesced actor_placed_batch channel
        and wait for its verdict (None when the head stayed unreachable or
        this lease state died in a reset)."""
        box: list = []
        done = threading.Event()
        with self._placed_lock:
            self._placed_queue.append((payload, box, done))
        self._ensure_placed_thread()
        self._placed_wake.set()
        while not done.wait(timeout=0.5):
            if st.abort.is_set() or self._agent.shutting_down:
                return None
        return box[0] if box else None

    def _ensure_placed_thread(self):
        if self._placed_thread is not None and self._placed_thread.is_alive():
            return
        with self._placed_lock:
            if self._placed_thread is None or not self._placed_thread.is_alive():
                self._placed_thread = threading.Thread(
                    target=self._placed_flush_loop, daemon=True,
                    name="actor-placed-flush",
                )
                self._placed_thread.start()

    def _placed_flush_loop(self):
        while not self._agent.shutting_down:
            self._placed_wake.wait(timeout=0.5)
            self._placed_wake.clear()
            if self._placed_window_s:
                # coalescing beat: a gang bring-up finishes N creations
                # nearly at once — one breath batches their reports
                time.sleep(self._placed_window_s)
            self._flush_placed()
        self._flush_placed()

    def _flush_placed(self, attempts: int = 8):
        with self._placed_lock:
            batch, self._placed_queue = self._placed_queue, []
        if not batch:
            return
        payloads = [p for p, _, _ in batch]
        verdicts = None
        for attempt in range(attempts):
            if self._agent.shutting_down:
                break
            # hold placements while a resume awaits its reconcile verdict
            # (bounded by the agent's hold deadline, like _flush_reports)
            self._agent.wait_reports_open()
            try:
                verdicts = self._agent.call_controller(
                    "actor_placed_batch", payloads, timeout=30.0
                )
                break
            except Exception as e:  # noqa: BLE001 — retried, then reconciled
                logger.warning(
                    "actor_placed_batch failed (attempt %d/%d): %s",
                    attempt + 1, attempts, e,
                )
                time.sleep(min(0.2 * 2 ** attempt, 2.0))
        for i, (_, box, done) in enumerate(batch):
            if verdicts is not None and i < len(verdicts):
                box.append(verdicts[i])
            done.set()

    @staticmethod
    def _poolable(lease: "P.LeaseActor") -> bool:
        """May this lease's worker come from / return to the agent's task
        pool? Package-staged and pip-venv workers are not pool-compatible:
        the pool is keyed on (tpu, env_vars) only, and task leases never
        carry packages or a pip spec (``Controller._leasable`` excludes
        them), so such a worker would sit in an unreachable bucket holding
        a pool-cap slot forever."""
        return (
            not lease.packages
            and "RAY_TPU_PIP_SPEC" not in lease.env_vars
        )

    def _release_survivor(self, st: _Lease):
        """Return a worker that survived a raising ``__init__`` to the
        local task pool; non-poolable (package/venv) workers retire."""
        if not self._poolable(st.lease):
            self._kill_worker(st.worker_id)
            return
        fp = (
            st.lease.tpu_chips,
            tuple(sorted(st.lease.env_vars.items())),
        )
        self._agent.adopt_idle_worker(st.worker_id, fp)

    def _kill_worker(self, worker_id: Optional[WorkerID]):
        if worker_id is None:
            return
        with self._agent.workers_lock:
            w = self._agent.workers.get(worker_id)
        proc = w.get("proc") if w is not None else None
        if proc is not None:
            try:
                proc.terminate()
            except OSError:
                pass

    def _forget(self, st: _Lease):
        with self._lock:
            self._leases.pop(st.key, None)
            self._by_task.pop(st.lease.spec.task_id.binary(), None)
            if st.worker_id is not None:
                cur = self._by_worker.get(st.worker_id)
                if cur == st.key:
                    del self._by_worker[st.worker_id]
