"""ServeController: reconciles target state into replica actors.

Reference: ``python/ray/serve/_private/controller.py:92`` (ServeController)
+ ``deployment_state.py:1391`` (replica rollout/scaling state machines) +
``autoscaling_state.py`` (queue-metric autoscaling). One controller actor per
cluster, named ``serve-controller``; a background reconcile loop:

  target replicas  ->  start/stop replica actors (rolling, health-checked)
  replica metrics  ->  autoscaling decisions between min/max

TPU delta: a replica can be gang-scheduled on a pod slice via
``ray_actor_options={"num_tpus": n}`` (or ``{"resources": {"TPU": n}}``) —
the scheduler's slice-aware placement does the rest; multi-host replicas
come from the LLM layer building a placement group per engine replica.
"""

from __future__ import annotations

import logging
import threading
import time
import traceback
from typing import Any, Optional

import ray_tpu
from ray_tpu._private import locktrace

logger = logging.getLogger(__name__)

CONTROLLER_NAME = "serve-controller"


class _DeploymentState:
    def __init__(self, name: str, spec: dict):
        self.name = name
        self.spec = spec  # serialized target, init payload, config fields
        self.replicas: dict[str, Any] = {}  # replica_name -> actor handle
        self.target = spec["initial_replicas"]
        self.next_replica_id = 0
        self.last_scale_t = 0.0
        self.last_health_t = 0.0
        self.replica_started_t: dict[str, float] = {}
        self.replica_healthy_once: set[str] = set()
        # replica name -> first time its actor was observed ALIVE (i.e.
        # __init__ returned). The hung-replica timeout clock starts HERE,
        # not at actor submission: a replica still constructing (first jit
        # can take minutes on TPU) is STARTING, not hung (reference: the
        # slow-startup states of deployment_state.py:1391).
        self.replica_alive_t: dict[str, float] = {}
        # replica name -> code_version it was started with (rolling updates)
        self.replica_code: dict[str, str] = {}
        # long-poll versioning: RANDOMIZED start (reference long_poll uses
        # random snapshot ids) so a restarted controller's counter can never
        # coincide with a listener's stale version and silently block
        import random as _random

        self.version = _random.getrandbits(62)
        self.metric_window: list[tuple[float, float]] = []  # (ts, ongoing)
        self.status = "UPDATING"


class ServeControllerActor:
    def __init__(self):
        self._deployments: dict[str, _DeploymentState] = {}
        self._apps: dict[str, dict] = {}  # app name -> {ingress, route_prefix}
        # proxy endpoint table (reference: the proxy state the controller
        # tracks in _private/proxy_state.py): proxy_id -> endpoint record.
        # Proxies re-register periodically; the timestamp doubles as a
        # liveness heartbeat and stale entries are reaped by the reconciler.
        self._proxies: dict[str, dict] = {}
        self._proxy_tombstones: dict[str, float] = {}  # incarnation -> t
        self._lock = locktrace.register_lock(
            "serve.controller_lock", threading.RLock()
        )
        # long-poll: handles block here until a replica set changes
        # (reference: serve/_private/long_poll.py config push)
        self._change_cv = threading.Condition(self._lock)
        # serializes whole reconcile passes: deploy_application's inline pass
        # must not interleave with the background loop (both would observe the
        # same replica deficit and start duplicates)
        self._reconcile_mutex = threading.Lock()
        self._stop = threading.Event()
        self._loop = threading.Thread(
            target=self._reconcile_loop, daemon=True, name="serve-reconcile"
        )
        self._loop.start()

    # -- deploy API ---------------------------------------------------------

    def deploy_application(self, app_name: str, route_prefix: str,
                           deployments: list[dict], ingress_name: str):
        import hashlib

        with self._lock:
            for spec in deployments:
                name = spec["name"]
                # code version: replicas running a different version are
                # ROLLED (replaced one at a time with graceful drain) by the
                # reconciler — reference: DeploymentState version rollout,
                # ``_private/deployment_state.py:1391``
                spec["code_version"] = hashlib.sha256(
                    spec["serialized_target"] + spec["init_args_payload"]
                ).hexdigest()[:16]
                existing = self._deployments.get(name)
                if existing is None:
                    self._deployments[name] = _DeploymentState(name, spec)
                else:
                    existing.spec = spec
                    existing.target = spec["initial_replicas"]
                    existing.status = "UPDATING"
                    # config rollout: reconfigure live replicas in place
                    # (code rollout happens in reconcile via code_version)
                    for h in list(existing.replicas.values()):
                        try:
                            h.reconfigure.remote(spec.get("user_config"))
                        except Exception:
                            pass
            self._apps[app_name] = {
                "ingress": ingress_name,
                "route_prefix": route_prefix,
                "deployments": [d["name"] for d in deployments],
            }
        self._reconcile_once()
        return True

    def delete_application(self, app_name: str):
        # exclude reconcile passes: a concurrent pass could otherwise start a
        # replica for the deployment we are deleting (orphan actor)
        with self._reconcile_mutex, self._lock:
            app = self._apps.pop(app_name, None)
            if not app:
                return False
            still_used = {
                d for a in self._apps.values() for d in a["deployments"]
            }
            for dname in app["deployments"]:
                if dname in still_used:
                    continue
                state = self._deployments.pop(dname, None)
                if state:
                    for h in state.replicas.values():
                        self._kill_replica(h)
        return True

    def shutdown(self):
        self._stop.set()
        # reconcile loop polls _stop every 0.5 s, so this join is bounded
        locktrace.join_if_alive(self._loop, timeout=2.0)
        with self._reconcile_mutex, self._lock:
            for state in self._deployments.values():
                for h in state.replicas.values():
                    self._kill_replica(h)
            self._deployments.clear()
            self._apps.clear()
        return True

    # -- introspection ------------------------------------------------------

    def get_replica_names(self, deployment_name: str) -> list[str]:
        with self._lock:
            state = self._deployments.get(deployment_name)
            return list(state.replicas.keys()) if state else []

    def get_replicas_versioned(self, deployment_name: str) -> tuple:
        """(version, names) — pull path that composes with push ordering."""
        with self._lock:
            state = self._deployments.get(deployment_name)
            if state is None:
                return (-1, [])
            return (state.version, list(state.replicas.keys()))

    def _bump_version(self, state: "_DeploymentState"):
        """Callers hold self._lock."""
        state.version += 1
        self._change_cv.notify_all()

    def listen_for_replica_change(
        self, deployment_name: str, known_version: int, timeout_s: float = 10.0
    ) -> tuple:
        """Long-poll (reference: ``_private/long_poll.py``): blocks until the
        deployment's replica set differs from ``known_version`` (or timeout),
        then returns (version, replica_names). Keep ``timeout_s`` modest —
        each blocked listen occupies one controller concurrency slot."""
        deadline = time.time() + timeout_s
        with self._lock:
            while True:
                state = self._deployments.get(deployment_name)
                if state is None:
                    return (-1, [])
                if state.version != known_version:
                    return (state.version, list(state.replicas.keys()))
                remaining = deadline - time.time()
                if remaining <= 0:
                    return (state.version, list(state.replicas.keys()))
                self._change_cv.wait(timeout=remaining)

    def get_app_route(self, app_name: str) -> Optional[dict]:
        with self._lock:
            return self._apps.get(app_name)

    def list_routes(self) -> dict:
        with self._lock:
            return {
                a["route_prefix"]: {
                    "app": name,
                    "ingress": a["ingress"],
                    # per-deployment admission-queue override for the proxy
                    # (None = the global serve_queue_depth_per_deployment)
                    "max_queued": (
                        self._deployments[a["ingress"]].spec.get(
                            "max_queued_requests"
                        )
                        if a["ingress"] in self._deployments
                        else None
                    ),
                }
                for name, a in self._apps.items()
            }

    # -- proxy endpoint table -----------------------------------------------

    def register_proxy(
        self, proxy_id: str, node_id: str, host: str, port: int,
        incarnation: str = "",
    ) -> bool:
        """Publish/refresh one proxy's ingress endpoint (re-registration is
        the liveness heartbeat; see ``list_proxies``). A registration from a
        deregistered incarnation is refused: the proxy's stats tick can race
        its own shutdown's deregister (proxy-side fire-and-forget sends give
        no ordering), and a dead endpoint must not re-enter the table."""
        with self._lock:
            if incarnation and incarnation in self._proxy_tombstones:
                return False
            self._proxies[proxy_id] = {
                "proxy_id": proxy_id,
                "node_id": node_id,
                "host": host,
                "port": port,
                "incarnation": incarnation,
                "registered_t": time.time(),
            }
        return True

    def deregister_proxy(self, proxy_id: str, incarnation: str = "") -> bool:
        with self._lock:
            if incarnation:
                now = time.time()
                self._proxy_tombstones[incarnation] = now
                # bounded: prune tombstones past the table's 30 s staleness
                # window (a zombie heartbeat older than that ages out anyway)
                for key in [
                    k for k, t in self._proxy_tombstones.items()
                    if now - t > 60.0
                ]:
                    del self._proxy_tombstones[key]
            return self._proxies.pop(proxy_id, None) is not None

    def list_proxies(self) -> dict:
        """The ingress endpoint table: proxy_id -> {node_id, host, port}.
        Entries silent for >30 s are dropped (a killed proxy actor must not
        stay routable)."""
        now = time.time()
        with self._lock:
            stale = [
                pid
                for pid, rec in self._proxies.items()
                if now - rec["registered_t"] > 30.0
            ]
            for pid in stale:
                del self._proxies[pid]
            return {pid: dict(rec) for pid, rec in self._proxies.items()}

    def status(self) -> dict:
        with self._lock:
            return {
                "applications": {
                    name: {
                        "route_prefix": a["route_prefix"],
                        "deployments": {
                            d: {
                                "status": self._deployments[d].status,
                                "replicas": len(self._deployments[d].replicas),
                                # replicas alive but not yet past their first
                                # successful health check (__init__/first jit)
                                "starting": sum(
                                    1
                                    for n in self._deployments[d].replicas
                                    if n
                                    not in self._deployments[d].replica_healthy_once
                                ),
                                "target": self._deployments[d].target,
                            }
                            for d in a["deployments"]
                            if d in self._deployments
                        },
                    }
                    for name, a in self._apps.items()
                }
            }

    def ping(self):
        return "pong"

    # -- reconciliation -----------------------------------------------------

    def _reconcile_loop(self):
        while not self._stop.wait(0.5):
            try:
                self._reconcile_once()
                self._autoscale()
            except Exception:
                logger.error("serve reconcile error:\n%s", traceback.format_exc())

    def _reconcile_once(self):
        with self._reconcile_mutex:
            with self._lock:
                states = list(self._deployments.values())
            for state in states:
                self._health_check(state)
                with self._lock:
                    cur = state.spec.get("code_version", "")
                    stale = [
                        n
                        for n in state.replicas
                        if state.replica_code.get(n, cur) != cur
                    ]
                    # rolling code update: surge ONE extra replica of the
                    # new version, drain one stale replica once a new one
                    # is healthy — repeat until no stale remain (reference:
                    # the replica rollout state machine,
                    # deployment_state.py:1391)
                    surge = 1 if stale else 0
                    delta = state.target + surge - len(state.replicas)
                if delta > 0:
                    for _ in range(delta):
                        self._start_replica(state)
                elif delta < 0:
                    with self._lock:
                        # prefer retiring stale-version replicas first
                        ordered = sorted(
                            state.replicas.items(),
                            key=lambda kv: (
                                state.replica_code.get(kv[0], cur) == cur
                            ),
                        )
                        victims = ordered[: -delta]
                        for name, h in victims:
                            self._forget_replica(state, name)
                        if victims:
                            self._bump_version(state)
                    grace = state.spec.get("graceful_shutdown_timeout_s", 20.0)
                    for _, h in victims:
                        self._graceful_stop(h, grace)
                if stale and delta == 0:
                    # at surge capacity: retire one stale replica as soon as
                    # a new-version replica has passed its health check
                    with self._lock:
                        new_ready = [
                            n
                            for n in state.replicas
                            if state.replica_code.get(n) == cur
                            and n in state.replica_healthy_once
                        ]
                        victim = None
                        if new_ready:
                            name = stale[0]
                            h = state.replicas.get(name)
                            if h is not None:
                                victim = (name, h)
                                self._forget_replica(state, name)
                                self._bump_version(state)
                    if victim is not None:
                        grace = state.spec.get(
                            "graceful_shutdown_timeout_s", 20.0
                        )
                        self._graceful_stop(victim[1], grace)
                with self._lock:
                    rolled = all(
                        state.replica_code.get(n, "") == cur
                        for n in state.replicas
                    )
                    state.status = (
                        "RUNNING"
                        if len(state.replicas) == state.target and rolled
                        else "UPDATING"
                    )

    def _start_replica(self, state: _DeploymentState):
        spec = state.spec
        with self._lock:
            replica_name = f"serve:{state.name}#{state.next_replica_id}"
            state.next_replica_id += 1
        opts = dict(spec.get("ray_actor_options") or {})
        resources = opts.pop("resources", None)
        from ray_tpu.serve.replica import ReplicaActor

        cls = ray_tpu.remote(ReplicaActor)
        # the keys DeploymentConfig admits; concurrency, name and restarts
        # are the controller's
        try:
            h = cls.options(
                name=replica_name,
                num_cpus=opts.get("num_cpus", 1),
                num_tpus=opts.get("num_tpus"),
                resources=resources,
                # +2 headroom so control-plane calls (check_health,
                # get_metrics, reconfigure) can't starve behind a saturated
                # request pool and get a healthy replica killed
                max_concurrency=spec.get("max_ongoing_requests", 8) + 2,
                max_restarts=0,  # controller owns restarts
            ).remote(
                spec["serialized_target"],
                spec["init_args_payload"],
                state.name,
                replica_name,
            )
        except Exception:
            logger.error("replica start failed:\n%s", traceback.format_exc())
            return
        with self._lock:
            state.replicas[replica_name] = h
            state.replica_started_t[replica_name] = time.time()
            state.replica_code[replica_name] = spec.get("code_version", "")
            self._bump_version(state)

    @staticmethod
    def _replica_actor_state(h) -> Optional[str]:
        """The replica actor's controller-side state (PENDING while its
        __init__ is still running, ALIVE after, DEAD on crash), or None when
        unknowable (control-plane hiccup)."""
        try:
            from ray_tpu.util.state.api import _call

            return _call("actor_state", h._actor_id)
        except Exception:  # noqa: BLE001
            return None

    @staticmethod
    def _starting_verdict(
        actor_state: Optional[str],
        started_t: float,
        alive_t: Optional[float],
        grace_s: Optional[float],
        timeout_s: float,
        now: float,
    ) -> str:
        """Decide a STARTING (never-yet-healthy) replica's fate after a
        health-check timeout — the slow-startup half of the replica state
        machine (reference: ``deployment_state.py:1391``):

        - actor DEAD/gone                  -> "replace" (crashed in __init__)
        - actor PENDING (still in __init__) -> "wait", unless the
          deployment's ``initial_health_grace_s`` is set and exceeded —
          "alive but compiling" is STARTING, not hung, so the default grace
          is unbounded and actor liveness is the watchdog
        - actor ALIVE (init returned)       -> the hung-replica timeout
          clock starts at this FIRST READINESS: replace only once
          ``timeout_s`` has elapsed since the actor came alive without a
          single successful health check
        - state unknowable                  -> "wait" (never kill on a
          control-plane hiccup)
        """
        if actor_state == "DEAD":
            return "replace"
        if actor_state == "ALIVE":
            if alive_t is not None and now - alive_t > timeout_s:
                return "replace"
            return "wait"
        if actor_state in ("PENDING", "RESTARTING"):
            # still constructing: only an explicit per-deployment grace
            # bounds this window
            if grace_s is not None and now - started_t > grace_s:
                return "replace"
            return "wait"
        # unknowable (lookup failed): never kill on a control-plane hiccup —
        # a nearly-compiled replica must not die to one failed state query;
        # the next period re-queries and the real state decides
        return "wait"

    def _forget_replica(self, state: _DeploymentState, name: str):
        """Drop all per-replica bookkeeping (callers hold self._lock)."""
        state.replicas.pop(name, None)
        state.replica_started_t.pop(name, None)
        state.replica_alive_t.pop(name, None)
        state.replica_healthy_once.discard(name)
        state.replica_code.pop(name, None)

    def _health_check(self, state: _DeploymentState):
        now = time.time()
        if now - state.last_health_t < state.spec.get("health_check_period_s", 2.0):
            return
        state.last_health_t = now
        with self._lock:
            replicas = list(state.replicas.items())
        if not replicas:
            return
        dead = []
        # one shared deadline for the whole gang — a single hung replica must
        # not stall the reconcile loop for timeout × num_replicas
        timeout = state.spec.get("health_check_timeout_s", 30)
        grace = state.spec.get("initial_health_grace_s")
        refs = [(name, h, h.check_health.remote()) for name, h in replicas]
        deadline = time.time() + timeout
        from ray_tpu.exceptions import GetTimeoutError

        for name, h, ref in refs:
            try:
                ray_tpu.get(ref, timeout=max(0.1, deadline - time.time()))
                state.replica_healthy_once.add(name)
                state.replica_alive_t.setdefault(name, time.time())
            except GetTimeoutError:
                if name in state.replica_healthy_once:
                    dead.append((name, h))  # was serving, now unresponsive
                    continue
                # STARTING: distinguish "alive but still in __init__/first
                # jit" from "hung" via the actor's real state instead of a
                # flat wall-clock grace
                actor_state = self._replica_actor_state(h)
                if actor_state == "ALIVE":
                    state.replica_alive_t.setdefault(name, time.time())
                verdict = self._starting_verdict(
                    actor_state,
                    state.replica_started_t.get(name, 0.0),
                    state.replica_alive_t.get(name),
                    grace,
                    timeout,
                    time.time(),
                )
                if verdict == "replace":
                    dead.append((name, h))
            except Exception:
                dead.append((name, h))
        for name, h in dead:
            logger.warning("replica %s unhealthy; replacing", name)
            with self._lock:
                self._forget_replica(state, name)
                self._bump_version(state)
            self._kill_replica(h)

    def _autoscale(self):
        with self._lock:
            states = list(self._deployments.values())
        for state in states:
            ac_dict = state.spec.get("autoscaling_config")
            if not ac_dict:
                continue
            from ray_tpu.serve.config import AutoscalingConfig

            ac = AutoscalingConfig(**ac_dict)
            with self._lock:
                replicas = list(state.replicas.values())
            total = 0.0
            for h in replicas:
                try:
                    m = ray_tpu.get(h.get_metrics.remote(), timeout=5)
                    total += m["ongoing"]
                except Exception:
                    pass
            now = time.time()
            state.metric_window.append((now, total))
            state.metric_window = [
                (t, v) for t, v in state.metric_window if now - t < 60
            ]
            desired = ac.desired_replicas(total, len(replicas) or 1)
            if desired > state.target:
                # upscale only after sustained pressure
                window = [
                    v for t, v in state.metric_window if now - t <= ac.upscale_delay_s
                ]
                if window and min(window) / max(len(replicas), 1) > ac.target_ongoing_requests:
                    state.target = desired
                    state.last_scale_t = now
            elif desired < state.target:
                window = [
                    v
                    for t, v in state.metric_window
                    if now - t <= ac.downscale_delay_s
                ]
                sustained = len(window) >= 2 and all(
                    v / max(len(replicas), 1) < ac.target_ongoing_requests
                    for v in window
                )
                if sustained and now - state.last_scale_t > ac.downscale_delay_s:
                    state.target = desired
                    state.last_scale_t = now

    # -- teardown helpers ---------------------------------------------------

    def _graceful_stop(self, h, grace_s: float = 20.0):
        try:
            ray_tpu.get(h.prepare_shutdown.remote(grace_s), timeout=grace_s + 5)
        except Exception:
            pass
        self._kill_replica(h)

    def _kill_replica(self, h):
        try:
            ray_tpu.kill(h)
        except Exception:
            pass
