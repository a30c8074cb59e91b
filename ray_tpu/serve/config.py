"""Serve configuration schemas.

Reference: ``python/ray/serve/config.py`` (``AutoscalingConfig``,
deployment options) — pydantic there, plain dataclasses here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass
class AutoscalingConfig:
    """Queue-depth-driven replica autoscaling (reference:
    ``serve/autoscaling_policy.py`` + ``_private/autoscaling_state.py``)."""

    min_replicas: int = 1
    max_replicas: int = 10
    target_ongoing_requests: float = 2.0
    upscale_delay_s: float = 3.0
    downscale_delay_s: float = 30.0
    metrics_interval_s: float = 1.0
    # smoothing applied to the desired-replica delta per decision
    upscaling_factor: float = 1.0
    downscaling_factor: float = 1.0

    def desired_replicas(self, total_ongoing: float, current: int) -> int:
        if current <= 0:
            return self.min_replicas
        raw = total_ongoing / max(self.target_ongoing_requests, 1e-9)
        if raw > current:
            desired = current + (raw - current) * self.upscaling_factor
        else:
            desired = current - (current - raw) * self.downscaling_factor
        import math

        desired = math.ceil(desired - 1e-9)
        return max(self.min_replicas, min(self.max_replicas, desired))


@dataclasses.dataclass
class DeploymentConfig:
    num_replicas: int = 1
    max_ongoing_requests: int = 8
    # Per-deployment bound on admitted-but-unfinished requests AT EACH
    # PROXY (the ingress admission queue): past it the proxy sheds with
    # 429 + Retry-After instead of queueing. None = the global
    # ``Config.serve_queue_depth_per_deployment`` knob. Distinct from
    # ``max_ongoing_requests``, which bounds concurrency INSIDE one
    # replica (reference: serve's max_queued_requests handle option).
    max_queued_requests: Optional[int] = None
    autoscaling_config: Optional[AutoscalingConfig] = None
    ray_actor_options: Optional[dict] = None
    health_check_period_s: float = 2.0
    health_check_timeout_s: float = 30.0
    graceful_shutdown_timeout_s: float = 20.0
    # Bound on how long a replica may stay in STARTING (alive but still in
    # __init__ / first jit) before it is replaced. None = unbounded: a
    # replica whose constructor is still RUNNING is never killed for slow
    # startup — only a dead actor is (reference: the slow-startup branch of
    # the deployment state machine, _private/deployment_state.py:1391).
    # Gang/LLM deployments set this from their compile budget.
    initial_health_grace_s: Optional[float] = None
    user_config: Optional[Any] = None

    def __post_init__(self):
        # what reaches the replica's actor; concurrency, name and restarts
        # are the controller's. Anything else is refused rather than dropped:
        # a dropped option is a replica on the wrong device.
        unknown = set(self.ray_actor_options or ()) - {
            "num_cpus", "num_tpus", "resources"
        }
        if unknown:
            raise ValueError(
                f"ray_actor_options keys {sorted(unknown)} are not supported "
                "for serve replicas; use num_cpus, num_tpus or resources"
            )

    def initial_replicas(self) -> int:
        if self.autoscaling_config:
            return self.autoscaling_config.min_replicas
        return self.num_replicas
