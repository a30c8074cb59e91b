"""TPU accelerator manager: detection, chip ownership, per-worker visibility.

Analog of the reference's ``TPUAcceleratorManager``
(``python/ray/_private/accelerators/tpu.py:110``): counts the host's chips
from its device nodes (never through a JAX backend — whoever initialises one
takes the chip away from the workers), names the ``TPU`` resource, computes
the ``TPU_VISIBLE_CHIPS``/``TPU_CHIPS_PER_HOST_BOUNDS`` env for sub-host
partitioning, hands each chip to one worker process at a time
(``ChipPool``), and checks in a granted worker that JAX really sees the
chips (``verify_chip_grant``).
"""

from __future__ import annotations

import glob
import math
import os
import subprocess
import threading
import time
from typing import Callable, Optional

from ray_tpu.tpu.topology import SliceTopology

RESOURCE_NAME = "TPU"

# GKE-style env vars (reference tpu.py:16-30).
ENV_ACCEL_TYPE = "TPU_ACCELERATOR_TYPE"
ENV_WORKER_ID = "TPU_WORKER_ID"
ENV_WORKER_HOSTNAMES = "TPU_WORKER_HOSTNAMES"
ENV_CHIPS_PER_HOST_BOUNDS = "TPU_CHIPS_PER_HOST_BOUNDS"
ENV_VISIBLE_CHIPS = "TPU_VISIBLE_CHIPS"
ENV_TOPOLOGY = "TPU_TOPOLOGY"
ENV_NAME = "TPU_NAME"


class TPUAcceleratorManager:
    @staticmethod
    def get_resource_name() -> str:
        return RESOURCE_NAME

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        return os.environ.get(ENV_ACCEL_TYPE) or None

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Chips this process could open, counted from the device nodes the
        host exposes: ``/dev/accel<N>`` (v2-v4) or one numbered VFIO group
        per chip under ``/dev/vfio`` (v5e and later). The nodes win over the
        GKE variables: a one-chip machine cut from a four-chip host keeps
        ``TPU_CHIPS_PER_HOST_BOUNDS=2,2,1`` and exposes one group."""
        nodes = glob.glob("/dev/accel[0-9]*") or [
            p for p in glob.glob("/dev/vfio/*") if os.path.basename(p).isdigit()
        ]
        if nodes:
            return len(nodes)
        bounds = os.environ.get(ENV_CHIPS_PER_HOST_BOUNDS)
        if bounds:
            try:
                dims = [int(x) for x in bounds.split(",")]
                n = 1
                for d in dims:
                    n *= d
                return n
            except ValueError:
                pass
        accel = os.environ.get(ENV_ACCEL_TYPE)
        if accel:
            try:
                topo = SliceTopology.from_accelerator_type(accel)
                return topo.chips_per_host
            except ValueError:
                pass
        return 0

    @staticmethod
    def get_current_slice() -> Optional[SliceTopology]:
        accel = TPUAcceleratorManager.get_current_node_accelerator_type()
        if accel is None:
            return None
        try:
            return SliceTopology.from_accelerator_type(accel)
        except ValueError:
            return None

    @staticmethod
    def get_current_node_tpu_worker_id() -> Optional[int]:
        v = os.environ.get(ENV_WORKER_ID)
        return int(v) if v is not None and v.isdigit() else None

    @staticmethod
    def get_current_pod_name() -> Optional[str]:
        return os.environ.get(ENV_NAME) or None

    @staticmethod
    def get_current_pod_worker_count() -> Optional[int]:
        hostnames = os.environ.get(ENV_WORKER_HOSTNAMES)
        if hostnames:
            return len(hostnames.split(","))
        slice_ = TPUAcceleratorManager.get_current_slice()
        return slice_.num_hosts if slice_ else None

    @staticmethod
    def validate_resource_request_quantity(quantity: float) -> tuple[bool, Optional[str]]:
        """Sub-host chip requests must be 1, 2, 4 or 8 so visibility bounds
        tile the host (reference tpu.py:180)."""
        if quantity != int(quantity):
            return False, "TPU resource quantities must be whole chips"
        if int(quantity) not in (1, 2, 4, 8):
            return (
                False,
                f"got {int(quantity)} TPU chips; only 1, 2, 4 or 8 chips per "
                f"task are schedulable on a single host",
            )
        return True, None

    @staticmethod
    def get_visibility_env(chip_ids: list[int]) -> dict[str, str]:
        """Env for a worker restricted to ``chip_ids`` on this host
        (reference tpu.py:194-229)."""
        n = len(chip_ids)
        env = {ENV_VISIBLE_CHIPS: ",".join(str(c) for c in chip_ids)}
        if n == 1:
            env[ENV_CHIPS_PER_HOST_BOUNDS] = "1,1,1"
            env["TPU_HOST_BOUNDS"] = "1,1,1"
        elif n == 2:
            env[ENV_CHIPS_PER_HOST_BOUNDS] = "1,2,1"
            env["TPU_HOST_BOUNDS"] = "1,1,1"
        elif n == 4:
            env[ENV_CHIPS_PER_HOST_BOUNDS] = "2,2,1"
            env["TPU_HOST_BOUNDS"] = "1,1,1"
        return env


def chips_requested(resources: dict) -> int:
    """Whole chips a task or actor with these resources is granted."""
    return math.ceil(resources.get(RESOURCE_NAME) or 0)


class ChipPool:
    """Which worker process holds which chip of this host.

    libtpu keeps a chip open until the process that opened it has exited
    (a SIGTERMed holder takes seconds to let go), and a second process that
    tries meanwhile fails at backend start-up. So the logical ``TPU``
    resource may be free again while the chip is not: a spawn for a ``TPU``
    grant takes its chips here, and waits for the previous holders' exit.
    """

    def __init__(self, n_chips: int):
        self.n_chips = n_chips
        self._lock = threading.Lock()
        # chip index -> holder: None (free), a Popen (free once it exited),
        # or True (reserved by a spawn in progress)
        self._holders: list = [None] * n_chips

    def _free(self) -> list[int]:
        return [
            i
            for i, h in enumerate(self._holders)
            if h is None or (h is not True and h.poll() is not None)
        ]

    def acquire(
        self, k: int, timeout_s: float, evict: Optional[Callable[[], None]] = None
    ) -> list[int]:
        """Reserve ``k`` chips (an aligned group, so the visibility bounds
        tile the host). ``evict`` retires idle workers that still hold
        chips; it is called when the chips are not free yet."""
        if k > self.n_chips:
            raise RuntimeError(
                f"worker granted {k} TPU chips but this host has {self.n_chips}"
            )
        deadline = time.monotonic() + timeout_s
        while True:
            with self._lock:
                free = set(self._free())
                for start in range(0, self.n_chips - k + 1, k):
                    group = list(range(start, start + k))
                    if free.issuperset(group):
                        for i in group:
                            self._holders[i] = True
                        return group
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"no {k} free TPU chips after {timeout_s:.0f}s: "
                    "the processes that held them have not exited"
                )
            if evict is not None:
                evict()
            time.sleep(0.05)

    def drain(self, grace_s: float = 10.0) -> None:
        """Shutdown: when this returns, every process that held a chip has
        exited (killed after ``grace_s``; a holder told to stop still takes
        seconds to let the device go), so the next program on this host can
        open the chips at once."""
        with self._lock:
            holders = {
                id(h): h for h in self._holders if h is not None and h is not True
            }
        deadline = time.monotonic() + grace_s
        for proc in holders.values():
            try:
                proc.wait(timeout=max(0.05, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30.0)

    def bind(self, chips: list[int], proc) -> None:
        """The reserved ``chips`` now belong to ``proc`` until it exits;
        ``proc=None`` returns them (the spawn failed)."""
        with self._lock:
            for i in chips:
                self._holders[i] = proc


def chip_worker_env(
    chips: list[int], n_host_chips: int, explicit: Optional[dict] = None
) -> dict[str, str]:
    """Env to lay over what a worker process spawned for a ``TPU`` grant
    inherits. JAX must use the chip or fail: a ``JAX_PLATFORMS=cpu``
    inherited from a driver that keeps itself off the chip would otherwise
    yield a CPU worker that reports success. A worker granted the whole host
    sees every chip as is. Variables the task set itself (``explicit``, its
    runtime_env) still win; the worker's grant check judges the outcome."""
    env = {"JAX_PLATFORMS": "tpu"}
    if len(chips) < n_host_chips:
        env.update(TPUAcceleratorManager.get_visibility_env(chips))
    return {k: v for k, v in env.items() if k not in (explicit or {})}


def verify_chip_grant(granted: int) -> None:
    """Run in a worker process spawned for ``granted`` chips, before it takes
    work: JAX's platform is ``tpu`` and it sees exactly that many chips."""
    import jax

    try:
        devices = jax.local_devices()
        seen = f"{len(devices)} {devices[0].platform!r} device(s)"
        ok = devices[0].platform == "tpu" and len(devices) == granted
    except RuntimeError as e:  # the backend did not start
        seen, ok = f"no backend ({e})", False
    if not ok:
        raise RuntimeError(
            f"worker was granted {granted} TPU chip(s) but JAX reports {seen} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}, "
            f"TPU_VISIBLE_CHIPS={os.environ.get(ENV_VISIBLE_CHIPS)!r})"
        )


def device_report() -> dict:
    """What JAX sees in THIS process, for a benchmark or smoke line: call it
    in the worker that owns the chips, never in a driver (it starts a
    backend). Memory figures are per local device, in device order."""
    import jax

    devices = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "pid": os.getpid(),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
    }
