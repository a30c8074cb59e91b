"""TPU generation + pod-slice topology model.

The reference treats TPU as a pluggable vendor accelerator
(``python/ray/_private/accelerators/tpu.py``: generations at ``:61``, valid
chip counts at ``:180``, pod-slice ``TPU-{type}-head`` resources in
``ray.util.tpu``). Here the topology is first-class scheduler input: a slice
is an ICI domain; the scheduler must never split an XLA program across a
partial slice, and placement groups align bundles to slice hosts.

Geometry follows public TPU system data (v4/v5p: 3D torus, 4 chips/host;
v5e/v6e: 2D mesh, up to 8 chips/host; 2 cores/chip on v4/v5p, 1 on v5e/v6e).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional

# generation -> (chips_per_host_max, cores_per_chip, ici_dims)
TPU_GENERATIONS: dict[str, tuple[int, int, int]] = {
    "v2": (4, 2, 2),
    "v3": (4, 2, 2),
    "v4": (4, 2, 3),
    "v5p": (4, 2, 3),
    "v5e": (8, 1, 2),
    "v5litepod": (8, 1, 2),
    "v6e": (8, 1, 2),
}

# Published peaks of ONE chip, keyed by ``jax.devices()[0].device_kind``. The
# one table every utilization or roofline number divides by; a kind that is
# not here is an error, never a default.
CHIP_PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e" (system architecture)',
    },
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks recorded for device_kind {device_kind!r}; "
            f"add it to ray_tpu.tpu.topology.CHIP_PEAKS with its source "
            f"(known: {sorted(CHIP_PEAKS)})"
        ) from None


_ACCEL_TYPE_RE = re.compile(r"^(v\d+[a-z]*|v5litepod)-(\d+)$")


@dataclasses.dataclass(frozen=True)
class SliceTopology:
    """One pod slice: an ICI-connected set of chips across one or more hosts."""

    generation: str  # "v5e", "v4", ...
    num_chips: int  # total chips in the slice
    chips_per_host: int
    accelerator_type: str  # e.g. "v5e-16"

    @property
    def num_hosts(self) -> int:
        return max(1, self.num_chips // self.chips_per_host)

    @property
    def cores_per_chip(self) -> int:
        return TPU_GENERATIONS[self.generation][1]

    @property
    def ici_dims(self) -> int:
        return TPU_GENERATIONS[self.generation][2]

    @property
    def is_multi_host(self) -> bool:
        return self.num_hosts > 1

    def head_resource_name(self) -> str:
        """Gang-scheduling resource owned by worker 0 of the slice
        (reference: per-slice ``TPU-{type}-head`` resource)."""
        return f"TPU-{self.accelerator_type}-head"

    def mesh_shape_2d(self) -> tuple[int, int]:
        """A near-square 2D logical mesh over the slice's chips (XLA will map
        it onto the physical torus)."""
        n = self.num_chips
        a = int(math.sqrt(n))
        while n % a:
            a -= 1
        return (n // a, a)

    @classmethod
    def from_accelerator_type(cls, accelerator_type: str) -> "SliceTopology":
        m = _ACCEL_TYPE_RE.match(accelerator_type)
        if not m:
            raise ValueError(f"unrecognized TPU accelerator type: {accelerator_type!r}")
        gen, count = m.group(1), int(m.group(2))
        if gen not in TPU_GENERATIONS:
            raise ValueError(f"unknown TPU generation: {gen}")
        chips_max, cores_per_chip, _ = TPU_GENERATIONS[gen]
        # v2/v3/v4/v5p accelerator types count cores, not chips (reference
        # tpu.py:161ff normalization); v5e/v6e count chips.
        num_chips = count // cores_per_chip if cores_per_chip > 1 else count
        chips_per_host = min(chips_max, num_chips)
        return cls(
            generation="v5e" if gen == "v5litepod" else gen,
            num_chips=num_chips,
            chips_per_host=chips_per_host,
            accelerator_type=accelerator_type,
        )

    def valid_subhost_chip_counts(self) -> tuple[int, ...]:
        """Chip counts a single task may reserve on one host (reference
        tpu.py:180 — {1, 2, 4, 8} bounded by chips per host)."""
        return tuple(c for c in (1, 2, 4, 8) if c <= self.chips_per_host)
