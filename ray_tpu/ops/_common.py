"""Shared Pallas kernel helpers."""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK_ROWS = 256
# A kernel here keeps its input and output blocks double-buffered plus fp32
# working copies, about 10 bytes per block element, inside the ~16 MB of
# scoped VMEM: 256 rows of 8192 bf16 (2M elements) are refused by the v5e
# compiler, 1M elements fit.
BLOCK_ELEMS = 1 << 20
_SUBLANE = 8  # TPU tiling: block sublane dim must be a multiple of 8


def interpret() -> bool:
    """Interpreter mode off-TPU so the CPU suite runs the same code path."""
    return jax.default_backend() != "tpu"


def pad_rows(x):
    """Pad the leading dim to a multiple of 8 (TPU sublane constraint).

    Returns (padded, original_rows). Kernels then always get blocks whose
    sublane dim divides by 8, and never a whole-tensor block that could
    blow the ~16MB VMEM budget on ragged inputs.
    """
    rows = x.shape[0]
    rem = rows % _SUBLANE
    if rem == 0:
        return x, rows
    pad = _SUBLANE - rem
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)), rows


def pick_block(rows: int, cols: int) -> int:
    """Largest divisor of ``rows`` that is a multiple of 8, at most
    BLOCK_ROWS, and keeps a ``[block, cols]`` block within BLOCK_ELEMS
    (callers pad rows to x8 first via ``pad_rows``)."""
    upper = max(_SUBLANE, min(BLOCK_ROWS, rows, BLOCK_ELEMS // cols))
    for b in range(upper - upper % _SUBLANE, 0, -_SUBLANE):
        if rows % b == 0:
            return b
    return rows  # < 8 rows: single tiny block (equal to the array dim)
