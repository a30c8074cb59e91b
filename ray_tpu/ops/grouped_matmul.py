"""Grouped matrix multiplication: rows sorted by group, each group's rows
times that group's own matrix (a routed expert bank over tokens sorted by
expert).

The kernel is JAX's Pallas TPU ``megablox`` grouped matmul, which visits only
the (row tile, group) pairs that exist, so a group's matrix is read once
(twice where its rows straddle two tiles) and empty groups cost nothing. Rows
that no group owns (the sizes sum to fewer than the rows: the assignments a
device's share of the experts does not hold, sorted behind the held ones)
cost no grid step either and are left unwritten.
``jax.lax.ragged_dot`` computes the same, and the v5e compiler has a kernel
for it, but rewrites it under the name ``ragged-dot-none``: its device time
then carries no ``jax.named_scope`` (a traced serving run booked 15 of a
chunk's 18 ms to no scope), and it tiles 512 rows at a time, which at 8 rows a
group multiplies every tile by 64 groups' matrices. A Pallas call keeps the
scope path, and its row tile is ours to choose."""

from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.ops._common import interpret

# rows a tile; contraction and output tiles (cut to the matrix where smaller)
TILING = (128, 2048, 1024)


def grouped_matmul(rows, bank, group_sizes, out_dtype=None):
    """``rows`` [M, k] sorted by group, ``bank`` [G, k, n], ``group_sizes``
    [G] int32 summing to M (the first ``group_sizes[0]`` rows belong to
    group 0, and so on) -> [M, n] in ``out_dtype`` (default: the rows'),
    accumulated in float32."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    m, k = rows.shape
    n = bank.shape[2]
    tm, tk, tn = TILING
    padded = -(-m // tm) * tm
    if padded != m:
        rows = jnp.pad(rows, ((0, padded - m), (0, 0)))
    # rows are padded to whole tiles; the kernel leaves rows no group owns
    # unwritten, and those are cut off again
    return megablox.gmm(
        rows, bank, group_sizes, out_dtype or rows.dtype,
        (tm, min(tk, k), min(tn, n)), None, None, False, interpret(),
    )[:m]
