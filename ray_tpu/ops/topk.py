"""Exact top-k of a wide row in two stages, for the engine's sampler.

``lax.top_k`` over a whole vocabulary sorts every logit of every slot at
every step; on a v5e that runs at 0.2-0.3 ns a logit, a fortieth of a plain
pass over the row (PERF.md section 6, PR 50). ``top_k`` here finds the same
``k`` candidates from far fewer sorted numbers:

1. view the row as blocks of ``BLOCK`` lanes and take each block's maximum
   (one pass over the row);
2. ``lax.top_k`` of the maxima, ``k`` of them: the winning blocks, then in
   ascending order;
3. gather the winning blocks (``k * BLOCK`` numbers, in vocabulary order)
   and ``lax.top_k`` them, ``k`` again; a candidate's index is
   ``block * BLOCK + lane``.

It is the one-stage call's answer, values and indices. Order the row by
(value descending, index ascending), ``lax.top_k``'s documented order. Were
``x`` among the first ``k`` and its block not chosen, ``k`` blocks would
stand before its block by (maximum descending, block index ascending), each
holding an element before ``x`` (its maximum is greater than ``x``, or equal
at a lower index): ``k`` elements before ``x``, a contradiction. The winners
are gathered in ascending order, so the second call breaks ties by
vocabulary index as the first would have.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.ops._common import _SUBLANE

# numbers a block: the lanes of a tile on the chip, so a block's maximum is a
# reduction over a tile's minor dimension
BLOCK = 128


def two_stage(width: int, k: int) -> bool:
    """Whether ``top_k`` of a ``width``-wide row takes the two stages: only
    where the row has more blocks than ``k`` stands to pick. At or under
    ``k * BLOCK`` numbers stage three would hold the whole row, so the plain
    call is the function (every tiny model's vocabulary)."""
    return -(-width // BLOCK) > k


def top_k(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """``jax.lax.top_k(x, k)``: the ``k`` largest along the last axis of
    ``x`` ``[..., width]`` and their int32 indices, ties to the lower index."""
    *lead, width = x.shape
    if not two_stage(width, k):
        return jax.lax.top_k(x, k)
    nb = -(-width // BLOCK)
    if nb * BLOCK != width:
        # -inf never displaces a real entry: a padded lane's index is above
        # every real one, so on a tie at -inf the real entry stands first
        x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, nb * BLOCK - width)],
                    constant_values=-jnp.inf)
    # ``[rows, width]`` lies on the chip in tiles of 8 rows by BLOCK lanes.
    # With the rows split the same way a tile of ``x`` is a tile of the view,
    # so the reshape moves nothing; ``[rows, nb, BLOCK]`` would tile blocks by
    # lanes and copy the logits twice (0.26 of 0.65 ms at 64 x 262,272)
    view = tuple(lead)
    if lead and lead[-1] % _SUBLANE == 0:
        view = (*lead[:-1], lead[-1] // _SUBLANE, _SUBLANE)
    blocks = x.reshape(*view, nb, BLOCK)
    # the sorts see rows by numbers, the form the chip's top-k is written
    # for; one row goes in alone (the chip's top-k of ``[1, n]`` is a merge of
    # parts that drops the order among ties; of ``[n]`` it is a stable sort)
    rows = math.prod(lead)
    flat = (rows,) if rows > 1 else ()
    _, winners = jax.lax.top_k(blocks.max(axis=-1).reshape(*flat, nb), k)
    winners = jnp.sort(winners, axis=-1)
    picked = jnp.take_along_axis(blocks, winners.reshape(*view, k, 1), axis=-2,
                                 mode="promise_in_bounds")
    vals, at = jax.lax.top_k(picked.reshape(*flat, k * BLOCK), k)
    idx = jnp.take_along_axis(winners, at // BLOCK, axis=-1) * BLOCK + at % BLOCK
    return vals.reshape(*lead, k), idx.reshape(*lead, k)
