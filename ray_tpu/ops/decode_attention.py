"""Decode attention over a slot-striped cache: one new token a row, each row
reading its keys and values between its own bounds and nothing else of its
stripe.

``models/patterned.py decode_forward`` carries the whole cache
``[L, B, K, S, D]`` round its layer loop. The einsum it falls back to
(``_grouped_attention``) is handed layer ``l`` whole and masks over all ``S``
positions, so a decode step streams every stripe at any length (PERF.md
section 5: 2.94 of a 13.79 ms Mistral step, "the same with 2 slots active or
31"). This kernel takes the carried cache where it lies (in HBM, never an
operand sliced out of it: a layer's slice is a 134 MB copy), the layer index
and the bounds as scalars, and for row ``b`` copies the blocks of the
position axis that hold ``[lo[b], hi[b])`` into VMEM, all ``K`` key-value
heads of a block at once, double-buffered; a block outside the bounds costs
no DMA and no compute. One invocation walks all rows' live blocks as one
stream, so the next row's first block is in flight while this row's last is
multiplied.

Same mathematics as the einsum under the same mask: keys, values and queries
as they are stored; scores, the running maximum, the sum and the output
accumulator in float32 across blocks (online softmax); the mask exact inside
the first and last block; ``H // K`` query heads share a key-value head's
block without repeating it. Off the TPU it runs in Pallas interpret mode.

A latent-attention layer (``models/patterned.py``, absorbed form) is the same
walk over one key-value "head" whose key lies in two leaves: the rotated key
all heads share in ``k`` (64 wide) and the normed latent in ``v`` (512 wide),
which is also the value. ``latent_decode_attention`` hands the kernel a second
query (the heads' queries absorbed through the key up-projection) for the
latent, so a block's score is ``q . k + q_latent . v`` and each block of the
latent is copied once for both uses.

Its sibling for a prompt's chunk (``T`` > 1 new tokens a row over the same
latent cache) is ``ops/latent_chunk_attention.py``: the same cache taken where
it lies and the same running softmax, a tile of queries and heads against each
key block under a mask, on the grid's pipeline instead of this walk's own
copies (a chunk's rows are few and its blocks are many)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._common import _SUBLANE, interpret

# Positions a block of the walk takes, longest first: all K key-value heads of
# one row, [K, bs, D] of keys and as much of values, copied double-buffered.
BLOCKS = (512, 256, 128)
BLOCK = BLOCKS[-1]  # the shortest: a stripe that is no whole number of them is not this kernel's
# Bytes of keys and values together a block is sized to. A step of the walk
# costs its copies (bytes over the bandwidth) or a fixed part, whichever is
# longer (the running maximum, ``exp``, sum and rescale are one serial chain a
# block whatever its width; two copies to start, two semaphores to wait on),
# and a row reads whole blocks past its bounds: so a block is the shortest at
# which the copies are the longer of the two. Measured on a v5e, bfloat16, the
# kernel's device time in a profiler trace (``tools/decode_block_sweep.py``;
# PERF.md section 6, PR 54), us a block by what it holds: 0.46-0.48 at 128 KB,
# 0.52-0.55 at 256, 0.71-0.74 at 512, 1.39-1.41 at 1,024, 2.78-2.81 at 2,048:
# 730-750 GB/s from 512 KB up, a fixed 0.45-0.5 us under it. Us a layer at 128
# / 256 / 512 positions, at the serving cells' shapes and live lengths:
#   2 heads of 128 (1 KB a position): ZAYA1 (64 rows of 1,556 live in 4,608)
#     371 / 218 / 163, Nemotron-3 (64 of 469 in 2,048; 16 query heads a group)
#     128 / 80 / 64 (95 at 1,024);
#   8 heads of 128 (4 KB a position): Mistral (32 of 245 in 1,024) 57 / 63 / 95,
#     Laguna 185 / 191 / 217 (32 of 972 in 4,096) and 105 / 120 / 157 (its 512
#     window), Solar (64 of 1,556 in 8,192) 584 / 586 / 634 (PR 31 read the
#     same order: 71 / 82 / 107 at 304 live, 222 / 228 / 246, 105 / 119 / 152).
# Each shape's best is the longest block of 512 KB or less. A sliding window
# of few heads pays for the length (a window of 512 positions under
# 512-position blocks reads two blocks for one; no served model has one).
BLOCK_BYTES = 512 * 1024
_MASKED = -1e30  # finite: exp(_MASKED - m) is 0 and nothing is inf - inf


def cache_position_bytes(ck_all, cv_all) -> int:
    """Bytes one position of a row holds in a layer of the cache ``ck_all``,
    ``cv_all`` [L, B, K, S, D]: ``K`` heads of a key and of a value."""
    return sum(x.shape[2] * x.shape[-1] * x.dtype.itemsize for x in (ck_all, cv_all))


def takes_stripe(stripe: int) -> bool:
    """Whether the walk applies to a cache of ``stripe`` positions a slot: a
    whole number of blocks, of the shortest (and so of whichever divides it)."""
    return stripe % BLOCK == 0


def block_size(stripe: int, position_bytes: int, latent: bool = False) -> Optional[int]:
    """Positions a block of a ``stripe``-position cache whose rows hold
    ``position_bytes`` a position and layer (``cache_position_bytes``), or None
    where the kernel does not apply (``takes_stripe``; the caller keeps the
    einsum): the longest of ``BLOCKS`` that divides the stripe and holds
    ``BLOCK_BYTES`` or less; the shortest is taken whatever it holds.

    ``latent``: the longest that divides the stripe, whatever it holds. A
    latent block's fixed part is the larger (every query head of the model, 32
    to 128 of them, meets each position twice, as part of the key and as the
    value), and its rows are documents of thousands of positions, where a long
    block reads little past the end: at Kanana-2's shape (24 rows of 17,880
    live, 1.25 KB a position) a layer takes 1,736 / 1,129 / 837 us at 128 / 256
    / 512 positions (160 / 320 / 640 KB a block; 765 at 1,024, not offered), so
    the 256 that ``BLOCK_BYTES`` would give it is a third slower than 512; at
    dots3's sliding layers (16 rows, a window of 513 of a 2.25 KB latent)
    61 / 53 / 57 (same sweep)."""
    for bs in BLOCKS:
        if stripe % bs == 0 and (latent or bs == BLOCK or bs * position_bytes <= BLOCK_BYTES):
            return bs
    return None


def takes_heads_of(cache_k) -> bool:
    """Whether the kernel's copies take a cache whose rows are as wide as
    ``cache_k``'s [.., D]: whole 128-lane tiles on the chip (a 64-wide head is
    padded to a lane tile there and a copy takes no part of one; the v5e
    compiler: "slice shape along dimension 4 must be aligned to tiling (128),
    but is 64": IBM Granite-4.0-H's heads), any width interpreted. Where not,
    the caller keeps the einsum over the stripe (``models/patterned.py
    reads_blocks`` asks)."""
    return interpret() or cache_k.shape[-1] % 128 == 0


def _whole_blocks(stripe: int, position_bytes: int, latent: bool = False) -> int:
    bs = block_size(stripe, position_bytes, latent)
    if bs is None:
        raise ValueError(f"a {stripe}-position stripe is no whole number of {BLOCK}-position blocks")
    return bs


def _clamp(lo, hi, stripe: int, xp=jnp):
    """Bounds the kernel can walk: at least one position, inside the stripe
    (a dead slot's length runs on past its stripe). ``xp``: ``jnp`` for the
    kernel's operands, ``np`` for the host's count of the same walk."""
    hi = xp.clip(hi, 1, stripe)
    return xp.clip(lo, 0, hi - 1), hi


def positions_read(lo, hi, stripe: int, position_bytes: int, latent: bool = False):
    """Positions the kernel's blocks cover for rows bounded ``[lo, hi)`` in a
    cache of ``stripe`` positions a slot and ``position_bytes`` a position
    (whole blocks, ``block_size``'s): what it reads of each of keys and
    values, a key-value head. Integers or NumPy arrays of them, on the host:
    the engine counts with it. The bounds go through the ``_clamp`` the
    kernel's go through, and the blocks between them are the kernel's
    ``lo // bs`` to ``(hi - 1) // bs``."""
    bs = _whole_blocks(stripe, position_bytes, latent)
    lo, hi = _clamp(np.asarray(lo), np.asarray(hi), stripe, np)
    return ((hi - 1) // bs - lo // bs + 1) * bs


def _kernel(layer_ref, lo_ref, hi_ref, q_ref, *rest, bs: int, scale: float, latent: bool):
    # ``latent``: a second query [B, K, G, Dv] for the values' leaf comes in
    # behind the first, and a block's score is q . k + q_latent . v
    ql_ref, rest = (rest[0], rest[1:]) if latent else (None, rest)
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sem = rest
    B, K, G, _ = q_ref.shape
    D = v_buf.shape[-1]  # the output is as wide as a value
    layer = layer_ref[0]

    def copies(b, blk, buf):
        at = pl.ds(pl.multiple_of(blk * bs, bs), bs)
        return (
            pltpu.make_async_copy(k_hbm.at[layer, b, :, at, :], k_buf.at[buf], sem.at[0, buf]),
            pltpu.make_async_copy(v_hbm.at[layer, b, :, at, :], v_buf.at[buf], sem.at[1, buf]),
        )

    def fetch(b, blk, buf):
        for copy in copies(b, blk, buf):
            copy.start()

    fetch(0, lo_ref[0] // bs, 0)

    def row(b, buf):
        lo, hi = lo_ref[b], hi_ref[b]
        last = (hi - 1) // bs
        q = q_ref[b]  # [K, G, D]
        ql = ql_ref[b] if latent else None

        def scores(h, k, v):
            s = jax.lax.dot_general(
                q[h], k[h], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            if latent:
                s = s + jax.lax.dot_general(
                    ql[h], v[h], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            return s

        def block(blk, carry):
            m, den, acc, buf = carry
            other = 1 - buf

            @pl.when(blk < last)
            def _():
                fetch(b, blk + 1, other)

            @pl.when(jnp.logical_and(blk == last, b + 1 < B))
            def _():  # the next row's first block, while this row's last is multiplied
                fetch(b + 1, lo_ref[b + 1] // bs, other)

            k_copy, v_copy = copies(b, blk, buf)
            k_copy.wait()
            k = k_buf[buf]  # [K, bs, D]
            if latent:  # the latent is part of the key
                v_copy.wait()
            v = v_buf[buf] if latent else None
            s = jnp.concatenate(
                [scores(h, k, v) for h in range(K)], axis=0
            ) * scale  # [K * G, bs]
            pos = blk * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(jnp.logical_and(pos >= lo, pos < hi), s, _MASKED)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            den = alpha * den + p.sum(axis=-1, keepdims=True)
            if not latent:
                v_copy.wait()
                v = v_buf[buf]
            pv = jnp.concatenate([
                jnp.dot(
                    p[h * G:(h + 1) * G].astype(v.dtype), v[h],
                    preferred_element_type=jnp.float32,
                ) for h in range(K)
            ], axis=0)  # [K * G, D]
            return m_new, den, alpha * acc + pv, other

        m0 = jnp.full((K * G, 1), _MASKED, jnp.float32)
        den0 = jnp.zeros((K * G, 1), jnp.float32)
        acc0 = jnp.zeros((K * G, D), jnp.float32)
        _, den, acc, buf = jax.lax.fori_loop(
            lo // bs, last + 1, block, (m0, den0, acc0, buf)
        )
        o_ref[b] = (acc / den).reshape(K, G, D).astype(o_ref.dtype)
        return buf

    jax.lax.fori_loop(0, B, row, 0)


def _walk(queries, ck_all, cv_all, layer, lo, hi, scale: float, latent: bool, name: str):
    """The kernel's call. queries: one [B, H, Dk], or for ``latent`` two,
    [B, H, Dk] and [B, H, Dv] -> [B, H, Dv] in the first's dtype."""
    B, H, _ = queries[0].shape
    _, _, K, S, Dk = ck_all.shape
    Dv = cv_all.shape[-1]
    bs = _whole_blocks(S, cache_position_bytes(ck_all, cv_all), latent)
    G = H // K
    # a key-value head's query heads are its matmul's rows: whole sublanes
    Gp = -(-G // _SUBLANE) * _SUBLANE

    def grouped(q):
        qg = q.reshape(B, K, G, q.shape[-1])
        return qg if Gp == G else jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    lo, hi = (x.astype(jnp.int32) for x in _clamp(lo, hi, S))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, scale=scale, latent=latent),
        out_shape=jax.ShapeDtypeStruct((B, K, Gp, Dv), queries[0].dtype),
        in_specs=[smem, smem, smem] + [vmem] * len(queries)
        + [pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((2, K, bs, Dk), ck_all.dtype),
            pltpu.VMEM((2, K, bs, Dv), cv_all.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret(),
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), lo, hi, *map(grouped, queries), ck_all, cv_all)
    return out[:, :, :G].reshape(B, H, Dv)


def decode_attention(q, ck_all, cv_all, layer, lo, hi):
    """Attention of one query token a row over layer ``layer`` of a carried
    cache. q: [B, H, D]; ck_all, cv_all: [L, B, K, S, D], ``S`` a whole number
    of blocks (``block_size``); layer: an int or an int32 scalar (traced
    under the layer loop); lo, hi: [B] int32, row ``b`` attends to positions
    ``lo[b] <= s < hi[b]`` -> [B, H, D] in q's dtype."""
    return _walk((q,), ck_all, cv_all, layer, lo, hi, q.shape[-1] ** -0.5, False,
                 "decode_attention")


def latent_decode_attention(q_rope, q_latent, ck_all, cv_all, layer, lo, hi, scale: float):
    """The absorbed form of latent attention for one query token a row.
    q_rope: [B, H, Dr] against the shared rotated keys ck_all [L, B, 1, S, Dr];
    q_latent: [B, H, R] (each head's query through its half of the key
    up-projection) against the normed latents cv_all [L, B, 1, S, R], which
    are the values too -> each head's context in the latent's space
    [B, H, R]. ``scale``: one over the root of the width of a head's whole
    key, which the caller knows and the cache does not. ``S`` is a whole
    number of ``block_size(S, .., latent=True)`` blocks."""
    return _walk((q_rope, q_latent), ck_all, cv_all, layer, lo, hi, scale, True,
                 "latent_decode_attention")


def sparse_latent_decode_attention(q_rope, q_latent, ck_all, cv_all, layer, chosen, hi,
                                   scale: float):
    """``latent_decode_attention`` over chosen positions only: row ``b``
    attends ``chosen[b]`` [B, n] (an indexer's choice, in any order; an entry
    at or past ``hi[b]`` is no position of the row and is masked). The chosen
    rows of both leaves are gathered out of the layer's stripe (XLA's gather:
    ``n`` rows of a key and a latent a row and layer, whatever the row's
    length) and scored in one piece, float32, a plain softmax: the selection
    has already bounded the work, so no block walk is needed. -> [B, H, R]."""
    # out of the carried cache where it lies: a layer's slice handed to the
    # gather is a copy of it (0.2 GB of latents a layer and step)
    rows = jnp.arange(chosen.shape[0])[:, None]
    keys, latents = ck_all[layer, rows, 0, chosen], cv_all[layer, rows, 0, chosen]  # [B, n, D]
    s = (jnp.einsum("bhd,bnd->bhn", q_rope, keys, preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bnr->bhn", q_latent, latents, preferred_element_type=jnp.float32))
    s = jnp.where((chosen < hi[:, None])[:, None, :], s * scale, _MASKED)
    p = jax.nn.softmax(s, axis=-1).astype(latents.dtype)
    return jnp.einsum("bhn,bnr->bhr", p, latents,
                      preferred_element_type=jnp.float32).astype(q_rope.dtype)
