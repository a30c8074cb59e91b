"""A prompt chunk's attention over a latent cache: ``T`` new tokens a row
against the row's stripe of shared rotated keys and normed latents, one Pallas
kernel a layer. The sibling of ``ops/decode_attention.py`` (one new token a
row) for ``models/patterned.py _latent_reader``'s chunk path.

The walk it replaces steps over key blocks in plain XLA and writes each
block's float32 scores ``[B, heads, T, block]`` to HBM (134 MB a block at 128
heads and 256 tokens), reads them for the mask and the maximum, again for the
exponential, and writes the probabilities once more for the value product.
Here a tile of queries and heads keeps its scores, running maximum, sum and
context accumulator in VMEM in float32 while the key blocks stream past;
nothing of size ``heads x T x block`` leaves the chip.

The carried cache ``[n, B, 1, S, D]`` is taken where it lies (the layer index
is a prefetched scalar that the block specs' index maps read: never an operand
sliced out of it), a block of keys and latents at a time, double-buffered by
the grid's pipeline. The mask comes as ``[B, T, S]`` int8 (causal over
absolute positions, a sliding layer's window, an indexed layer's choice: the
caller makes it, in one elementwise pass) and is read a block at a time; the
first and last block a tile of queries can see come as scalars, and a block
outside them costs no copy (the index map stays on the nearest one it needs)
and no matrix work (``pl.when``): the causal tail, and everything before a
sliding window.

Same mathematics as the walk under the same mask: scores in float32, times the
scale, ``_MASKED`` where the mask is 0; running maximum, sum and context in
float32 across blocks; probabilities cast to the cache's type before the value
product. Two forms, as the walk has (``models/patterned.py _chunk_expands``):
absorbed, where the queries have been through ``wuk`` and meet the latents
themselves, which are the values too (multi-query attention of ``heads x T``
rows, 2 x (2 rank + rope) operations a row and key position); expanded, where a
block's latents go through ``wuk`` / ``wuv`` for a group of heads inside the
kernel (2 x rank x (nope + v) a head and key position, then 2 x (nope + rope +
v) a row and key position) and the expanded keys and values are rounded to the
cache's type as the walk's are. Off the TPU it runs in Pallas interpret
mode."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._common import interpret
from ray_tpu.ops.decode_attention import _MASKED

# queries a tile: the largest of these that divides the chunk's width (the
# mask's int8 tile is 32 rows; the engine's chunk buckets are these)
QUERY_TILES = (256, 128, 64, 32)
# key positions a block: the largest of these that divides the stripe (the
# expanded form, which expands a block once a group of heads, tries 1,024
# first). Measured on a v5e (PERF.md section 6, PR 52; one layer, 128 heads,
# 256 queries behind 20,480 positions under a mask of an eighth, ms): absorbed
# at 1,024 rows 10.32 / 9.40 / 9.52 with blocks of 256 / 512 / 1,024, at 2,048
# rows 8.96 / 9.33 with 512 / 1,024, at 4,096 rows of 512 8.75; expanded 10.91
# / 8.28 at 8 heads of 256 / 512, then flat: 7.60 / 7.62 / 7.60 at 16 heads of
# 512 / 1,024 / 2,048, 8.56 at 32 heads
KEY_BLOCKS = (512, 256, 128)
_KEY_BLOCK_EXPANDED = 1024
# rows (queries x heads) a tile of the absorbed form at most: its float32
# scores are rows x block x 4 bytes in VMEM (4 MB) and its accumulator rows x
# rank x 4 (4 to 8 MB); a block of keys and latents is read once a tile
_ROWS = 2048
# heads a group of the expanded form at most: the group's rows of ``wuk`` and
# ``wuv`` lie in VMEM beside the block (2 x 16 x 128 x 512 x 2 bytes = 4 MB)
_HEADS = 16
_VMEM_LIMIT = 96 << 20


def tiles(heads: int, T: int, stripe: int, expanded: bool = False) -> Optional[tuple]:
    """(heads a tile, queries a tile, key positions a block) of the kernel for
    ``T`` new tokens a row over a ``stripe``-position latent cache, or None
    where it does not apply (the caller keeps the XLA walk): the stripe is no
    whole number of blocks, or ``T`` no whole number of query tiles (the
    expanded form: not one tile, since a second would expand each block again)."""
    blocks = ((_KEY_BLOCK_EXPANDED,) if expanded else ()) + KEY_BLOCKS
    bk = next((b for b in blocks if stripe % b == 0), None)
    tq = next((t for t in QUERY_TILES if T % t == 0), None)
    if bk is None or tq is None or (expanded and tq != T):
        return None
    most = _HEADS if expanded else max(1, _ROWS // tq)
    hg = max(h for h in range(1, min(heads, most) + 1) if heads % h == 0)
    return hg, tq, bk


def takes_widths(*widths: int) -> bool:
    """Whether the kernel's blocks take rows of these widths: whole lane tiles
    on the chip, anything interpreted (as ``decode_attention.takes_heads_of``)."""
    return interpret() or all(w % 128 == 0 for w in widths)


def _nt(a, b):
    """a [m, d] . b [n, d]^T -> [m, n] float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _softmax_step(s, seen, v, m_ref, l_ref, acc_ref, at):
    """One block of the running softmax for the rows ``at`` of the scratch:
    s [heads, queries, block] float32 scores (scaled), seen [queries, block]
    the mask, v [block, width] what the probabilities multiply."""
    hg, tq, bk = s.shape
    s = jnp.where(seen[None], s, _MASKED).reshape(hg * tq, bk)
    m = m_ref[at]
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_ref[at] = alpha * l_ref[at] + p.sum(axis=-1, keepdims=True)
    acc_ref[at] = alpha * acc_ref[at] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[at] = m_new


def _walked(kernel):
    """The grid's frame round a block's work: (row, head group, query tile,
    key block), the key blocks innermost; the scratch starts at the first and
    the output leaves at the last, and a block outside the tile's bounds is
    skipped."""

    def framed(layer_ref, lo_ref, hi_ref, *refs, scale, n_qt):
        *ins, o_ref, m_ref, l_ref, acc_ref = refs
        b, qt, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
        tile = b * n_qt + qt

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        @pl.when(jnp.logical_and(j >= lo_ref[tile], j <= hi_ref[tile]))
        def _():
            kernel(*ins, m_ref, l_ref, acc_ref, scale=scale)

        @pl.when(j == pl.num_programs(3) - 1)
        def _():
            o_ref[...] = (acc_ref[...] / l_ref[...]).reshape(o_ref.shape).astype(o_ref.dtype)

    return framed


@_walked
def _absorbed(qr_ref, ql_ref, mask_ref, k_ref, c_ref, m_ref, l_ref, acc_ref, *, scale):
    hg, tq, r = ql_ref.shape
    c = c_ref[...]  # [bk, rank]: the key's latent part, and the value
    s = _nt(qr_ref[...].reshape(hg * tq, -1), k_ref[...]) + _nt(ql_ref[...].reshape(hg * tq, r), c)
    s = (s * scale).reshape(hg, tq, -1)
    _softmax_step(s, mask_ref[...].astype(jnp.int32) != 0, c, m_ref, l_ref, acc_ref, slice(None))


@_walked
def _expanded(qr_ref, qn_ref, mask_ref, k_ref, c_ref, wuk_ref, wuv_ref, m_ref, l_ref, acc_ref,
              *, scale):
    hg, tq, _ = qn_ref.shape
    c, k = c_ref[...], k_ref[...]
    seen = mask_ref[...].astype(jnp.int32) != 0
    for h in range(hg):  # the head's keys and values of this block, then its queries
        k_nope = _nt(c, wuk_ref[h]).astype(c.dtype)  # [bk, nope]
        v = jnp.dot(c, wuv_ref[h], preferred_element_type=jnp.float32).astype(c.dtype)
        s = (_nt(qr_ref[h], k) + _nt(qn_ref[h], k_nope)) * scale
        _softmax_step(s[None], seen, v, m_ref, l_ref, acc_ref, slice(h * tq, (h + 1) * tq))


def _bounds(positions, tq: int, bk: int, n_blocks: int, window: Optional[int]):
    """The first and last key block each tile of ``tq`` queries can see, as
    two int32 [B * tiles]. Row b's positions are consecutive from
    ``positions[b, 0]``, so a tile's last query sees furthest and its first
    (under a window) earliest."""
    first, last = positions[:, ::tq], positions[:, tq - 1::tq]
    hi = jnp.clip(last // bk, 0, n_blocks - 1)
    lo = jnp.zeros_like(hi) if window is None else jnp.minimum(
        jnp.maximum(first - window + 1, 0) // bk, hi)
    return lo.reshape(-1).astype(jnp.int32), hi.reshape(-1).astype(jnp.int32)


def expands(heads: int, T: int, stripe: int, dims) -> bool:
    """Whether the kernel has its expanded form for these shapes (``dims``:
    ``models/patterned.py LatentDims``): one tile of queries, and a head's
    keys and values of whole lane tiles on the chip."""
    return tiles(heads, T, stripe, expanded=True) is not None and takes_widths(dims.nope, dims.v)


def latent_chunk_attention(q_rope, q, mask, ck_all, cv_all, layer, positions, scale: float,
                           window: Optional[int] = None, wuk_all=None, wuv_all=None):
    """``T`` new tokens a row over layer ``layer`` of a carried latent cache.
    q_rope [B, H, T, Dr] against the shared rotated keys ck_all
    [n, B, 1, S, Dr]; cv_all [n, B, 1, S, R] the normed latents; mask
    [B, T, S] int8, 0 where query ``t`` does not see position ``s``; layer: an
    int or an int32 scalar; positions [B, T] int32, consecutive a row (they
    bound the blocks a tile walks, with ``window`` from below; the mask decides
    inside them).

    Absorbed (no weights): q [B, H, T, R], each head's query through its half
    of the key up-projection, meets the latents themselves, which are the
    values too -> each head's context in the latent's space [B, H, T, R].
    Expanded (``wuk_all`` [n, H, nope, R] and ``wuv_all`` [n, H, R, v], the
    stacked leaves: the kernel takes row ``layer`` of each): q [B, H, T, nope]
    meets each head's keys, expanded a block at a time -> each head's own
    context [B, H, T, v]."""
    B, H, T, _ = q_rope.shape
    S = ck_all.shape[3]
    expanded = wuk_all is not None
    kernel, weights, width = (
        (_expanded, (wuk_all, wuv_all), wuv_all.shape[-1]) if expanded
        else (_absorbed, (), cv_all.shape[-1]))
    hg, tq, bk = tiles(H, T, S, expanded)
    n_qt, n_blocks = T // tq, S // bk
    lo, hi = _bounds(positions, tq, bk, n_blocks, window)

    def blk(b, qt, j, lo_ref, hi_ref):  # the nearest block the tile needs
        return jnp.clip(j, lo_ref[b * n_qt + qt], hi_ref[b * n_qt + qt])

    def q_spec(x):
        return pl.BlockSpec((None, hg, tq, x.shape[-1]), lambda b, g, qt, j, *_: (b, g, qt, 0))

    def cache_spec(x):
        return pl.BlockSpec(
            (None, None, None, bk, x.shape[-1]),
            lambda b, g, qt, j, layer_ref, *bounds: (layer_ref[0], b, 0, blk(b, qt, j, *bounds), 0))

    def weight_spec(x):
        return pl.BlockSpec((None, hg) + x.shape[2:],
                            lambda b, g, qt, j, layer_ref, *_: (layer_ref[0], g, 0, 0))

    mask_spec = pl.BlockSpec(
        (None, tq, bk), lambda b, g, qt, j, _, *bounds: (b, qt, blk(b, qt, j, *bounds)))
    rows = hg * tq
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, n_qt=n_qt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H // hg, n_qt, n_blocks),
            in_specs=[q_spec(q_rope), q_spec(q), mask_spec, cache_spec(ck_all),
                      cache_spec(cv_all), *map(weight_spec, weights)],
            out_specs=pl.BlockSpec((None, hg, tq, width), lambda b, g, qt, j, *_: (b, g, qt, 0)),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32), pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, width), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, T, width), q_rope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret(),
        name="latent_chunk_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), lo, hi, q_rope, q, mask, ck_all, cv_all, *weights)
