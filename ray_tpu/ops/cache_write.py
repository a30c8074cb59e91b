"""A decode step's new keys and values into a slot-striped cache: one new
position a row, every row of the set and both tensors in one call a layer.

``models/patterned.py decode_forward`` carries the whole cache
``[L, B, K, S, D]`` round its layer loop and writes row ``b``'s new ``[K, D]``
at ``cache[l, b, :, pos[b], :]``. As a scatter that is one index row a (slot,
key-value head) pair and tensor, which the chip walks one by one (73-100 ns
each: 19 us a tensor and layer at 12 slots of 16 heads, 23 at 32 of 8; PERF.md
section 6, PR 27 and PR 57), so a model with a cache row for each of 192
layer-passes spent a sixth of its step on twelve new positions. This kernel
takes the cache where it lies (in HBM, aliased to its result, the layer index
a scalar: never a layer sliced out of it, never a copy of it) and moves a
row's ``K`` heads as one strided copy, all rows' copies in flight together:
6.4 us a layer for keys and values where the two scatters took 37.6 at that
shape, 8.4 against 46.1 at 32 slots of 8 heads, 12.7 against 25.7 at 64 of 2
(``tools/cache_write_sweep.py`` on a v5e; PERF.md section 6, PR 58).

A position is narrower than what a copy can address: the cache's rows lie in
tiles of ``tile_positions`` positions x 128 lanes (8 for four-byte numbers, 16
for bfloat16, which packs two positions into a sublane's word), and the v5e
compiler takes no copy of part of one. So a row's copy is of the aligned tile
``[K, tile, D]`` that holds ``pos[b]``: into VMEM, the one position replaced
there under an iota mask, and back (64 KB a slot and tensor at 16 heads of 128
bfloat16 numbers). Rows never share a tile (a tile lies inside one row's
stripe), so the copies need no order among themselves.

The new rows come in head-major, ``[K, B, D]``: as the cache lies, and as a
projection onto head-major weights leaves them, so the transposition in front
of the call is a relabelling and no operation. (Handed ``[B, K, D]``, the
compiler for the v5e gave the carrying chunk's whole output of ``wk`` and
``wv`` that order, then the chunk's own stripe the order that follows from it,
and copied the 0.3 GB stripe in and out of it a launch.) A row is then one
sublane of the operand, read at an index the loop over the rows computes, which
the compiler takes of four-byte numbers only ("cannot statically prove that
index in dimension 1 is a multiple of 8" of a bfloat16 operand): the new rows
come in as float32, widened from the cache's type outside the kernel and
narrowed back inside it, both exact.

The bytes left are the ``mode="drop"`` scatter's: a row that is not valid
and a position outside ``[0, S)`` start no copy at all (``rows_in_stripe``:
nothing is clamped into the stripe), and nothing else of a tile changes. Off the TPU it runs in
Pallas interpret mode; ``tests/test_cache_write.py`` holds it to the scatter
bit for bit."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._common import interpret


def tile_positions(cache) -> int:
    """Positions a native tile of ``cache`` [.., S, D] holds: 8 sublanes of
    four bytes, each packing ``4 / itemsize`` positions."""
    return 8 * max(4 // cache.dtype.itemsize, 1)


def takes_cache(cache) -> bool:
    """Whether the kernel's copies take ``cache`` [.., S, D]: rows of whole
    128-lane tiles (a narrower head is padded to a lane tile on the chip and a
    copy takes no part of one: ``ops/decode_attention.py takes_heads_of``) and
    stripes of whole tiles of positions, so that the tile that holds a
    position lies inside the stripe. The same answer wherever it is asked:
    interpreted on the CPU the kernel would run at any width, but a tiny
    model's step is then what the chip never runs, at several times the
    scatter's cost to compile and interpret."""
    return cache.shape[-1] % 128 == 0 and cache.shape[-2] % tile_positions(cache) == 0


def _kernel(layer_ref, pos_ref, ok_ref, k_new, v_new, k_in, v_in, k_out, v_out,
            k_buf, v_buf, sem, *, tile: int):
    # k_new, v_new [K, B, D] float32; k_in / k_out the same cache [L, B, K, S, D] in
    # HBM (aliased), as v_in / v_out; k_buf, v_buf [B, K, tile, D]; sem [4, B]:
    # a row's copies in and out of each tensor
    B = k_new.shape[1]
    layer = layer_ref[0]

    def tensors():
        return enumerate(((k_new, k_in, k_buf, k_out), (v_new, v_in, v_buf, v_out)))

    def tile_of(b):
        return pl.ds(pl.multiple_of(pos_ref[b] // tile * tile, tile), tile)

    def copy_in(b, i, src, buf):
        return pltpu.make_async_copy(src.at[layer, b, :, tile_of(b), :], buf.at[b], sem.at[2 * i, b])

    def copy_back(b, i, buf, dst):
        return pltpu.make_async_copy(
            buf.at[b], dst.at[layer, b, :, tile_of(b), :], sem.at[2 * i + 1, b])

    def each_row(step):
        def body(b, _):
            pl.when(ok_ref[b] != 0)(lambda: step(b))
            return 0

        jax.lax.fori_loop(0, B, body, 0)

    def fetch(b):
        for i, (_, src, buf, _) in tensors():
            copy_in(b, i, src, buf).start()

    def replace(b):
        row = pos_ref[b] % tile
        for i, (new, src, buf, dst) in tensors():
            copy_in(b, i, src, buf).wait()
            old = buf[b]  # [K, tile, D]
            here = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1) == row
            row_new = jnp.broadcast_to(new[:, pl.ds(b, 1), :], old.shape).astype(old.dtype)
            buf[b] = jnp.where(here, row_new, old)
            copy_back(b, i, buf, dst).start()

    def settle(b):
        for i, (_, _, buf, dst) in tensors():
            copy_back(b, i, buf, dst).wait()

    each_row(fetch)  # every row's tiles on their way in together,
    each_row(replace)  # each written back as it arrives,
    each_row(settle)  # and all waited on at the end


def rows_in_stripe(pos, valid, stripe: int):
    """What the kernel takes for rows at ``pos`` [B] (``valid`` [B] bool or
    None) of a ``stripe``-position cache: (each row's position, 0 where it
    writes nothing; whether it writes, int32 [B]). A row writes where it is
    valid and its position lies in ``[0, stripe)``: the ``mode="drop"``
    scatter's rule. The same for every layer of a step, so a caller under a
    layer loop asks once, outside it."""
    pos = pos.astype(jnp.int32)
    ok = (pos >= 0) & (pos < stripe)
    if valid is not None:
        ok = ok & valid
    return jnp.where(ok, pos, 0), ok.astype(jnp.int32)


def write_rows_in_place(ck_all, cv_all, layer, new_k, new_v, pos, ok):
    """Row ``b``'s new key ``new_k[b]`` and value ``new_v[b]`` [B, K, D] into
    position ``pos[b]`` of layer ``layer`` of the carried caches ``ck_all``,
    ``cv_all`` [L, B, K, S, D] (layer: an int or an int32 scalar, traced under
    the layer loop), where ``ok[b]`` is not 0; ``pos``, ``ok`` int32 [B] as
    ``rows_in_stripe`` gives them. Returns the two caches, each aliased to its
    argument. The caches are such as ``takes_cache`` says."""
    _, B, K, _, _ = ck_all.shape
    tile = tile_positions(ck_all)
    if not (takes_cache(ck_all) and takes_cache(cv_all)) or tile != tile_positions(cv_all):
        raise ValueError(f"caches {ck_all.shape} and {cv_all.shape}: no whole lane tiles a row, or no "
                         f"whole number of {tile}-position tiles a stripe")
    # head-major, as the cache lies and as a projection onto head-major weights
    # comes out: [K, B, D]; the cache's values, in float32
    new_k, new_v = (new.astype(c.dtype).astype(jnp.float32).transpose(1, 0, 2)
                    for new, c in ((new_k, ck_all), (new_v, cv_all)))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in (ck_all, cv_all)],
        in_specs=[smem, smem, smem, vmem, vmem, hbm, hbm],
        out_specs=[hbm, hbm],
        scratch_shapes=[
            pltpu.VMEM((B, K, tile, ck_all.shape[-1]), ck_all.dtype),
            pltpu.VMEM((B, K, tile, cv_all.shape[-1]), cv_all.dtype),
            pltpu.SemaphoreType.DMA((4, B)),
        ],
        input_output_aliases={5: 0, 6: 1},  # the caches, behind the scalars and the new rows
        interpret=interpret(),
        name="cache_write_rows",
    )(jnp.asarray(layer, jnp.int32).reshape(1), pos, ok, new_k, new_v, ck_all, cv_all)
