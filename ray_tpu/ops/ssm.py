"""Mamba-2's state-space recurrence in its two forms, and the short causal
convolution in front of it (Dao & Gu 2024, "Transformers are SSMs"; the
published instance here is NVIDIA Nemotron-3-Super's ``M`` blocks).

A head ``h`` of width ``P`` keeps a state ``S`` [P, N] a sequence. With a step
``dt_t > 0``, a head's decay rate ``a < 0``, the token's input ``x_t`` [P] and
its group's ``B_t``, ``C_t`` [N] (head ``h`` reads group ``h // (H / G)``):

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

``ssm_step`` is that line for one token a row (a decode step: all it has to
move is the state, once in and once out). ``ssm_scan`` computes the same over
``T`` tokens in chunks of ``chunk``: inside a chunk every pair (t, s <= t) at
once, ``y_t += exp(cs_t - cs_s) (C_t . B_s) dt_s x_s`` with ``cs`` the running
sum of ``dt a`` (matrix multiplications, no loop over tokens); from chunk to
chunk one state a head, carried by a scan over the ``T / chunk`` chunks. A
token whose ``dt`` is 0 leaves the state as it was and adds nothing to any
later token: that is how a right-padded row stops at its own length.

A decode step runs ``ssm_step_in_place``: the line on one row of the stacked
leaf ``[n, slots, H, P, N]`` a model carries round its layer loop. Where the
state tiles (``step_groups``: ``N`` whole lanes, ``P`` whole sublanes, whole
groups of heads a tile; decided from the shapes at trace time) it is one
Pallas kernel that takes the leaf whole and the row as a scalar, reads each
tile of that row once, writes the new state back where the tile came from and
sums ``y`` from the tile it still holds. XLA makes two fusions of the plain
line, the update in place and a sum that reads the new state again: three
passes over the state, 1.19 ms a block at Nemotron-3-Super's 64 slots of
[128, 64, 128], where the kernel's two take 0.84 (PERF.md section 6, PR 38).
Any other shape (the ``nemotron-tiny`` preset's 16 x 16 state) takes the row
out of the leaf, through ``ssm_step`` and back; ``ssm_step`` stays the
definition and the tests' reference. The chunked form is plain ``jax.numpy``:
batched matrix multiplications. Off the TPU the kernel runs in Pallas
interpret mode. The state, the decays, the products and the sums over ``N``
and over a sequence are float32 whatever type the weights have: a state sums
thousands of steps."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._common import _SUBLANE, interpret

_LANE = 128
# Bytes of state a grid step of the fused step moves each way: whole groups of
# heads (a group shares its B and C), as many as fit. Measured on a v5e at
# Nemotron-3-Super's [5, 64, 128, 64, 128] (PERF.md section 6, PR 38; ms a
# block at 0.5 / 1 / 2 MB): 0.915 / 0.837 / 0.835, where a copy through the
# same grid takes 0.836 / 0.833 / 0.832 (537 MB at 645 GB/s): from 1 MB on a
# grid step's fixed cost hides behind its copies. The smaller of the two, for
# the shorter unrolled kernel; in and out are both double-buffered, four tiles
# in the 16 MB of scoped VMEM (``ops/_common.py``).
TILE_BYTES = 1 << 20


def causal_conv(tail, x, w, b):
    """Depthwise causal convolution of x [B, T, C] behind the ``K - 1``
    inputs that came before it, ``tail`` [B, K - 1, C]; w [K, C] (``w[K - 1]``
    multiplies the token itself), b [C]. Returns (y [B, T, C] float32, the
    inputs in front of and with x [B, K - 1 + T, C]: a caller cuts the next
    tail out of it where its row ends)."""
    T = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w32 = w.astype(jnp.float32)
    y = b.astype(jnp.float32)
    for j in range(w.shape[0]):
        y = y + w32[j] * ext[:, j:j + T].astype(jnp.float32)
    return y, ext


def ssm_step(state, x, dt, a, B, C, D):
    """One token a row. state [b, H, P, N] float32; x [b, H, P]; dt [b, H]
    (after its softplus); a [H] (negative); B, C [b, G, N]; D [H]. Returns
    (y [b, H, P] float32, the new state)."""
    b, H, P, N = state.shape
    G = B.shape[1]
    f32 = jnp.float32
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    # heads by group, so that a group's B and C broadcast over its heads
    s = state.reshape(b, G, H // G, P, N)
    decay = jnp.exp(dt * a.astype(f32)).reshape(b, G, H // G, 1, 1)
    xdt = (x * dt[..., None]).reshape(b, G, H // G, P, 1)
    s = s * decay + xdt * B[:, :, None, None, :]
    y = (s * C[:, :, None, None, :]).sum(axis=-1).reshape(b, H, P)
    return y + D.astype(f32)[None, :, None] * x, s.reshape(b, H, P, N)


def step_groups(H: int, P: int, N: int, G: int) -> Optional[int]:
    """Groups of heads a tile of the fused step for a state [H, P, N] float32
    a slot in ``G`` groups, or None where the shape does not tile (the caller
    keeps ``ssm_step``): a head's [P, N] is whole (8, 128) tiles with ``N`` on
    the lanes, and four tiles of at least one group fit the scoped VMEM."""
    if N % _LANE or P % _SUBLANE or H % G:
        return None
    group = H // G * P * N * 4
    if 4 * group > 12 << 20:  # in and out double-buffered, beside the kernel's own values
        return None
    return max(k for k in range(1, G + 1) if G % k == 0 and (k == 1 or k * group <= TILE_BYTES))


def _step_kernel(layer_ref, decay_ref, xdt_ref, b_ref, c_ref, s_ref, y_ref, o_ref):
    # one slot's block of heads: s_ref, o_ref [1, 1, heads, P, N] (the same
    # tile of the stacked leaf), xdt_ref, y_ref [1, 1, heads, P], b_ref, c_ref
    # [1, 1, groups, N]; decay_ref [slots, H] in SMEM
    del layer_ref  # the index maps read it
    slot, block = pl.program_id(0), pl.program_id(1)
    heads = s_ref.shape[2]
    per_group = heads // b_ref.shape[2]
    # a head's dt x multiplies along P, which lies on the sublanes of its tile
    xdt = xdt_ref[0, 0].T  # [P, heads]
    for h in range(heads):
        g = h // per_group
        new = (s_ref[0, 0, h] * decay_ref[slot, block * heads + h]
               + xdt[:, h:h + 1] * b_ref[0, 0, g:g + 1, :])
        o_ref[0, 0, h] = new
        # y from the tile just written, while it is held. The sum over N
        # runs down the sublanes of the transposed product and leaves a row
        # with P on the lanes, as y lies: summed along the lanes, 8 cross-lane
        # reductions a head bound the kernel (1.04-1.06 ms a block at
        # Nemotron's shape where this form reads 0.84, the copies' own time:
        # PERF.md section 6, PR 38)
        y_ref[0, 0, h:h + 1, :] = (new * c_ref[0, 0, g:g + 1, :]).T.sum(axis=0, keepdims=True)


def ssm_step_in_place(state_all, layer, x, dt, a, B, C, D):
    """``ssm_step`` on row ``layer`` of the stacked leaf ``state_all``
    [n, b, H, P, N] float32 (layer: an int or an int32 scalar, traced under
    the layer loop); the other operands as ``ssm_step``'s. Returns (y
    [b, H, P] float32, the leaf with that row's new state). Where the shape
    tiles (``step_groups``) one Pallas kernel reads each tile of the row once,
    writes the new state where the tile came from (the leaf is aliased to the
    result; the other rows are not touched) and sums ``y`` from the tile it
    holds: the state moves once in and once out. Any other shape takes the
    row out, through ``ssm_step`` and back."""
    n, b, H, P, N = state_all.shape
    G = B.shape[1]
    k = step_groups(H, P, N, G)
    if k is None:
        y, state = ssm_step(
            jax.lax.dynamic_index_in_dim(state_all, layer, 0, keepdims=False), x, dt, a, B, C, D)
        return y, jax.lax.dynamic_update_index_in_dim(state_all, state, layer, 0)
    f32 = jnp.float32
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    heads, blocks = H // G * k, G // k

    def small(*block):  # an operand [b, blocks, ...]: one block a grid step
        return pl.BlockSpec((1, 1) + block, lambda s, i, *_: (s, i, 0, 0))

    tile = pl.BlockSpec((1, 1, heads, P, N), lambda s, i, layer, _: (layer[0], s, i, 0, 0))
    y, state_all = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, blocks),
            in_specs=[small(heads, P), small(k, N), small(k, N), tile],
            out_specs=[small(heads, P), tile],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, blocks, heads, P), f32),
                   jax.ShapeDtypeStruct(state_all.shape, f32)],
        input_output_aliases={5: 1},  # the leaf, behind the two prefetched scalars' operands
        interpret=interpret(),
        name="ssm_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.exp(dt * a.astype(f32)),
      (x * dt[..., None]).reshape(b, blocks, heads, P), B.reshape(b, blocks, k, N),
      C.reshape(b, blocks, k, N), state_all)
    return y.reshape(b, H, P) + D.astype(f32)[None, :, None] * x, state_all


def ssm_scan(state, x, dt, a, B, C, D, chunk: int):
    """``T`` tokens a row, in chunks. state [b, H, P, N] float32 (what the
    row's earlier tokens left); x [b, T, H, P]; dt [b, T, H] (after its
    softplus; 0 where the row has no token); a [H]; B, C [b, T, G, N]; D [H].
    Returns (y [b, T, H, P] float32, the state after the row's last token)."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    f32 = jnp.float32
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    Q = min(chunk, T)
    pad = -T % Q
    if pad:  # whole chunks; a padded token has dt 0
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    nc, Hg = (T + pad) // Q, H // G
    x = x.reshape(b, nc, Q, G, Hg, P)
    dt = dt.reshape(b, nc, Q, G, Hg)
    B, C = B.reshape(b, nc, Q, G, N), C.reshape(b, nc, Q, G, N)
    cs = jnp.cumsum(dt * a.astype(f32).reshape(G, Hg), axis=2)  # [b, nc, Q, G, Hg], falling
    xdt = x * dt[..., None]
    # inside a chunk: every pair (t, s <= t)
    seg = cs[:, :, :, None] - cs[:, :, None, :]  # [b, nc, t, s, G, Hg]
    causal = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])[None, None, :, :, None, None]
    pair = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bctsg", C, B)
    y = jnp.einsum("bctsgh,bcsghp->bctghp", pair * cb[..., None], xdt)
    # what a chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cs[:, :, -1:] - cs)
    added = jnp.einsum("bcsgn,bcsghp->bcghpn", B, xdt * to_end[..., None])
    over = jnp.exp(cs[:, :, -1])  # a chunk's whole decay [b, nc, G, Hg]

    def carry(s, inp):
        add, d = inp
        return s * d[..., None, None] + add, s  # hand out the state a chunk starts from

    last, before = jax.lax.scan(
        carry, state.reshape(b, G, Hg, P, N),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(over, 1, 0)),
    )
    before = jnp.moveaxis(before, 0, 1)  # [b, nc, G, Hg, P, N]
    y = y + jnp.einsum("bctgn,bcghpn->bctghp", C, before) * jnp.exp(cs)[..., None]
    y = y + D.astype(f32).reshape(G, Hg)[..., None] * x
    return y.reshape(b, nc * Q, H, P)[:, :T], last.reshape(b, H, P, N)
