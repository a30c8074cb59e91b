"""The gated delta rule with a decay a channel (Kimi Delta Attention: Kimi
Linear, arXiv:2510.26692; the published instance here is upstage
Solar-Open2-250B's ``kda`` layers) in its two forms. The short causal
convolution in front of it is ``ops/ssm.py causal_conv``.

A head keeps a state ``S`` [K (key), V (value)] a sequence. With the token's
query and key ``q_t``, ``k_t`` [K] (the key of unit length), its value ``v_t``
[V], a log-decay a key channel ``g_t <= 0`` [K] and a writing strength
``beta_t`` in [0, 2):

    S' = diag(exp(g_t)) S_{t-1}        S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

The state is not only decayed and added to: what the key already reads out of
it, ``S'^T k_t``, is taken away before the new value goes in (a reduction over
the state *before* its update, which ``ops/ssm.py``'s recurrence has not). A
token with ``beta`` 0 and ``g`` 0 leaves the state as it was: that is how a
right-padded row stops at its own length.

``kda_step`` is that line for one token a row, as elementwise products and
sums in float32: the definition and the tests' reference. A decode step runs
``kda_step_in_place`` on one row of the stacked leaf ``[n, slots, H, K, V]``
a model carries through its layers: where the state tiles (``step_heads``) one
Pallas kernel takes the leaf whole and the row as a scalar, reads each tile of
a slot's heads once, computes ``S'^T k``, the update and ``S^T q`` from the
tile it holds and writes the new state where the tile came from, as
``ops/ssm.py ssm_step_in_place`` does (PERF.md section 6, PR 38): the state
moves once in and once out. A head's [K, V] lies with V on the lanes, so both
reductions run down the sublanes and leave rows as ``v`` and ``o`` lie. Any
other shape (the ``solar-tiny`` preset's 16 x 16 state) takes the row out,
through ``kda_step`` and back.

``kda_scan`` computes the same over ``T`` tokens in chunks of ``chunk`` (the
WY form). Inside a chunk, with ``G`` the running sum of ``g`` and
``w_t = beta_t (v_t - S'^T k_t)`` what token ``t`` writes:

    A[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])      (s <  t)
    B[t, s] =        sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])      (s <= t)
    (I + A) W = beta (V - (exp(G) K) S_0)        O = (exp(G) Q) S_0 + B W
    S_end = diag(exp(G_end)) S_0 + (exp(G_end - G) K)^T W

so the part inside a chunk is no pairwise product as ``ssm_scan``'s but a
unit-lower-triangular *solve* a head and chunk, and ``S_0`` goes from chunk to
chunk in a scan whose body is four small matrix products.

Two design decisions, both about float32's range and digits:

- The pair weights ``exp(G_t - G_s)`` are a channel's, so ``A`` and ``B`` do
  not factor into ``(k_t exp(G_t)) . (k_s exp(-G_s))`` safely: ``g`` is
  ``-exp(A_log) softplus(. + dt_bias)``, at the seeded vectors up to -1.6 a
  token from the bias alone and, with the data's own term under the softplus,
  -30 a token in a tail, so ``exp(-G_s)`` overflows inside one chunk and
  ``exp(G_t)`` is flushed to zero where the pair's own weight is near one.
  The chunk is cut into four sub-blocks. A pair in different sub-blocks is
  split at ``G_e``, the sum at the earlier sub-block's last token:
  ``exp(G_t - G_e)`` and ``exp(G_e - G_s)`` both have exponents <= 0 whatever
  the decays are, so neither overflows and one that underflows bounds a weight
  that is as small: the bound is 1, not a reckoning of the seeds
  (``tests/test_kda.py`` runs decays of -40 a token). A pair in the same
  sub-block takes the same rule on the sub-block, 64 -> 16 -> 4 tokens, and
  inside ``_PAIR_BASE`` (4) the difference itself, ``exp(G_t - G_s)`` a channel
  (an elementwise product and a sum over K: a sixteenth of all pairs).
- ``(I + A)^-1`` of the strictly lower ``A`` is a finite product,
  ``(I - A)(I + A^2)(I + A^4)..`` (``A`` is nilpotent), which would keep the
  whole solve on the matrix unit. But the powers' entries grow to
  ``a^k C(n - 2, k - 1)`` for keys that are alike (``a = beta cos``) and
  cancel to a result of order one: over a chunk of 64 that costs float32 five
  digits at ``a = 0.3``, and over a block of 16 all seven at ``a = 2``
  (``tests/test_kda.py``: keys nearly the same, writing strengths near 2).
  So the diagonal blocks of ``_SOLVE_BASE`` (16) are inverted by forward
  substitution, a row at a time (16 elementwise steps on every block of the
  launch at once: nothing beside the rest), and the blocks are merged by block
  forward substitution, ``[[T1, 0], [-T2 A21 T1, T2]]``: matrix products,
  ``log2(chunk / 16)`` levels of them, between factors of order one.

Every product of the chunked form is float32 at ``Precision.HIGHEST`` (on the
chip the default would round its operands to bfloat16, and the delta rule
feeds what it reads back into what it writes). The state, the decays,
``S'^T k`` and every sum over a sequence are float32 whatever type the weights
have. Off the TPU the kernel runs in Pallas interpret mode."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._common import _SUBLANE, interpret
from ray_tpu.ops.ssm import TILE_BYTES

_LANE = 128
_HIGHEST = jax.lax.Precision.HIGHEST
# the largest diagonal block ``_unit_lower_inverse`` inverts a row at a time
_SOLVE_BASE = 16
# the largest block whose pair weights ``_pair_weights`` takes a channel at a time
_PAIR_BASE = 4


def kda_step(state, q, k, v, g, beta):
    """One token a row. state [b, H, K, V] float32; q, k, g [b, H, K] (``g``
    the log-decay, <= 0); v [b, H, V]; beta [b, H]. Returns (o [b, H, V]
    float32, the new state)."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    s = state * jnp.exp(g)[..., None]
    read = (s * k[..., None]).sum(axis=-2)  # S'^T k, before the update
    w = beta[..., None] * (v - read)
    s = s + k[..., None] * w[..., None, :]
    return (s * q[..., None]).sum(axis=-2), s


def step_heads(H: int, K: int, V: int) -> Optional[int]:
    """Heads a tile of the fused step for a state [H, K, V] float32 a slot, or
    None where the shape does not tile (the caller keeps ``kda_step``): a
    head's [K, V] is whole (8, 128) tiles with ``V`` on the lanes, and four
    tiles of at least one head fit the scoped VMEM."""
    if V % _LANE or K % _SUBLANE:
        return None
    head = K * V * 4
    if 4 * head > 12 << 20:  # in and out double-buffered, beside the kernel's own values
        return None
    return max(n for n in range(1, H + 1) if H % n == 0 and (n == 1 or n * head <= TILE_BYTES))


def _step_kernel(layer_ref, beta_ref, q_ref, k_ref, a_ref, v_ref, s_ref, o_ref, out_ref):
    # one slot's block of heads: s_ref, out_ref [1, 1, heads, K, V] (the same
    # tile of the stacked leaf); q_ref, k_ref, a_ref [1, 1, heads, K] (a_ref the
    # decay exp(g)); v_ref, o_ref [1, 1, heads, V]; beta_ref [slots, H] in SMEM
    del layer_ref  # the index maps read it
    slot, block = pl.program_id(0), pl.program_id(1)
    heads = s_ref.shape[2]
    # what multiplies along K, which lies on the sublanes of a head's tile
    q, k, a = q_ref[0, 0].T, k_ref[0, 0].T, a_ref[0, 0].T  # [K, heads]
    for h in range(heads):
        kh = k[:, h:h + 1]
        s = s_ref[0, 0, h] * a[:, h:h + 1]
        # both sums run down the sublanes and leave a row with V on the lanes
        read = (s * kh).sum(axis=0, keepdims=True)  # S'^T k, before the update
        w = beta_ref[slot, block * heads + h] * (v_ref[0, 0, h:h + 1, :] - read)
        new = s + kh * w
        out_ref[0, 0, h] = new
        o_ref[0, 0, h:h + 1, :] = (new * q[:, h:h + 1]).sum(axis=0, keepdims=True)


def kda_step_in_place(state_all, layer, q, k, v, g, beta):
    """``kda_step`` on row ``layer`` of the stacked leaf ``state_all``
    [n, b, H, K, V] float32 (layer: an int or an int32 scalar, traced under a
    layer loop); the other operands as ``kda_step``'s. Returns (o [b, H, V]
    float32, the leaf with that row's new state). Where the shape tiles
    (``step_heads``) one Pallas kernel reads each tile of the row once and
    writes the new state where the tile came from (the leaf is aliased to the
    result; the other rows are not touched). Any other shape takes the row
    out, through ``kda_step`` and back."""
    n, b, H, K, V = state_all.shape
    heads = step_heads(H, K, V)
    if heads is None:
        o, state = kda_step(
            jax.lax.dynamic_index_in_dim(state_all, layer, 0, keepdims=False), q, k, v, g, beta)
        return o, jax.lax.dynamic_update_index_in_dim(state_all, state, layer, 0)
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    blocks = H // heads

    def small(width):  # an operand [b, blocks, heads, width]: one block a grid step
        return pl.BlockSpec((1, 1, heads, width), lambda s, i, *_: (s, i, 0, 0))

    tile = pl.BlockSpec((1, 1, heads, K, V), lambda s, i, layer, _: (layer[0], s, i, 0, 0))
    o, state_all = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, blocks),
            in_specs=[small(K), small(K), small(K), small(V), tile],
            out_specs=[small(V), tile],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, blocks, heads, V), f32),
                   jax.ShapeDtypeStruct(state_all.shape, f32)],
        input_output_aliases={6: 1},  # the leaf, behind the two prefetched scalars' operands
        interpret=interpret(),
        name="kda_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), beta,
      q.reshape(b, blocks, heads, K), k.reshape(b, blocks, heads, K),
      jnp.exp(g).reshape(b, blocks, heads, K), v.reshape(b, blocks, heads, V), state_all)
    return o.reshape(b, H, V), state_all


def _mm(subscripts, x, y):
    return jnp.einsum(subscripts, x, y, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _pair_weights(x, y, G, strict: bool):
    """``P[t, s] = sum_c x_t[c] y_s[c] exp(G_t[c] - G_s[c])`` for ``s < t``
    (``strict``) or ``s <= t``, zero elsewhere. x, y, G [..., C, K] with ``G``
    falling along C. No exponent is ever positive (the module's first design
    decision): four sub-blocks, a pair in two of them split at the earlier
    one's last token, a pair in one of them by the same rule on the sub-block,
    down to ``_PAIR_BASE`` tokens, whose pairs take the difference itself."""
    *lead, C, K = x.shape
    at = jnp.arange(C)
    if C <= _PAIR_BASE or C % 4:
        seen = at[:, None] > at[None, :] if strict else at[:, None] >= at[None, :]
        diff = jnp.where(seen[:, :, None], G[..., :, None, :] - G[..., None, :, :], -jnp.inf)
        return (x[..., :, None, :] * y[..., None, :, :] * jnp.exp(diff)).sum(axis=-1)
    J, sub = 4, C // 4
    blocked = (*lead, J, sub, K)
    xb, yb, Gb = x.reshape(blocked), y.reshape(blocked), G.reshape(blocked)
    diag = _pair_weights(xb, yb, Gb, strict)  # [.., J, t, s]: the same sub-block
    # an earlier sub-block j: split at the sum its last token reached
    Ge = Gb[..., -1, :]  # [.., J, K]
    right = yb * jnp.exp(Ge[..., None, :] - Gb)  # [.., J, s, K]
    later = at[None, :] >= (jnp.arange(J)[:, None] + 1) * sub  # [J, t]
    left = x[..., None, :, :] * jnp.exp(
        jnp.where(later[:, :, None], G[..., None, :, :] - Ge[..., None, :], -jnp.inf))  # [.., J, t, K]
    off = _mm("...jtk,...jsk->...tjs", left, right)  # [.., t, J, s]
    own = jnp.eye(J, dtype=bool)[:, None, :, None]  # [J (t's), 1, J (s's), 1]
    diag = jnp.where(own, diag[..., :, :, None, :], 0.0).reshape(*lead, C, J, sub)
    return (off + diag).reshape(*lead, C, C)


def _unit_lower_inverse(A):
    """``(I + A)^-1`` of a strictly lower-triangular ``A`` [..., n, n] (the
    module's second design decision): blocks of ``_SOLVE_BASE`` a row at a
    time, larger ones from their halves."""
    n = A.shape[-1]
    if n <= _SOLVE_BASE or n % 2:
        eye = jnp.eye(n, dtype=A.dtype)
        rows = [jnp.broadcast_to(eye[0], A.shape[:-2] + (n,))]
        for t in range(1, n):  # row t of the inverse from the rows above it
            above = jnp.stack(rows, axis=-2)  # [.., t, n]
            rows.append(eye[t] - (A[..., t, :t, None] * above).sum(axis=-2))
        return jnp.stack(rows, axis=-2)
    h = n // 2
    t1, t2 = _unit_lower_inverse(A[..., :h, :h]), _unit_lower_inverse(A[..., h:, h:])
    t21 = -_mm("...ij,...jk->...ik", t2, _mm("...ij,...jk->...ik", A[..., h:, :h], t1))
    top = jnp.concatenate([t1, jnp.zeros_like(t21).swapaxes(-1, -2)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([t21, t2], axis=-1)], axis=-2)


def kda_scan(state, q, k, v, g, beta, chunk: int):
    """``T`` tokens a row, in chunks. state [b, H, K, V] float32 (what the
    row's earlier tokens left); q, k, g [b, T, H, K]; v [b, T, H, V]; beta
    [b, T, H] (``g`` and ``beta`` 0 where the row has no token). ``chunk``: a
    multiple of 4. Returns (o [b, T, H, V] float32, the state after the row's
    last token)."""
    b, T, H, K = q.shape
    V = v.shape[-1]
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    C = chunk
    pad = -T % C
    if pad:  # whole chunks; a padded token has beta 0 and g 0
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    nc = (T + pad) // C

    def heads_first(t):  # [b, T, H, ..] -> [b, nc, H, C, ..]
        return jnp.moveaxis(t.reshape((b, nc, C) + t.shape[2:]), 3, 2)

    q, k, v, g, beta = (heads_first(t) for t in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=3)  # falling
    kb = k * beta[..., None]
    solve = _unit_lower_inverse(_pair_weights(kb, k, G, strict=True))
    reads = _pair_weights(q, k, G, strict=False)
    decay = jnp.exp(G)
    u = _mm("bchts,bchsv->bchtv", solve, v * beta[..., None])
    wk = _mm("bchts,bchsk->bchtk", solve, kb * decay)
    to_end = k * jnp.exp(G[..., -1:, :] - G)  # what a token writes, decayed to the chunk's end

    def carry(S, inp):
        u, wk, qg, reads, to_end, over = inp
        w = u - _mm("bhtk,bhkv->bhtv", wk, S)  # what each token writes
        o = _mm("bhtk,bhkv->bhtv", qg, S) + _mm("bhts,bhsv->bhtv", reads, w)
        return S * over[..., None] + _mm("bhtk,bhtv->bhkv", to_end, w), o

    last, o = jax.lax.scan(
        carry, state,
        tuple(jnp.moveaxis(t, 1, 0) for t in (u, wk, q * decay, reads, to_end, decay[..., -1, :])),
    )
    # [nc, b, H, C, V] -> [b, T, H, V]
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, nc * C, H, V)[:, :T], last
