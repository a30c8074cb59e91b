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
chunked WY form). Inside a chunk, with ``G`` the running sum of ``g`` and
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
  (``tests/test_kda_forms.py`` runs decays of -40 a token). A pair in the same
  sub-block takes the same rule on the sub-block, 64 -> 16 -> 4 tokens, and
  inside ``_PAIR_BASE`` (4) the difference itself, ``exp(G_t - G_s)`` a channel
  (an elementwise product and a sum over K: a sixteenth of all pairs).
- ``(I + A)^-1`` of the strictly lower ``A`` is a finite product,
  ``(I - A)(I + A^2)(I + A^4)..`` (``A`` is nilpotent), which would keep the
  whole solve on the matrix unit. But the powers' entries grow to
  ``a^k C(n - 2, k - 1)`` for keys that are alike (``a = beta cos``) and
  cancel to a result of order one: over a chunk of 64 that costs float32 five
  digits at ``a = 0.3``, and over a block of 16 all seven at ``a = 2``
  (``tests/test_kda_forms.py``: keys nearly the same, writing strengths near 2).
  So the diagonal blocks of ``_SOLVE_BASE`` (16) are inverted by forward
  substitution, a row at a time (16 elementwise steps on every block of the
  launch at once: nothing beside the rest), and the blocks are merged by block
  forward substitution, ``[[T1, 0], [-T2 A21 T1, T2]]``: matrix products,
  ``log2(chunk / 16)`` levels of them, between factors of order one.

``kda_scan_plain`` is that form as a ``jax.numpy`` graph: its definition, the
tests' reference, and what a shape that does not tile runs. Where the shapes
tile (``scan_heads``, asked at trace time as ``step_heads`` is: ``K`` one lane
tile, ``V`` whole ones, a chunk of 16 to 128 tokens that divides 128, the heads
whole sets of ``128 // chunk``) ``kda_scan`` runs it as one Pallas kernel a
layer, a grid over (row, heads of a step, chunk) with the chunks innermost and
in turn:

- *What crosses HBM:* each token's ``q``, ``k``, ``g``, ``v`` once in and its
  ``o`` once out, as [chunk, heads * 128] slabs of the arrays
  ``[b, T, H * width]`` the projections were written as (no layout pass: a
  head's block at lane offset ``head * 128`` is whole tiles), a row of
  ``beta`` a step, and a head's state once in at a row's first chunk and once
  out after its last: the state's block is the same for every chunk of the
  row, so it stays in VMEM from chunk to chunk.
- *What stays in VMEM,* a set of ``128 // chunk`` heads at a time, their
  chunks side by side on the lanes: the running sum ``G`` (stacked
  [(head, t), K], and turned, [K, (head, t)]), both pair-weight matrices as
  ``M^T`` [s, (head, t)], the blocks' inverses, their merges, what the found
  state gives (``(exp(G) k) S_0``, ``(exp(G) q) S_0``), ``w`` and the keys
  decayed to the chunk's end. A step takes two sets, emitted in step with
  each other, so that the sixteen-row substitution and the merges' chain of
  one set run under the other's products.
- *Both design decisions hold inside it.* Pairs in one block of 16: the
  difference itself a channel, as fifteen bands ``(t, t - d)`` of the turned
  operands (a lane roll, ``exp`` of a difference that is never positive, a sum
  down the sublanes); pairs in two blocks: split at the earlier block's last
  token, three matrix products a matrix (so the base is 16 wide in VMEM where
  the graph's is ``_PAIR_BASE``: both are exact by construction). The
  16-blocks of ``I + A`` are inverted by forward substitution, a row of every
  block of the set at a time, in the band form (row ``t`` from the rows above
  it, rolled along the lanes); 32 and 64 by block substitution,
  ``X^T = T^T - T^T A21^T T^T`` with the halves' inverses on ``T``'s diagonal,
  never by the product form.

Every product of the chunked form, graph or kernel, is float32 at
``Precision.HIGHEST`` (on the chip the default would round its operands to
bfloat16, and the delta rule feeds what it reads back into what it writes).
The state, the decays, ``S'^T k`` and every sum over a sequence are float32
whatever type the weights have (``tests/test_kda_scan.py`` holds the kernel to
the graph at float32's level, and fails on operands rounded to bfloat16). Off
the TPU the kernels run in Pallas interpret mode."""

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


def kda_scan_plain(state, q, k, v, g, beta, chunk: int):
    """``kda_scan`` as a plain ``jax.numpy`` graph: the chunked form's
    definition, the tests' reference for the kernel and what any shape that
    does not tile runs (``scan_heads``). Operands and results as
    ``kda_scan``'s."""
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


def scan_heads(H: int, K: int, V: int, C: int) -> Optional[int]:
    """Heads a grid step of the chunked form's kernel takes for a state
    [H, K, V] float32 a row and chunks of ``C`` tokens, or None where the
    shapes do not tile (the caller keeps ``kda_scan_plain``): ``K`` one lane
    tile (a token's channels lie on the lanes of the operands and, turned, on
    the sublanes of the pair weights), ``V`` whole lane tiles, ``C`` a multiple
    of ``_SOLVE_BASE`` that divides a lane tile, so that a *set* of
    ``128 // C`` heads' chunks lie side by side on the lanes of one [C, 128]
    pair-weight matrix, and the heads whole sets. A step takes two sets where
    the heads pair up (what one set waits for, the other computes: 1.55 ms
    against 2.15 a layer and row of 1,024 tokens; PERF.md section 6, PR 43), and
    its operands, double buffered, lie inside half the scoped VMEM (2.3 MB at
    K = V = 128, C = 64) beside some 3 MB of the kernel's own values."""
    if K != _LANE or V % _LANE or C % _SOLVE_BASE or _LANE % C:
        return None
    a_set = _LANE // C
    for heads in (2 * a_set, a_set):
        tokens, state = C * heads * (3 * K + 2 * V) * 4, heads * K * V * 4
        if H % heads == 0 and 2 * tokens + 4 * state <= 8 << 20:
            return heads
    return None


def _dot(x, y):
    return jnp.dot(x, y, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _dot_nt(x, y):  # x [m, c] . y [n, c] -> [m, n]
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _scan_kernel(beta_ref, q_ref, k_ref, g_ref, v_ref, s_ref, o_ref, out_ref):
    # one chunk of ``sets`` sets of P heads of one row, the sets in step with
    # each other (what one waits for, the other computes). q_ref, k_ref, g_ref
    # [1, C, sets * P * K]; v_ref, o_ref [1, C, sets * P * V]; beta_ref
    # [1, sets, 1, 1, P * C]; s_ref, out_ref [1, sets * P, K, V], the same block
    # for every chunk of the row: out_ref is the state the chunks carry. Two
    # layouts of a [tokens, channels] operand of a set's P heads: *stacked*
    # [(head, t), channels] and, turned, *packed* [channels, (head, t)]; a
    # [C, C] matrix a head is packed [s, (head, t)] (``M^T``: the earlier token
    # on the sublanes) or block-diagonal [(head, .), (head, .)].
    f32 = jnp.float32
    C, K, W = q_ref.shape[1], _LANE, _SOLVE_BASE
    P, sets = _LANE // C, beta_ref.shape[1]
    V = v_ref.shape[2] // (P * sets)
    J, shift = C // W, C.bit_length() - 1
    each, heads = range(sets), range(P)

    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[...] = s_ref[...]

    def stacked(ref, i, width):
        return jnp.concatenate(
            [ref[0, :, (i * P + h) * width:(i * P + h + 1) * width] for h in heads], axis=0)

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    def a_head(x, h):  # a head's rows of a stacked operand
        return x[h * C:(h + 1) * C]

    row, lane = iota((_LANE, _LANE), 0), iota((_LANE, _LANE), 1)
    same_head = (row >> shift) == (lane >> shift)

    def diagonal(m):  # packed [C, (head, t)] -> [(head, s), (head, t)], zero across heads
        return jnp.where(same_head, jnp.concatenate([m] * P, axis=0), 0.0)

    q, k, g = ([stacked(ref, i, K) for i in each] for ref in (q_ref, k_ref, g_ref))
    v = [stacked(v_ref, i, V) for i in each]
    beta = [beta_ref[0, i, 0] for i in each]  # [1, (head, t)]
    # the running sum of g a head, falling: ones under the diagonal of each head's block
    ones = jnp.where(same_head & (lane <= row), 1.0, 0.0)
    G = [_dot(ones, g[i]) for i in each]
    decay = [jnp.exp(G[i]) for i in each]
    k_now, q_now = ([x[i] * decay[i] for i in each] for x in (k, q))
    # what the state the chunk found gives: (exp(G) k) S_0 to take from v, (exp(G) q) S_0 to read
    found = [[_dot(jnp.concatenate([a_head(k_now[i], h), a_head(q_now[i], h)], axis=0),
                   out_ref[0, i * P + h]) for h in heads] for i in each]
    kT, qT, GT = ([x[i].T for i in each] for x in (k, q, G))  # packed

    a_off, b_off = [[] for i in each], [[] for i in each]
    if J > 1:
        # pairs in two blocks of W: split at the sum the earlier block's last
        # token reached; both exponents <= 0
        head_of = iota((W, _LANE), 1) >> shift
        t_stacked = row & (C - 1)
        for j in range(J - 1):
            end = j * W + W - 1
            for i in each:
                ends = [G[i][h * C + end:h * C + end + 1] for h in heads]  # [1, K] a head
                G_end = jnp.concatenate([jnp.broadcast_to(e, (C, K)) for e in ends], axis=0)
                later = jnp.where(t_stacked > end, jnp.exp(jnp.minimum(G[i] - G_end, 0.0)), 0.0)
                block = [slice(h * C + j * W, h * C + (j + 1) * W) for h in heads]
                right = jnp.concatenate(
                    [k[i][block[h]] * jnp.exp(ends[h] - G[i][block[h]]) for h in heads],
                    axis=0)  # [(head, s in block j), K]
                for off, left in ((a_off[i], k[i] * later), (b_off[i], q[i] * later)):
                    every = _dot_nt(right, left)  # [(head of s, s), (head of t, t)]
                    own = every[:W]
                    for h in range(1, P):
                        own = jnp.where(head_of == h, every[h * W:(h + 1) * W], own)
                    off.append(own)

    def down(x):  # a sum over the channels of a packed operand -> [1, (head, t)]
        return jnp.sum(x, axis=0, keepdims=True)

    # pairs in one block of W: the difference itself, a channel; band d holds
    # the weight of (t, t - d). Zero where t - d lies in the block before (or,
    # rolled round, in another head: min keeps that exponent at 0)
    in_block = iota((1, _LANE), 1) & (W - 1)  # a token's place in its block
    a_band, b_band = [[None] for i in each], [[down(qT[i] * kT[i])] for i in each]
    for d in range(1, W):
        for i in each:
            m = pltpu.roll(kT[i], d, 1) * jnp.exp(jnp.minimum(GT[i] - pltpu.roll(GT[i], d, 1), 0.0))
            seen = in_block >= d
            a_band[i].append(jnp.where(seen, down(kT[i] * m) * beta[i], 0.0))
            b_band[i].append(jnp.where(seen, down(qT[i] * m), 0.0))

    # (I + A)^-1 of each block of W by forward substitution, a row of every
    # block of a set's heads at a time: z[j, (head, t)] is the inverse's entry
    # (t, the block's first token + j)
    place, col = iota((W, _LANE), 0), iota((W, _LANE), 1) & (W - 1)
    eye = jnp.where(place == col, 1.0, 0.0)
    z = [eye for i in each]
    for r in range(1, W):
        for i in each:
            above = a_band[i][1] * pltpu.roll(z[i], 1, 1)
            for d in range(2, r + 1):
                above = above + a_band[i][d] * pltpu.roll(z[i], d, 1)
            z[i] = jnp.where(col == r, eye - above, z[i])

    s_at, t_at = iota((C, _LANE), 0), iota((C, _LANE), 1) & (C - 1)
    apart = t_at - s_at
    zeros = jnp.zeros((W, _LANE), f32)
    a_T, b_T, solve_T = [], [], []
    for i in each:
        a, b = jnp.zeros((C, _LANE), f32), jnp.where(apart == 0, b_band[i][0], 0.0)
        for d in range(1, W):
            a, b = jnp.where(apart == d, a_band[i][d], a), jnp.where(apart == d, b_band[i][d], b)
        if a_off[i]:
            a = a + beta[i] * jnp.concatenate(a_off[i] + [zeros], axis=0)
            b = b + jnp.concatenate(b_off[i] + [zeros], axis=0)
        a_T.append(a)
        b_T.append(b)
        solve_T.append(jnp.where(s_at // W == t_at // W, jnp.concatenate([z[i]] * J, axis=0), 0.0))

    # larger blocks from their halves, [[T1, 0], [-T2 A21 T1, T2]], turned:
    # X^T = T^T - T^T A21^T T^T with T the halves' inverses on the diagonal
    size = W
    while size < C:
        half = size.bit_length() - 1
        below = ((s_at >> half) & 1 == 0) & ((t_at >> half) == (s_at >> half) + 1)
        lower = [_dot(solve_T[i], diagonal(jnp.where(below, a_T[i], 0.0))) for i in each]
        solve_T = [solve_T[i] - _dot(lower[i], diagonal(solve_T[i])) for i in each]
        size *= 2

    # what each token writes, (I + A)^-1 beta (v - (exp(G) k) S_0): beta scales the columns
    w = [_dot(diagonal(solve_T[i]).T * beta[i],
              v[i] - jnp.concatenate([found[i][h][:C] for h in heads], axis=0)) for i in each]
    for i in each:
        o = jnp.concatenate([found[i][h][C:] for h in heads], axis=0) + _dot(diagonal(b_T[i]).T, w[i])
        for h in heads:
            o_ref[0, :, (i * P + h) * V:(i * P + h + 1) * V] = a_head(o, h)
    # the state at the chunk's end: decayed, and what each token wrote decayed to there
    for i in each:
        last = [GT[i][:, h * C + C - 1:(h + 1) * C] for h in heads]  # [K, 1] a head
        G_last = jnp.broadcast_to(last[0], (K, _LANE))
        for h in range(1, P):
            G_last = jnp.where((lane >> shift) == h, last[h], G_last)
        to_end_T = kT[i] * jnp.exp(G_last - GT[i])
        for h in heads:
            out_ref[0, i * P + h] = out_ref[0, i * P + h] * jnp.exp(last[h]) + _dot(
                to_end_T, jnp.where((row[:, :1] >> shift) == h, w[i], 0.0))


def kda_scan(state, q, k, v, g, beta, chunk: int):
    """``T`` tokens a row, in chunks. state [b, H, K, V] float32 (what the
    row's earlier tokens left); q, k, g [b, T, H, K]; v [b, T, H, V]; beta
    [b, T, H] (``g`` and ``beta`` 0 where the row has no token). ``chunk``: a
    multiple of 4. Returns (o [b, T, H, V] float32, the state after the row's
    last token). Where the shapes tile (``scan_heads``) one Pallas kernel over
    (row, heads of a step, chunk), the chunks of a row in turn; any other
    shape ``kda_scan_plain``."""
    b, T, H, K = q.shape
    V = v.shape[-1]
    heads = scan_heads(H, K, V, chunk)
    if heads is None:
        return kda_scan_plain(state, q, k, v, g, beta, chunk)
    f32 = jnp.float32
    C, a_set = chunk, _LANE // chunk
    pad = -T % C  # whole chunks; a padded token has beta 0 and g 0
    nc = (T + pad) // C

    def whole(t):
        t = t.astype(f32)
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t

    # [b, T, H, width] -> [b, T, H * width] moves nothing: a head's [C, width]
    # is a slab of whole tiles at lane offset head * width
    q, k, g, v = (whole(t.reshape(b, T, -1)) for t in (q, k, g, v))
    # [b, T, H] -> [b, set, chunk, 1, (head, t)]: a set's writing strengths
    # over a chunk as one row of lanes, the order its pair weights lie in
    beta = whole(beta).reshape(b, nc, C, H // a_set, a_set).transpose(0, 3, 1, 4, 2).reshape(
        b, H // a_set, nc, 1, _LANE)

    def tokens(width):
        return pl.BlockSpec((1, C, heads * width), lambda r, s, c: (r, c, s))

    a_state = pl.BlockSpec((1, heads, K, V), lambda r, s, c: (r, s, 0, 0))
    o, last = pl.pallas_call(
        _scan_kernel,
        grid=(b, H // heads, nc),
        in_specs=[pl.BlockSpec((1, heads // a_set, 1, 1, _LANE), lambda r, s, c: (r, s, c, 0, 0)),
                  tokens(K), tokens(K), tokens(K), tokens(V), a_state],
        out_specs=[tokens(V), a_state],
        out_shape=[jax.ShapeDtypeStruct((b, nc * C, H * V), f32),
                   jax.ShapeDtypeStruct((b, H, K, V), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret(),
        name="kda_scan",
    )(beta, q, k, g, v, state.astype(f32))
    return o.reshape(b, nc * C, H, V)[:, :T], last
