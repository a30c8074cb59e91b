"""``ops/decode_attention.py`` in Pallas interpret mode against the einsum it
stands in for (``models/patterned.py _grouped_attention``) under the same
mask, at every block its rule can give (the block follows from what a position
of the cache holds and from the stripe), and the host's count of what it
reads. Compilation at the serving cells' widths for a described v5e is in
``tests/test_chip_compile_kernels.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.patterned import _grouped_attention
from ray_tpu.ops.decode_attention import (
    BLOCK,
    BLOCK_BYTES,
    block_size,
    cache_position_bytes,
    decode_attention,
    positions_read,
)

L, D = 3, 128
# name -> (key-value heads, query heads a key-value head, stripe, the block the
# rule gives a cache of such rows): two heads of 2 x 128 bfloat16 numbers are
# 1,024 bytes a position and take the longest block that divides the stripe
# (ZAYA1's 4 and Nemotron-3's 16 query heads a group), eight are 4,096 and take
# 128 whatever the stripe (Mistral's 4, Laguna's 6 and 8)
SHAPES = {
    "2x4-block-128": (2, 4, 5 * 128, 128),
    "2x4-block-256": (2, 4, 5 * 256, 256),
    "2x4-block-512": (2, 4, 4 * 512, 512),
    "2x16-block-256": (2, 16, 5 * 256, 256),
    "2x16-block-512": (2, 16, 4 * 512, 512),
    "8x4-block-128": (8, 4, 4 * 128, 128),
    "8x6-block-128": (8, 6, 4 * 512, 128),
    "8x8-block-128": (8, 8, 4 * 128, 128),
}


def _bounds(bs, stripe):
    """name -> [lo, hi) of one row, in blocks of ``bs`` positions."""
    return {
        "one-position": (0, 1),
        "one-position-at-a-block-end": (2 * bs - 1, 2 * bs),
        "ends-mid-block": (0, bs + 37),
        "starts-and-ends-mid-block": (bs - 5, 3 * bs + 9),
        "window-inside-one-block": (bs + 3, bs + 11),
        "one-whole-block": (bs, 2 * bs),
        "the-whole-stripe": (0, stripe),
        "a-dead-slot-past-the-stripe": (0, stripe + 400),
    }


BOUNDS = list(_bounds(BLOCK, 4 * BLOCK))


def _draw(K, group, S, dtype, seed=0, rows=len(BOUNDS)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (rows, K * group, D), dtype),
        jax.random.normal(ks[1], (L, rows, K, S, D), dtype),
        jax.random.normal(ks[2], (L, rows, K, S, D), dtype),
    )


# float32 rows hold twice the bytes: (key-value heads, a group, stripe, the block)
FLOAT32_SHAPES = [(2, 4, 5 * 128, 128), (2, 4, 4 * 512, 256), (1, 8, 4 * 512, 512)]


def _einsum(q, ck, cv, layer, lo, hi):
    slot = jnp.arange(ck.shape[3])[None, None, :]
    mask = (slot >= lo[:, None, None]) & (slot < hi[:, None, None])
    return _grouped_attention(q[:, None], ck[layer], cv[layer], mask)[:, 0]


@pytest.mark.parametrize("name", BOUNDS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_equals_the_einsum_under_the_same_mask(shape, name):
    """Every row of one batch has bounds of its own (the rows before and
    after ``name``'s are the other cases, so a row's first block is fetched
    while another row's last is multiplied); bf16 as the cells store it,
    equal within bf16's rounding of the einsum's scores."""
    K, group, S, bs = SHAPES[shape]
    q, ck, cv = _draw(K, group, S, jnp.bfloat16)
    assert block_size(S, cache_position_bytes(ck, cv)) == bs
    bounds = _bounds(bs, S)
    order = BOUNDS[BOUNDS.index(name):] + BOUNDS[:BOUNDS.index(name)]
    lo, hi = (jnp.asarray([bounds[n][i] for n in order], jnp.int32) for i in (0, 1))
    got = jax.jit(decode_attention)(q, ck, cv, jnp.int32(1), lo, hi)
    want = _einsum(q, ck, cv, 1, lo, jnp.minimum(hi, S))
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.03, rtol=0.03)


@pytest.mark.parametrize("K, group, S, bs", FLOAT32_SHAPES)
def test_kernel_in_float32_equals_the_einsum_to_rounding(K, group, S, bs):
    q, ck, cv = _draw(K, group, S, jnp.float32, seed=1)
    assert block_size(S, cache_position_bytes(ck, cv)) == bs
    lo, hi = (jnp.asarray([b[i] for b in _bounds(bs, S).values()], jnp.int32) for i in (0, 1))
    for layer in (0, 2):  # a static layer index, as the leading layers pass it
        got = decode_attention(q, ck, cv, layer, lo, hi)
        np.testing.assert_allclose(
            got, _einsum(q, ck, cv, layer, lo, jnp.minimum(hi, S)), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("K, group, S, bs", FLOAT32_SHAPES)
def test_bounds_past_the_stripe_are_walked_as_the_clamped_ones(K, group, S, bs):
    """A dead slot's length runs on past its stripe, and a window's start with
    it: the kernel reads inside the stripe and gives finite numbers."""
    q, ck, cv = _draw(K, group, S, jnp.float32, seed=2, rows=7)
    lo = jnp.asarray([S + 90, -3, S - 1, 0, 7, 7, 7], jnp.int32)
    hi = jnp.asarray([S + 99, 0, S + 5, S + 1, 7, 3, 8], jnp.int32)
    got = decode_attention(q, ck, cv, 0, lo, hi)
    clo = jnp.asarray([S - 1, 0, S - 1, 0, 6, 2, 7], jnp.int32)
    chi = jnp.asarray([S, 1, S, S, 7, 3, 8], jnp.int32)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, _einsum(q, ck, cv, 0, clo, chi), atol=2e-5, rtol=2e-5)


# what a position holds in a layer of the nine cells' caches (bfloat16): key-value
# heads x (key + value), a latent layer's one 128-lane row of the rotated key
# and its latent
HEADS_2, HEADS_8, LATENT, LATENT_WIDE = 2 * 256 * 2, 8 * 256 * 2, (128 + 512) * 2, (128 + 1024) * 2


@pytest.mark.parametrize("cell, stripe, position_bytes, latent, want", [
    ("zaya1-8b-serve-long-chat", 4608, HEADS_2, False, 512),
    ("nemotron3-super-serve-chat", 2048, HEADS_2, False, 512),
    ("mistral7b-serve-saturated", 1024, HEADS_8, False, 128),
    ("laguna-xs2-serve-mixed", 4096, HEADS_8, False, 128),
    ("solar-open2-serve-long-chat", 8192, HEADS_8, False, 128),
    ("kanana2-serve-docs-shared", 24576, LATENT, True, 512),
    ("dots3-note-serve-docs-shared", 24576, LATENT, True, 512),
    ("dots3-note-serve-docs-shared: its sliding layers", 24576, LATENT_WIDE, True, 512),
    ("a latent stripe of whole 256s", 768, LATENT, True, 256),
    ("a latent stripe of whole 128s", 128, LATENT, True, 128),
    ("two heads, a stripe of whole 256s", 768, HEADS_2, False, 256),
    ("two heads, a stripe of whole 128s", 640, HEADS_2, False, 128),
    ("four heads: 256 positions hold the target", 4096, 2 * HEADS_2, False, 256),
    ("thirty-two heads: the shortest, whatever it holds", 4096, 4 * HEADS_8, False, 128),
    ("a stripe no block divides", 4608 + 8, HEADS_2, False, None),
    ("a stripe shorter than a block", 64, HEADS_8, False, None),
    ("a latent stripe no block divides", 96, LATENT, True, None),
])
def test_block_size_at_the_cells_shapes(cell, stripe, position_bytes, latent, want):
    """The block follows from the cache's shape and stripe alone: at or under
    ``BLOCK_BYTES`` of keys and values together (Mistral's 128 positions of
    eight heads), the longest that divides the stripe."""
    assert block_size(stripe, position_bytes, latent) == want
    if want and not latent and want > BLOCK:
        assert want * position_bytes <= BLOCK_BYTES
    lengths = np.asarray([1, want or 1, 700, 1500, stripe, stripe + 300])
    if want is None:
        with pytest.raises(ValueError, match="whole number"):
            positions_read(0, lengths, stripe, position_bytes, latent)
    else:  # whole blocks that cover the live positions, inside the stripe
        read = positions_read(0, lengths, stripe, position_bytes, latent)
        assert (read % want == 0).all() and (read >= np.minimum(lengths, stripe)).all()
        assert (read - np.minimum(lengths, stripe) < want).all() and (read <= stripe).all()


def test_cache_position_bytes_reads_the_leaves_the_kernel_is_handed():
    sds = jax.ShapeDtypeStruct
    zaya = sds((20, 64, 2, 4608, 128), jnp.bfloat16)
    assert cache_position_bytes(zaya, zaya) == HEADS_2
    mistral = sds((16, 32, 8, 1024, 128), jnp.bfloat16)
    assert cache_position_bytes(mistral, mistral) == HEADS_8
    assert cache_position_bytes(sds((5, 24, 1, 24576, 128), jnp.bfloat16),
                                sds((5, 24, 1, 24576, 512), jnp.bfloat16)) == LATENT
    wide = sds((1, 1, 2, 512, 128), jnp.float32)
    assert cache_position_bytes(wide, wide) == 2 * HEADS_2


def test_a_stripe_of_no_whole_blocks_is_refused():
    with pytest.raises(ValueError, match="whole number"):
        positions_read(0, 10, 64, HEADS_2)
    with pytest.raises(ValueError, match="whole number"):
        decode_attention(jnp.zeros((1, 4, D)), jnp.zeros((1, 1, 1, 64, D)),
                         jnp.zeros((1, 1, 1, 64, D)), 0, jnp.zeros((1,), jnp.int32),
                         jnp.ones((1,), jnp.int32))


def _counted(bs, S):
    """name -> (lo, hi, positions read) in a stripe of ``S`` positions walked
    in blocks of ``bs``."""
    return {
        "one-position": (0, 1, bs), "one-whole-block": (0, bs, bs), "one-past-a-block": (0, bs + 1, 2 * bs),
        "across-a-block-end": (bs - 1, bs + 1, 2 * bs), "the-second-block": (bs, 2 * bs, bs),
        "the-whole-stripe": (5, S, S), "past-the-stripe": (0, S + 400, S),
        "wholly-past-the-stripe": (S + 3, S + 9, bs), "before-the-stripe": (-20, 0, bs),
    }


@pytest.mark.parametrize("block, stripe, position_bytes", [
    (128, 4 * 128, HEADS_8), (128, 5 * 128, HEADS_2), (256, 5 * 256, HEADS_2), (512, 4 * 512, HEADS_2)])
@pytest.mark.parametrize("name", list(_counted(BLOCK, 4 * BLOCK)))
def test_positions_read_counts_the_blocks_between_the_bounds(block, stripe, position_bytes, name):
    lo, hi, want = _counted(block, stripe)[name]
    assert block_size(stripe, position_bytes) == block
    assert positions_read(lo, hi, stripe, position_bytes) == want
    assert max(min(hi, stripe) - max(lo, 0), 1) <= want <= stripe


@pytest.mark.parametrize("shape", ["2x4-block-128", "2x4-block-256", "2x4-block-512", "8x4-block-128"])
def test_positions_read_walks_the_blocks_the_kernel_walks(shape):
    """The host's count against the kernel itself: values are 1 in the blocks
    ``positions_read`` counts and NaN in every other, so a block read beyond
    the count brings a NaN out (a masked position's weight is 0, and 0 x NaN
    is NaN). The counted blocks cover the bounds (checked here on the host),
    and that the kernel leaves nothing out between the bounds is the
    comparisons with the einsum above. Bounds as arrays, as the engine hands
    them, some past the stripe as a dead slot's are."""
    K, group, S, bs = SHAPES[shape]
    lo = np.asarray([0, bs - 5, bs + 3, 5, S + 3, -20, 0])
    hi = np.asarray([1, 3 * bs + 9, bs + 11, S, S + 9, 0, S + 400])
    position_bytes = K * 2 * D * 2  # bfloat16, as the shape's block is reckoned
    read = positions_read(lo, hi, S, position_bytes)
    assert read.tolist() == [bs, 4 * bs, bs, S, bs, bs, S]
    clo, chi = np.clip(lo, 0, S - 1), np.clip(hi, 1, S)
    clo = np.minimum(clo, chi - 1)
    first = clo // bs * bs
    in_blocks = (np.arange(S) >= first[:, None]) & (np.arange(S) < (first + read)[:, None])
    assert (in_blocks.sum(1) == read).all() and (first + read >= chi).all()
    cv = jnp.broadcast_to(jnp.where(in_blocks, 1.0, jnp.nan)[None, :, None, :, None],
                          (1, len(lo), K, S, D)).astype(jnp.bfloat16)
    assert cache_position_bytes(cv, cv) == position_bytes and block_size(S, position_bytes) == bs
    got = decode_attention(jnp.ones((len(lo), group * K, D), cv.dtype), jnp.zeros_like(cv), cv, 0,
                           jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32))
    np.testing.assert_allclose(np.asarray(got, np.float32), 1.0, rtol=1e-6)
