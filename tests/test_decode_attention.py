"""``ops/decode_attention.py`` in Pallas interpret mode against the einsum it
stands in for (``models/patterned.py _grouped_attention``) under the same
mask, and the host's count of what it reads. Compilation at the serving
cells' widths for a described v5e is in ``tests/test_chip_compile_kernels.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.patterned import _grouped_attention
from ray_tpu.ops.decode_attention import BLOCK, block_size, decode_attention, positions_read

L, K, D, S = 3, 2, 128, 4 * BLOCK
# [lo, hi) of one row
BOUNDS = {
    "one-position": (0, 1),
    "one-position-at-a-block-end": (2 * BLOCK - 1, 2 * BLOCK),
    "ends-mid-block": (0, BLOCK + 37),
    "starts-and-ends-mid-block": (BLOCK - 5, 3 * BLOCK + 9),
    "window-inside-one-block": (BLOCK + 3, BLOCK + 11),
    "one-whole-block": (BLOCK, 2 * BLOCK),
    "the-whole-stripe": (0, S),
}


def _case(group, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (len(BOUNDS), K * group, D), dtype),
        jax.random.normal(ks[1], (L, len(BOUNDS), K, S, D), dtype),
        jax.random.normal(ks[2], (L, len(BOUNDS), K, S, D), dtype),
    )


def _einsum(q, ck, cv, layer, lo, hi):
    slot = jnp.arange(ck.shape[3])[None, None, :]
    mask = (slot >= lo[:, None, None]) & (slot < hi[:, None, None])
    return _grouped_attention(q[:, None], ck[layer], cv[layer], mask)[:, 0]


@pytest.mark.parametrize("name", list(BOUNDS))
@pytest.mark.parametrize("group", [4, 6, 8])
def test_kernel_equals_the_einsum_under_the_same_mask(group, name):
    """Every row of one batch has bounds of its own (the rows before and
    after ``name``'s are the other cases, so a row's first block is fetched
    while another row's last is multiplied); bf16 as the cells store it,
    equal within bf16's rounding of the einsum's scores."""
    q, ck, cv = _case(group, jnp.bfloat16)
    order = list(BOUNDS)
    order = order[order.index(name):] + order[:order.index(name)]
    lo, hi = (jnp.asarray([BOUNDS[n][i] for n in order], jnp.int32) for i in (0, 1))
    got = jax.jit(decode_attention)(q, ck, cv, jnp.int32(1), lo, hi)
    want = _einsum(q, ck, cv, 1, lo, hi)
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.03, rtol=0.03)


def test_kernel_in_float32_equals_the_einsum_to_rounding():
    q, ck, cv = _case(4, jnp.float32, seed=1)
    lo, hi = (jnp.asarray([b[i] for b in BOUNDS.values()], jnp.int32) for i in (0, 1))
    for layer in (0, 2):  # a static layer index, as the leading layers pass it
        got = decode_attention(q, ck, cv, layer, lo, hi)
        np.testing.assert_allclose(got, _einsum(q, ck, cv, layer, lo, hi), atol=2e-5, rtol=2e-5)


def test_bounds_past_the_stripe_are_walked_as_the_clamped_ones():
    """A dead slot's length runs on past its stripe, and a window's start with
    it: the kernel reads inside the stripe and gives finite numbers."""
    q, ck, cv = _case(4, jnp.float32, seed=2)
    n = len(BOUNDS)
    lo = jnp.asarray([S + 90, -3, S - 1, 0, 7, 7, 7][:n], jnp.int32)
    hi = jnp.asarray([S + 99, 0, S + 5, S + 1, 7, 3, 8][:n], jnp.int32)
    got = decode_attention(q, ck, cv, 0, lo, hi)
    clo = jnp.asarray([S - 1, 0, S - 1, 0, 6, 2, 7], jnp.int32)
    chi = jnp.asarray([S, 1, S, S, 7, 3, 8], jnp.int32)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, _einsum(q, ck, cv, 0, clo, chi), atol=2e-5, rtol=2e-5)


def test_a_stripe_of_no_whole_blocks_is_refused():
    assert block_size(S) == BLOCK and block_size(S + 8) is None and block_size(64) is None
    with pytest.raises(ValueError, match="whole number"):
        positions_read(0, 10, 64)
    with pytest.raises(ValueError, match="whole number"):
        decode_attention(jnp.zeros((1, 4, D)), jnp.zeros((1, 1, 1, 64, D)),
                         jnp.zeros((1, 1, 1, 64, D)), 0, jnp.zeros((1,), jnp.int32),
                         jnp.ones((1,), jnp.int32))


@pytest.mark.parametrize("lo, hi, want", [
    (0, 1, BLOCK), (0, BLOCK, BLOCK), (0, BLOCK + 1, 2 * BLOCK), (BLOCK - 1, BLOCK + 1, 2 * BLOCK),
    (BLOCK, 2 * BLOCK, BLOCK), (5, S, S), (0, S + 400, S), (S + 3, S + 9, BLOCK), (-20, 0, BLOCK),
])
def test_positions_read_counts_the_blocks_between_the_bounds(lo, hi, want):
    assert positions_read(lo, hi, S) == want
    assert max(min(hi, S) - max(lo, 0), 1) <= positions_read(lo, hi, S) <= S


def test_positions_read_walks_the_blocks_the_kernel_walks():
    """The host's count against the kernel itself: values are 1 in the blocks
    ``positions_read`` counts and NaN in every other, so a block read beyond
    the count brings a NaN out (a masked position's weight is 0, and 0 x NaN
    is NaN). The counted blocks cover the bounds (checked here on the host),
    and that the kernel leaves nothing out between the bounds is the
    comparisons with the einsum above. Bounds as arrays, as the engine hands
    them, some past the stripe as a dead slot's are."""
    lo = np.asarray([0, BLOCK - 5, BLOCK + 3, 5, S + 3, -20, 0])
    hi = np.asarray([1, 3 * BLOCK + 9, BLOCK + 11, S, S + 9, 0, S + 400])
    read = positions_read(lo, hi, S)
    assert read.tolist() == [BLOCK, 4 * BLOCK, BLOCK, S, BLOCK, BLOCK, S]
    clo, chi = np.clip(lo, 0, S - 1), np.clip(hi, 1, S)
    clo = np.minimum(clo, chi - 1)
    first = clo // BLOCK * BLOCK
    in_blocks = (np.arange(S) >= first[:, None]) & (np.arange(S) < (first + read)[:, None])
    assert (in_blocks.sum(1) == read).all() and (first + read >= chi).all()
    cv = jnp.broadcast_to(jnp.where(in_blocks, 1.0, jnp.nan)[None, :, None, :, None],
                          (1, len(lo), K, S, D)).astype(jnp.float32)
    got = decode_attention(jnp.ones((len(lo), 4 * K, D)), jnp.zeros_like(cv), cv, 0,
                           jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32))
    np.testing.assert_allclose(got, 1.0, rtol=1e-6)
