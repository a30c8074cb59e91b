"""The sampler's selection (``ray_tpu/ops/topk.py top_k``) is the one-stage
``jax.lax.top_k``: the same values and the same indices, ties to the lower
index, at every serving cell's vocabulary and at the shapes that could part
the two (ties across blocks and at rank 64, all winners in one block or one
in each, ``-inf``), one row or many. The order itself is held against NumPy
(value descending, index ascending), so the file can also be run where the
one-stage call is the thing in doubt: on the chip, ``python -m pytest
tests/test_topk.py --noconftest -k "not engine and not 2x16384 and not
other_k"`` from outside the checkout (a v5e's own ``lax.top_k`` of ``[1, n]``
and ``[2, n]`` is a merge of sorted parts that drops the order among ties, so
two rows fail there as the one-stage call does; one row goes through the
stages unbatched, a stable sort, and four rows or more keep the order at the
sampler's ``k`` of 64; at ``k`` = 100, which no program asks for, the chip's
sorts lose the order among ties again: PERF.md section 6, PR 50). One tiny engine whose vocabulary takes the two stages draws the tokens
the one-stage call draws."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import topk

K = 64
B = topk.BLOCK


def _by_order(x, k=K):
    """(values, indices) of the first ``k`` of each row of ``x`` by (value
    descending, index ascending)."""
    x = np.asarray(x)
    rows = x.reshape(-1, x.shape[-1])
    index = np.broadcast_to(np.arange(rows.shape[-1]), rows.shape)
    order = np.lexsort((index, -rows.astype(np.float64)), axis=-1)[:, :k]
    return (np.take_along_axis(rows, order, -1).reshape(*x.shape[:-1], k),
            order.reshape(*x.shape[:-1], k))


def _random(width, rows=4, seed=0):
    return np.random.default_rng(seed + width).standard_normal((rows, width)).astype(np.float32) * 3


def _tied(width, rows=4):
    """Logits rounded to quarters: a few dozen distinct values, so hundreds
    tie at every rank, inside blocks and across them."""
    return np.round(_random(width, rows, seed=1) * 4) / 4


def _one_block(width, rows=4):
    """The 64 largest all in one block (the last whole one)."""
    x = _random(width, rows, seed=2)
    at = (width // B - 1) * B
    x[:, at:at + K] = 100.0 + np.arange(K, dtype=np.float32)[::-1]
    return x


def _one_a_block(width, rows=4):
    """One of the 64 largest in each of 64 blocks, the blocks spread over the row."""
    x = _random(width, rows, seed=3)
    for rank, block in enumerate(np.linspace(0, width // B - 1, K).astype(int)):
        x[:, block * B + (7 * rank) % B] = 200.0 - rank
    return x


def _ties_at_the_cut(width, rows=4):
    """96 entries tie at the value of rank 33 to 64, two a block over 48 blocks
    and on both sides of block boundaries: the lower 32 indices are in, the
    others out, and the block that holds only losers must not displace one
    that holds a winner."""
    x = _random(width, rows, seed=4).clip(-5, 5)
    x[:, :32] = 50.0 + np.arange(32, dtype=np.float32)
    first = width // B - 50
    for i in range(48):
        x[:, (first + i) * B - 1] = 40.0
        x[:, (first + i) * B] = 40.0
    return x


def _minus_infinity(width, rows=4):
    """All but 40 entries are -inf: the last 24 candidates are -inf entries,
    the lowest indices first, none of them a padded lane."""
    x = np.full((rows, width), -np.inf, np.float32)
    x[:, np.linspace(5, width - 3, 40).astype(int)] = np.arange(40, dtype=np.float32)
    return x


def _all_equal(width, rows=4):
    return np.zeros((rows, width), np.float32)


CELLS = (262272, 128256, 100352, 32768, 24576)
CASES = [
    *[pytest.param(_random, w, id=f"random-{w}") for w in (*CELLS, 20000, 8192, 8193, 512)],
    *[pytest.param(_tied, w, id=f"tied-{w}") for w in (*CELLS, 20000, 8192, 512)],
    *[pytest.param(make, w, id=f"{make.__name__.strip('_')}-{w}")
      for make in (_one_block, _one_a_block, _ties_at_the_cut, _minus_infinity, _all_equal)
      for w in (262272, 32768, 20000)],
]


@pytest.mark.parametrize("make, width", CASES)
def test_the_selection_is_the_one_stage_call(make, width):
    """Values and indices equal ``lax.top_k``'s and the order's, row by row
    and over the rows at once."""
    x = make(width)
    assert topk.two_stage(width, K) == (width > K * B)
    want_vals, want_idx = _by_order(x)
    plain_vals, plain_idx = jax.lax.top_k(jnp.asarray(x), K)
    np.testing.assert_array_equal(np.asarray(plain_idx), want_idx)
    for name, select in (
        ("rows at once", jax.jit(lambda a: topk.top_k(a, K))),
        ("a row at a time", jax.jit(jax.vmap(lambda r: topk.top_k(r, K)))),
    ):
        vals, idx = select(jnp.asarray(x))
        assert idx.dtype == plain_idx.dtype and vals.dtype == plain_vals.dtype, name
        np.testing.assert_array_equal(np.asarray(vals), want_vals, err_msg=name)
        np.testing.assert_array_equal(np.asarray(idx), want_idx, err_msg=name)
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(plain_vals), err_msg=name)


@pytest.mark.parametrize("shape", [(16384,), (1, 16384), (2, 16384), (4, 16400), (5, 16500),
                                   (8, 16512), (2, 16, 16384), (3, 8, 20000)],
                         ids=lambda s: "x".join(map(str, s)))
def test_any_leading_shape(shape):
    """One row with no batch, rows that fill whole tiles of eight (the view
    that keeps a tile a tile) and rows that do not, two leading axes."""
    x = np.round(np.random.default_rng(5).standard_normal(shape).astype(np.float32) * 8) / 4
    vals, idx = jax.jit(lambda a: topk.top_k(a, K))(jnp.asarray(x))
    want_vals, want_idx = _by_order(x)
    np.testing.assert_array_equal(np.asarray(vals), want_vals)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)


@pytest.mark.parametrize("k", [1, 5, 64, 100])
def test_other_k(k):
    """The function of ``k``: the stages engage where the row has more than
    ``k`` blocks."""
    x = _tied(20000)
    vals, idx = jax.jit(lambda a: topk.top_k(a, k))(jnp.asarray(x))
    want_vals, want_idx = _by_order(x, k)
    np.testing.assert_array_equal(np.asarray(vals), want_vals)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)


def test_a_narrow_row_lowers_to_the_plain_call():
    """At or under ``k`` blocks the function is ``lax.top_k`` to the letter:
    every tiny model's programs are the parent's."""
    x = jax.ShapeDtypeStruct((4, K * B), jnp.float32)
    ours = jax.jit(lambda a: topk.top_k(a, K)).lower(x).as_text()
    plain = jax.jit(lambda a: jax.lax.top_k(a, K)).lower(x).as_text()
    assert ours == plain
    wide = jax.jit(lambda a: topk.top_k(a, K)).lower(
        jax.ShapeDtypeStruct((4, K * B + 1), jnp.float32)).as_text()
    assert wide.count("chlo.top_k") == 2 and "stablehlo.gather" in wide


# ---- one engine whose vocabulary takes the two stages ----

WIDE = 8320  # 65 blocks: one more than the static K


def _tokens(monkeypatch, one_stage: bool):
    from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams

    if one_stage:
        monkeypatch.setattr(topk, "top_k", jax.lax.top_k)
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="tiny", seed=3, model_kwargs=dict(vocab_size=WIDE)),
        engine=EngineConfig(max_num_seqs=2, max_seq_len=64, prefill_chunk=16, prefill_buckets=(16,),
                            max_concurrent_admissions=1, enable_prefix_caching=False,
                            dtype="float32")))
    try:
        assert eng._top_k_static == K and topk.two_stage(eng.model_cfg.vocab_size, K)
        prompts = ([11, 12, 13, 14, 15], list(range(40, 64)))  # a final chunk alone; one behind a middle chunk
        out = {}
        for name, kw in (("top_k 5", dict(temperature=0.8, top_k=5, seed=11)),
                         ("top_k 64", dict(temperature=0.8, top_k=64, seed=12)),
                         ("greedy", dict(temperature=0.0))):
            params = SamplingParams(max_tokens=12, ignore_eos=True, **kw)
            out[name] = [list(eng.generate(prompt_token_ids=list(p), sampling_params=params).token_ids)
                         for p in prompts]
        return out
    finally:
        eng.shutdown()


def test_engine_draws_the_tokens_the_one_stage_call_draws(monkeypatch):
    """Seeded requests at temperature 0.8 with ``top_k`` 5 and 64, and greedy
    ones, through a tiny engine whose vocabulary is 65 blocks wide: the first
    token (the final chunk's one row) and the decode steps' (the pool's rows)
    are those of the same engine with ``lax.top_k`` in the selection's place,
    from the same weights, prompts and keys."""
    ours = _tokens(monkeypatch, one_stage=False)
    plain = _tokens(monkeypatch, one_stage=True)
    assert ours == plain
    assert ours["top_k 5"] != ours["greedy"] and ours["top_k 64"] != ours["top_k 5"]
    assert all(len(t) == 12 and max(t) < WIDE for runs in ours.values() for t in runs)
