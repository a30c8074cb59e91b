"""A prompt chunk's launch carries the pool's decode step (``llm/engine.py
programs``: ``chunk_mid`` and ``chunk_final`` with the pool's ``rows``;
``models/patterned.py decode_forward`` with ``beside``): the rows that decode
ride through the same read of the weights as the chunk's tokens. Here the
carrying programs against the two programs they replace, on one pool's cache a
kind of model (the three tests share that fixture and what it has run, so
they stay one file: apart they took three times as long). The loop that
launches them, the counters and the pool that never carries:
``tests/test_carried_decode_loop.py``; requests admitted beside decoding rows,
family by family: ``tests/test_carried_decode_beside.py``; what an engine
compiles before it takes requests: ``tests/test_carried_decode_warm.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import programs
from ray_tpu.models.llama import LlamaConfig, init_kv_cache, init_params, prefill
from ray_tpu.models.patterned import STATE_LEAVES, moe_stats_names, plan

pytestmark = pytest.mark.timeout(900) if hasattr(pytest.mark, "timeout") else []

CONFIGS = {
    "dense": LlamaConfig.tiny,
    "routed-window": LlamaConfig.laguna_tiny,
    "state-space": LlamaConfig.nemotron_tiny,
}
SLOTS, STRIPE, CHUNK = 4, 64, 8
TOL = dict(atol=2e-5, rtol=2e-5)


@pytest.fixture(scope="module", params=list(CONFIGS))
def pool(request):
    """(cfg, the programs' bodies, params, a pool's cache of four slots of
    which three hold prompts of 5, 17 and 23 tokens and the fourth a
    tenant's leftovers, the pool's decode inputs, a prompt of 21 tokens)."""
    cfg = CONFIGS[request.param]()
    fns = programs(cfg)
    params = init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(41)
    lens = np.asarray([5, 17, 23, 11], np.int32)
    tokens = rng.integers(1, 250, (SLOTS, 24)).astype(np.int32)
    _, cache = prefill(params, init_kv_cache(cfg, SLOTS, STRIPE), jnp.asarray(tokens), cfg,
                       lengths=jnp.asarray(lens))
    rows = dict(
        tokens=jnp.asarray(rng.integers(1, 250, SLOTS).astype(np.int32)),
        temps=jnp.asarray([0.0, 0.9, 0.0, 0.7], jnp.float32),
        top_ks=jnp.asarray([1, 8, 1, 4], jnp.int32),
        keys=jax.random.split(jax.random.PRNGKey(9), SLOTS),
    )
    prompt = rng.integers(1, 250, 21).astype(np.int32)
    return cfg, fns, params, cache, rows, prompt


def _chunk(prompt, at, width=CHUNK):
    piece = prompt[at:at + width]
    toks = np.zeros((1, width), np.int32)
    toks[0, :len(piece)] = piece
    return (jnp.asarray(toks), jnp.asarray([len(piece)], jnp.int32), jnp.asarray([at], jnp.int32))


def _decode(fns, params, cache, rows):
    return fns["decode_fn"](params, dict(cache), rows["tokens"], rows["temps"], rows["top_ks"],
                            rows["keys"])


def _assert_slots_equal(got, want, before, rows_live, written_at, slots=range(SLOTS)):
    """A pool's cache after a carried step against the decode program's:
    equal within float32 rounding where a live row wrote (its position
    ``written_at[b]``), to the bit everywhere else, and a row that is not
    live as it was ``before``."""
    for name in ("k", "v"):
        g, w, b = (np.asarray(c[name]) for c in (got, want, before))
        for slot in slots:
            if not rows_live[slot]:
                np.testing.assert_array_equal(g[:, slot], b[:, slot])
                continue
            at = written_at[slot]
            np.testing.assert_allclose(g[:, slot, :, at], w[:, slot, :, at], **TOL)
            rest = np.arange(STRIPE) != at
            np.testing.assert_array_equal(g[:, slot][:, :, rest], w[:, slot][:, :, rest])
            np.testing.assert_array_equal(g[:, slot][:, :, rest], b[:, slot][:, :, rest])
    for name in STATE_LEAVES:
        if name in want:
            g, w, b = (np.asarray(c[name]) for c in (got, want, before))
            for slot in slots:
                if rows_live[slot]:
                    np.testing.assert_allclose(g[:, slot], w[:, slot], **TOL)
                else:
                    np.testing.assert_array_equal(g[:, slot], b[:, slot])


@pytest.mark.parametrize("n_stripes", [1, 2])
def test_a_carrying_middle_chunk_is_the_chunk_then_the_decode_step(pool, n_stripes):
    """``chunk_mid`` with the pool's rows against ``chunk_mid`` and then
    ``decode_fn``: the stripes, the next tokens (greedy and sampled rows),
    the keys, the pool's cache and state, the routing counts of all the
    launch's rows under the chunk's stripe. One row is not live: it comes
    out as it went in."""
    cfg, fns, params, cache, rows, prompt = pool
    ones = tuple(fns["new_stripe"](STRIPE) for _ in range(n_stripes))
    ones = fns["chunk_mid"](params, ones, *(jnp.concatenate([a] * n_stripes) for a in _chunk(prompt, 0)))
    args = tuple(jnp.concatenate([a] * n_stripes) for a in _chunk(prompt, CHUNK))
    live = np.asarray([True, True, False, True])
    want_ones = fns["chunk_mid"](params, ones, *args)
    want_tokens, want_cache, want_keys, want_stats = _decode(fns, params, cache, rows)
    got_ones, got_tokens, got_cache, got_keys = fns["chunk_mid"](
        params, ones, *args, dict(cache), dict(rows, live=jnp.asarray(live)))
    for got, want in zip(got_ones, want_ones):
        assert set(got) == set(want)
        for name in want:
            if name != "moe_stats":
                np.testing.assert_allclose(got[name], want[name], **TOL)
    length = np.asarray(cache["length"])
    np.testing.assert_array_equal(got_cache["length"], length + live)
    _assert_slots_equal(got_cache, want_cache, cache, live, length)
    np.testing.assert_array_equal(
        got_tokens, np.where(live, np.asarray(want_tokens), np.asarray(rows["tokens"])))
    np.testing.assert_array_equal(
        got_keys, np.where(live[:, None], np.asarray(want_keys), np.asarray(rows["keys"])))
    if cfg.moe_experts:
        names = moe_stats_names(cfg)
        grew = dict(zip(names, np.asarray(got_ones[0]["moe_stats"] - ones[0]["moe_stats"])))
        alone = dict(zip(names, np.asarray(want_ones[0]["moe_stats"] - ones[0]["moe_stats"])))
        step = dict(zip(names, np.asarray(want_stats)))
        assert grew["layer_steps"] == alone["layer_steps"] == step["layer_steps"]
        if cfg.moe_experts_held:  # a block of sorted rows a layer run, carried rows or not
            assert grew["passes"] == grew["layer_steps"] and alone["passes"] == alone["layer_steps"]
        # every routed row counts, a row that is not live and a padded token
        # too; the last layer's feed-forward, where it is traced on its own,
        # is the decode rows' alone (nothing reads the chunk's rows behind it)
        pl = plan(cfg)
        own = pl.kinds[-1][2] == "sparse" and (pl.reps == 0 or pl.tail_from < cfg.n_layers)
        assert own  # both rehearsal patterns end in an expert layer of its own
        assert grew["assignments"] == alone["assignments"] + step["assignments"] - (
            n_stripes * CHUNK * cfg.moe_top_k)
        assert grew["experts_touched"] <= alone["experts_touched"] + step["experts_touched"]


def test_a_carrying_final_chunk_is_the_decode_step_and_the_chunk(pool):
    """``chunk_final`` with the pool's rows against ``decode_fn`` and then
    ``chunk_final`` into a slot that holds no request: the first token and its
    key, the slot's stripe, state and length, the other rows' next tokens,
    keys, keys and values. The slot the chunk activates is no decode row of
    its launch: its next token is the first token, its key the request's."""
    cfg, fns, params, cache, rows, prompt = pool
    one, = fns["chunk_mid"](params, (fns["new_stripe"](STRIPE),), *_chunk(prompt, 0))
    one, = fns["chunk_mid"](params, (one,), *_chunk(prompt, CHUNK))
    final = (*_chunk(prompt, 2 * CHUNK), jnp.int32(3), jnp.float32(0.8), jnp.int32(5),
             jax.random.PRNGKey(77))
    live = np.asarray([True, True, True, False])
    step_tokens, step_cache, step_keys, _ = _decode(fns, params, cache, rows)
    want_tok, want_key, want_cache, _, want_stats = fns["chunk_final"](
        params, dict(step_cache), dict(one), *final)
    got_tok, got_key, got_cache, _, got_stats, got_tokens, got_keys = fns["chunk_final"](
        params, dict(cache), dict(one), *final, dict(rows, live=jnp.asarray(live)))
    assert int(got_tok) == int(want_tok)
    np.testing.assert_array_equal(got_key, want_key)
    length = np.asarray(cache["length"])
    np.testing.assert_array_equal(got_cache["length"], [*(length[:3] + 1), len(prompt)])
    np.testing.assert_array_equal(got_cache["length"], want_cache["length"])
    for name in ("k", "v", *(n for n in STATE_LEAVES if n in want_cache)):
        np.testing.assert_allclose(got_cache[name][:, 3], want_cache[name][:, 3], **TOL)
    _assert_slots_equal(got_cache, step_cache, cache, live, length, slots=range(3))
    np.testing.assert_array_equal(got_tokens, [*np.asarray(step_tokens)[:3], int(want_tok)])
    np.testing.assert_array_equal(got_keys[:3], step_keys[:3])
    np.testing.assert_array_equal(got_keys[3], want_key)
    if cfg.moe_experts:
        assert got_stats.shape == want_stats.shape == (2, len(moe_stats_names(cfg)))
        np.testing.assert_array_equal(got_stats[0], want_stats[0])  # the middle chunks'
        assert int(got_stats[1, 1]) == int(want_stats[1, 1]) + SLOTS * cfg.moe_top_k * (
            int(want_stats[1, 0]))


def test_a_launch_that_carries_none_leaves_the_pool_as_it_was(pool):
    """No live row: the pool's cache, state, lengths, tokens and keys come
    out to the bit as they went in, and the stripe is the chunk's alone."""
    cfg, fns, params, cache, rows, prompt = pool
    one = fns["new_stripe"](STRIPE)
    want, = fns["chunk_mid"](params, (one,), *_chunk(prompt, 0))
    (got,), tokens, after, keys = fns["chunk_mid"](
        params, (one,), *_chunk(prompt, 0), dict(cache),
        dict(rows, live=jnp.zeros((SLOTS,), bool)))
    for name in cache:
        np.testing.assert_array_equal(after[name], cache[name])
    np.testing.assert_array_equal(tokens, rows["tokens"])
    np.testing.assert_array_equal(keys, rows["keys"])
    for name in ("k", "v", "length", *(n for n in STATE_LEAVES if n in want)):
        np.testing.assert_allclose(got[name], want[name], **TOL)
