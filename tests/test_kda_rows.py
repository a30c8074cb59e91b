"""A delta-rule model's prompt in chunks and in rows of one launch against the
prompt whole, and a chunk over a long stripe that walks key blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import programs
from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, init_kv_cache, init_params, prefill
from tests.kda_models import CFG, STATE, model


@pytest.mark.parametrize("rows", [1, 2])
def test_a_prompt_in_chunks_of_a_multi_row_launch_equals_the_prompt_whole(model, rows):
    """The engine's own ``chunk_mid`` and ``chunk_final`` bodies: prompts of
    29 and 23 tokens go in as 8-token middle chunks, ``rows`` stripes a
    launch (stacked, run and handed back a row each: state and convolution
    tails with the keys and values), the shorter's last middle chunk beside
    the longer's (3 and 2 of them), then a final chunk of width 8 each into a
    pool of 3 slots; the slots' leaves and first tokens against each prompt
    whole through ``prefill``."""
    params, tokens, _, _ = model
    fns = {name: jax.jit(fn) for name, fn in programs(CFG).items() if name.startswith("chunk")}
    fns["new_stripe"] = programs(CFG)["new_stripe"]
    whole_prompt = jax.jit(lambda p, c, t: prefill(p, c, t, CFG))
    lens = (29, 23)
    ones = [fns["new_stripe"](64) for _ in lens]
    done = [0, 0]
    while any(n - d > 8 for n, d in zip(lens, done)):
        due = [b for b, n in enumerate(lens) if n - done[b] > 8]
        for group in ([due] if rows == 2 else [[b] for b in due]):
            out = fns["chunk_mid"](
                params, tuple(ones[b] for b in group),
                jnp.asarray(np.stack([tokens[b, done[b]:done[b] + 8] for b in group])),
                jnp.full((len(group),), 8, jnp.int32),
                jnp.asarray([done[b] for b in group], jnp.int32))
            for b, one in zip(group, out):
                ones[b], done[b] = one, done[b] + 8
    cache = init_kv_cache(CFG, 3, 64)
    # a tenant's leftovers in every slot: the final chunk must overwrite them
    cache = {k: (v + 1 if k in STATE else v) for k, v in cache.items()}
    first = []
    for b, n in enumerate(lens):
        tail = np.zeros((1, 8), np.int32)
        tail[0, :n - done[b]] = tokens[b, done[b]:n]
        tok, _, cache, _, stats = fns["chunk_final"](
            params, cache, ones[b], jnp.asarray(tail), jnp.asarray([n - done[b]], jnp.int32),
            jnp.asarray([done[b]], jnp.int32), jnp.int32(2 - b), jnp.float32(0.0), jnp.int32(1),
            jax.random.PRNGKey(0))
        first.append(int(tok))
        assert stats.shape == (2, 6)  # chunk_mid's and chunk_final's counts, the held ones and the blocks too
    for b, n in enumerate(lens):
        slot = 2 - b
        logits, whole = whole_prompt(params, init_kv_cache(CFG, 1, 64), jnp.asarray(tokens[b:b + 1, :n]))
        assert first[b] == int(jnp.argmax(logits[0]))
        assert int(cache["length"][slot]) == n
        for name in STATE:
            np.testing.assert_allclose(cache[name][:, slot], whole[name][:, 0], atol=1e-5)
        for name in ("k", "v"):
            np.testing.assert_allclose(cache[name][:, slot, :, :n], whole[name][:, 0, :, :n], atol=1e-5)


@pytest.mark.parametrize("preset", ["tiny", "solar_tiny"])
def test_a_chunk_over_a_long_stripe_walks_key_blocks_to_the_same_logits(preset, monkeypatch):
    """``_cache_reader`` at ``T > 1``: where the scores over the whole stripe
    would pass ``_STRIPE_SCORES_MAX_BYTES`` a full layer walks the stripe in
    blocks of 512 key positions up to the furthest row's last query; two
    chunks of ragged rows over a 2,048-position stripe against the same with
    the whole stripe scored at once."""
    cfg = getattr(LlamaConfig, preset)()
    params = init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)

    def run():
        chunk = jax.jit(lambda p, c, t, n, s: prefill(p, c, t, cfg, lengths=n, start_pos=s))  # traced anew
        first, cache = chunk(params, init_kv_cache(cfg, 2, 2048), tok[:, :24], jnp.array([24, 20]),
                             jnp.array([0, 0]))
        second, cache = chunk(params, cache, tok[:, 24:], jnp.array([16, 9]), jnp.array([24, 20]))
        return first, second, cache["k"]

    whole = run()
    monkeypatch.setattr(patterned, "_STRIPE_SCORES_MAX_BYTES", 0)
    for got, want in zip(run(), whole):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_the_accepted_cells_chunks_score_their_stripes_whole():
    """The widest launch of each accepted serving cell stays under the bound
    (its programs are what they were); this cell's 1,024-token chunks pass it
    at one row already."""
    bound = patterned._STRIPE_SCORES_MAX_BYTES
    rows_heads_tokens_stripe = {"mistral": (4, 32, 256, 1024), "laguna": (4, 48, 256, 4096),
                                "nemotron": (4, 32, 1024, 2048)}
    for b, h, t, s in rows_heads_tokens_stripe.values():
        assert b * h * t * s * 4 <= bound
    assert 1 * 64 * 1024 * 8192 * 4 > bound
