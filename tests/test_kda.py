"""Delta-rule linear-attention layers through the cache (``models/patterned.py``
layer kind ``kda``; Solar Open2 at test size): prefill and decode against the
benchmark's plain reference, the published order under one loop, a padded
row against the row alone, and the family's published keys and depth. The
rule's forms, its step as a kernel, rows of one launch and the shares a device
holds: ``tests/test_kda_forms.py``, ``test_kda_step.py``, ``test_kda_rows.py``,
``test_kda_shares.py`` (one file until PR 47, cut so that xdist's workers can
share it); the engine: ``tests/test_kda_engine.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, decode_step, init_kv_cache, init_params, prefill
from ray_tpu.models.patterned import STATE_LEAVES, _param_shapes, state_cache_shapes
from tests.kda_models import CFG, PUBLISHED, STATE, T, TOL, model


def _through_the_cache(params, tokens, chunks, cfg=CFG, stripe=64):
    """Logits of the last chunk's last token and of every decode step behind
    it, and the cache: the first ``sum(chunks)`` tokens go in as ``chunks``,
    the rest a token at a time."""
    B = tokens.shape[0]
    pre = jax.jit(lambda p, c, t, s: prefill(p, c, t, cfg, start_pos=s))
    dec = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))
    cache = init_kv_cache(cfg, B, stripe)
    at = 0
    for n in chunks:
        logits, cache = pre(params, cache, jnp.asarray(tokens[:, at:at + n]),
                            jnp.full((B,), at, jnp.int32))
        at += n
    got = [logits]
    for i in range(at, tokens.shape[1] - 1):
        logits, cache = dec(params, cache, jnp.asarray(tokens[:, i]))
        got.append(logits)
    return np.stack(got, axis=1), cache


def test_the_family_maps_the_published_keys_onto_the_tiny_preset():
    from benchmark.families import kda_moe as family

    assert LlamaConfig.solar_tiny(**family.model_kwargs(PUBLISHED)) == CFG
    assert {k: s for k, (s, _) in family.param_shapes(PUBLISHED).items()} == _param_shapes(CFG)


def test_published_depth_counts_its_parameters_and_the_cut_its_cache():
    """48 layers of Solar-Open2: 250.29 B parameters (the published "250B");
    the cut of layers 1-4 with 40 of 320 experts and an eighth of the
    vocabulary 3,308 M, as ISSUE 42 reckons them; the cache holds keys and
    values of the one attention layer, and a state and the convolutions'
    tails a slot for each of the three delta-rule layers."""
    whole = LlamaConfig.solar_open2_250b()
    pl = patterned.plan(whole)
    assert (pl.n_kda, pl.n_attention, pl.n_ffn, pl.lead, pl.period, pl.reps) == (36, 12, 48, 0, 4, 12)
    assert whole.num_params() == 250_287_810_304
    cut = LlamaConfig.solar_open2_250b(
        n_layers=4, gqa_layers=(3,), moe_experts_held=40, vocab_size=24576, max_seq_len=8192)
    assert cut.num_params() == 3_308_353_344
    pl = patterned.plan(cut)
    assert (pl.n_kda, pl.n_attention, pl.n_ffn, pl.n_mixer, pl.whole, pl.bodies) == (
        3, 1, 4, 4, False, 4)
    shapes = _param_shapes(cut)
    assert shapes["kda_w_in"] == (3, 4096, 3 * 8192 + 2 * 128 + 64)
    assert shapes["wg_full"] == (1, 4096, 64 * 128) and shapes["moe_w_up"] == (4, 40, 4096, 1280)
    cache = jax.eval_shape(lambda: init_kv_cache(cut, 64, 8192))
    assert cache["k"].shape == cache["v"].shape == (1, 64, 8, 8192, 128)
    assert cache["kda_state"].shape == (3, 64, 64, 128, 128) and cache["kda_state"].dtype == jnp.float32
    assert cache["kda_conv"].shape == (3, 64, 3, 24576)
    assert set(cache) - {"k", "v", "length"} == set(state_cache_shapes(cut, 1)) <= set(STATE_LEAVES)
    per_slot = sum(cache[k].size * cache[k].dtype.itemsize for k in state_cache_shapes(cut, 1)) // 64
    assert per_slot == 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2) == 13_025_280


@pytest.mark.parametrize("chunks", [(30,), (16, 14), (5, 16, 9), (8, 8, 8, 6)],
                         ids=["one-chunk", "at-a-rule-chunk", "three-chunks", "four-chunks"])
def test_prefill_then_decode_equals_the_reference(model, chunks):
    """Logits, not tokens, and the attention layer's keys and values, which
    lie behind all three delta-rule layers and three expert layers: the
    prompt's 30 tokens go in as ``chunks`` (the rule's own chunk is 8: widths
    that are and are not multiples of it, state and convolution tails carried
    from one to the next), the rest a token at a time, against the
    reference's token-by-token recurrence over the whole row."""
    params, tokens, want, want_kv = model
    got, cache = _through_the_cache(params, tokens, chunks)
    np.testing.assert_allclose(got, want[:, 29:T - 1], **TOL)
    for b in range(2):
        for name, ref_kv in zip(("k", "v"), want_kv[b]):  # [1, T, KV, D]
            have = np.asarray(cache[name][:, b, :, :T - 1]).transpose(0, 2, 1, 3)
            np.testing.assert_allclose(have, ref_kv[:, :T - 1], atol=2e-5, rtol=1e-4)


def test_the_published_order_runs_its_period_under_one_loop(model):
    """``solar-tiny`` whole (attention, then three delta-rule layers, twice):
    the period is one loop body whose delta-rule rows are traced indices into
    the stacked leaves; chunks and steps against one chunk."""
    cfg = LlamaConfig.solar_tiny()
    pl = patterned.plan(cfg)
    assert (pl.lead, pl.period, pl.reps, pl.n_kda, pl.n_attention) == (0, 4, 2, 6, 2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = model[1][:, :30]
    whole, cache_whole = _through_the_cache(params, tokens, (29,), cfg)
    parts, cache_parts = _through_the_cache(params, tokens, (8, 5), cfg)
    np.testing.assert_allclose(parts[:, -1], whole[:, -1], **TOL)
    for name in STATE:
        np.testing.assert_allclose(cache_parts[name], cache_whole[name], atol=1e-4)


def test_a_padded_rows_state_is_the_rows_own(model):
    """Two prompts of 30 and 19 tokens in one right-padded ``prefill`` of
    width 32: each row's state, convolution tails, keys, values and
    next-token logits are what the row alone, unpadded, gives."""
    params, tokens, _, _ = model
    lens = (30, 19)
    padded = np.zeros((2, 32), np.int32)
    for b, n in enumerate(lens):
        padded[b, :n] = tokens[b, :n]
    logits, cache = jax.jit(lambda p, c, t, n: prefill(p, c, t, CFG, lengths=n))(
        params, init_kv_cache(CFG, 2, 64), jnp.asarray(padded), jnp.asarray(lens, jnp.int32))
    for b, n in enumerate(lens):
        alone_logits, alone = jax.jit(lambda p, c, t: prefill(p, c, t, CFG))(
            params, init_kv_cache(CFG, 1, 64), jnp.asarray(tokens[b:b + 1, :n]))
        np.testing.assert_allclose(logits[b], alone_logits[0], **TOL)
        for name in STATE:
            np.testing.assert_allclose(cache[name][:, b], alone[name][:, 0], atol=1e-5)
        for name in ("k", "v"):
            np.testing.assert_allclose(cache[name][:, b, :, :n], alone[name][:, 0, :, :n], atol=1e-5)
