"""Delta-rule linear attention layers that keep a state a head and no keys,
attention with an output gate a channel, and experts of which a device holds
a share (``models/patterned.py`` layer kind ``kda``, ``attn_gate`` 'channel';
upstage Solar-Open2 at test size, ``LlamaConfig.solar_tiny``): the path
through the cache against the benchmark's plain reference (a token-by-token
recurrence), the chunked form against the one-token step, the fused step
against the plain line, a prompt in chunks and in rows of one launch against
the prompt whole, a padded row against the row alone, the shares of an expert
layer and of the head against the whole, and the other families' programs as
the parent traced them. The engine's slots, counters and refusals:
``tests/test_kda_engine.py``."""

import collections
import dataclasses
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import programs
from ray_tpu.models import patterned
from ray_tpu.models.llama import (
    LlamaConfig,
    decode_step,
    forward,
    init_kv_cache,
    init_params,
    prefill,
)
from ray_tpu.models.patterned import STATE_LEAVES, _param_shapes, state_cache_shapes
from ray_tpu.ops import kda
from ray_tpu.ops.kda import kda_scan, kda_step, kda_step_in_place
from tests import held_experts

# the cut the cell serves, at test size: three delta-rule layers and the
# attention layer behind them, 4 of the router's 16 experts held
CFG = LlamaConfig.solar_tiny(n_layers=4, gqa_layers=(3,))
STATE = tuple(state_cache_shapes(CFG, 1))
# what benchmark/families/kda_moe.py reads, for the reference
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
                           "num_kv_heads": None},
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4, "head_dim": 16,
    "num_key_value_heads": 2, "vocab_size": 256, "intermediate_size": 128,
    "moe_intermediate_size": 32, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 128, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": [3], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 4, "published": {"n_routed_experts": 16},
}
T = 44
TOL = dict(atol=5e-5, rtol=1e-4)


@pytest.fixture(scope="module")
def model():
    """(the benchmark's seeded params, tokens [2, T], the reference's logits
    [2, T, V] and keys and values of the attention layer)."""
    from benchmark.families import kda_moe as family

    params = family.make_params(3, PUBLISHED, jnp.float32)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, CFG.vocab_size))
    ref = family.Reference(PUBLISHED, jax.local_devices()[:1])
    want = ref.forward_rows(params, list(tokens), last=T, kv_rows=range(2))
    return params, tokens, np.stack(want["logits"]), want["kv"]


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


def _kda_inputs(T, b=2, H=3, K=16, V=16, seed=0, rate=1.0, beta_shift=0.0):
    """Operands of the rule: unit keys, queries times K ** -0.5, log-decays
    log-uniform down to ``-rate`` a token, writing strengths 2 sigmoid(. +
    ``beta_shift``), from a state that is not zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (b, T, H, K)) for key in ks[:2])
    q, k = (t / jnp.linalg.norm(t, axis=-1, keepdims=True) for t in (q, k))
    v = jax.random.normal(ks[2], (b, T, H, V))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, T, H, K), minval=-6.0, maxval=np.log(rate)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, H)) + beta_shift)
    return jax.random.normal(ks[5], (b, H, K, V)), q * K ** -0.5, k, v, g, beta


def _token_by_token(state, q, k, v, g, beta):
    os = []
    for t in range(q.shape[1]):
        o, state = kda_step(state, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        os.append(o)
    return jnp.stack(os, axis=1), state


@pytest.mark.parametrize("T,chunk,rate,beta_shift", [
    (1, 8, 1.0, 0.0), (7, 8, 1.0, 0.0), (8, 8, 1.0, 0.0), (16, 8, 1.0, 0.0), (21, 8, 1.0, 0.0),
    (40, 8, 1.0, 0.0), (64, 64, 1.0, 0.0), (100, 64, 1.0, 0.0),
    # the strongest seeded decay (A 16 at a step of 0.1: 1.6 a token), and far
    # past it, where exp(-G) alone overflows inside a chunk and inside a sub-block
    (100, 64, 1.6, 0.0), (100, 64, 40.0, 0.0),
    # writing strengths near 2 (eigenvalues of I - beta k k^T near -1)
    (100, 64, 1.6, 5.0), (21, 8, 1.0, 5.0),
], ids=lambda x: str(x))
def test_the_chunked_form_equals_the_step_token_by_token(T, chunk, rate, beta_shift):
    """Lengths under, at, and over whole chunks, from a state that is not
    zero: the outputs and the state after the last token, in float32 to
    rounding whatever the decays are."""
    args = _kda_inputs(T, seed=T, rate=rate, beta_shift=beta_shift)
    want_o, want_s = _token_by_token(*args)
    got_o, got_s = jax.jit(lambda *a: kda_scan(*a, chunk))(*args)
    assert np.isfinite(np.asarray(got_o)).all() and np.isfinite(np.asarray(got_s)).all()
    np.testing.assert_allclose(got_o, want_o, atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(got_s, want_s, atol=5e-5, rtol=1e-4)


def test_the_solve_of_keys_that_are_alike_keeps_its_digits():
    """Keys nearly the same token after token with writing strengths near 2
    and no decay: ``I + A`` has entries near 2 below its diagonal, where the
    powers of a product form of its inverse would grow to 1e5 and cancel;
    forward substitution a block and the block merge keep the result to 1e-3
    over a whole chunk of 64."""
    state, q, k, v, g, beta = _kda_inputs(64, seed=5, beta_shift=5.0)
    k = k[:, :1] + 0.05 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    args = (state, q, k, v, g * 1e-3, beta)
    want_o, want_s = _token_by_token(*args)
    got_o, got_s = kda_scan(*args, 64)
    np.testing.assert_allclose(got_o, want_o, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(got_s, want_s, atol=1e-3, rtol=1e-3)


def test_a_token_that_is_none_leaves_the_state_and_adds_nothing():
    """How a right-padded row stops at its own length: tokens whose ``beta``
    and ``g`` are 0 behind 11 real ones change neither the state nor any real
    output."""
    state, q, k, v, g, beta = _kda_inputs(20)
    real = jnp.arange(20) < 11
    o_pad, s_pad = kda_scan(state, q, k, v, jnp.where(real[None, :, None, None], g, 0.0),
                            jnp.where(real[None, :, None], beta, 0.0), 8)
    o, s = kda_scan(state, q[:, :11], k[:, :11], v[:, :11], g[:, :11], beta[:, :11], 8)
    np.testing.assert_allclose(o_pad[:, :11], o, atol=1e-6)
    np.testing.assert_allclose(s_pad, s, atol=1e-6)


# a stacked leaf that tiles: 2 layers, 3 slots, 4 heads of [16, 128]
TILED = dict(b=3, H=4, K=16, V=128)


def _stacked(steps, seed=0):
    """``steps`` tokens' operands a slot and a stacked leaf of 2 rows."""
    _, q, k, v, g, beta = _kda_inputs(steps, seed=seed, **TILED)
    return (jax.random.normal(jax.random.PRNGKey(seed + 9), (2, 3, 4, 16, 128)), q, k, v, g, beta)


def _steps_in_place(leaf, layer, q, k, v, g, beta):
    """One ``kda_step_in_place`` a token on row ``layer`` (traced, as under a
    layer loop) -> (o [steps, b, H, V], the leaf)."""
    def steps(leaf, layer, *ops):
        def one(leaf, t):
            o, leaf = kda_step_in_place(leaf, layer, *(x[:, t] for x in ops))
            return leaf, o
        leaf, os = jax.lax.scan(one, leaf, jnp.arange(q.shape[1]))
        return os, leaf
    return jax.jit(steps)(leaf, jnp.int32(layer), q, k, v, g, beta)


@pytest.mark.parametrize("heads_a_tile", [4, 2, 1])
@pytest.mark.parametrize("steps", [1, 32])
@pytest.mark.parametrize("layer", [0, 1])
def test_the_fused_step_equals_the_plain_line_on_its_row_and_touches_no_other(
        layer, steps, heads_a_tile, monkeypatch):
    """The kernel (interpreted here) on row ``layer`` of a stacked leaf
    against ``kda_step`` on that row taken out: ``o`` and the new state to
    float32 rounding after 1 step and after 32, with a tile a slot, two and
    four; the leaf's other row bit for bit what it was."""
    monkeypatch.setattr(kda, "TILE_BYTES", heads_a_tile * 16 * 128 * 4)
    assert kda.step_heads(4, 16, 128) == heads_a_tile
    leaf, *ops = _stacked(steps)
    os, got = _steps_in_place(leaf, layer, *ops)
    want = leaf[layer]
    for t in range(steps):
        o, want = kda_step(want, *(x[:, t] for x in ops))
        np.testing.assert_allclose(os[t], o, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got[layer], want, atol=2e-5, rtol=1e-5)
    assert np.array_equal(got[1 - layer], leaf[1 - layer])


@pytest.mark.parametrize("layer", [0, 1])
def test_the_fused_step_keeps_a_row_that_has_no_token_bit_for_bit(layer):
    """A slot whose ``beta`` and ``g`` are 0 (a dead slot, a padded token):
    its state after the step is its state before, every bit, while its
    neighbours' move."""
    leaf, q, k, v, g, beta = _stacked(1, seed=3)
    g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
    os, got = _steps_in_place(leaf, layer, q, k, v, g, beta)
    assert np.array_equal(got[layer, 1], leaf[layer, 1])
    assert not np.array_equal(got[layer, 0], leaf[layer, 0])
    o, _ = kda_step(leaf[layer], q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    np.testing.assert_allclose(os[0], o, atol=2e-5, rtol=1e-5)


def test_a_state_that_does_not_tile_takes_the_plain_line():
    """The ``solar-tiny`` preset's 16 x 16 state a head is no whole lane tile:
    ``kda_step_in_place`` is then ``kda_step`` on the row taken out and put
    back, bit for bit, and no kernel is traced; the shape that tiles traces
    one."""
    assert kda.step_heads(4, 16, 16) is None  # V
    assert kda.step_heads(4, 4, 128) is None  # K
    assert kda.step_heads(64, 128, 128) == 16  # Solar-Open2's: 1 MB a tile
    state, q, k, v, g, beta = _kda_inputs(1)
    leaf = jnp.stack([state, state + 1])
    args = (leaf, jnp.int32(1), q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    o, got = kda_step_in_place(*args)
    want_o, want = kda_step(leaf[1], *args[2:])
    assert np.array_equal(o, want_o) and np.array_equal(got[1], want)
    assert np.array_equal(got[0], leaf[0])
    assert "name=kda_step" not in str(jax.make_jaxpr(kda_step_in_place)(*args))
    leaf, *ops = _stacked(1)
    assert "name=kda_step" in str(jax.make_jaxpr(kda_step_in_place)(
        leaf, jnp.int32(1), *(x[:, 0] for x in ops)))


def test_a_decode_step_through_the_kernel_equals_the_plain_line(monkeypatch):
    """The call site (``models/patterned.py _kda_mix`` at one token a row):
    the tiny preset with heads 128 wide, whose state tiles, a 12-token prompt
    and 4 decode steps; logits and the state leaf against the same with the
    kernel's selection switched off."""
    cfg = dataclasses.replace(CFG, kda_head_dim=128, kda_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)

    def run():
        logits, cache = prefill(params, init_kv_cache(cfg, 2, 64), tokens, cfg)
        step = jax.jit(lambda c, t: decode_step(params, c, t, cfg))  # traced anew
        assert ("name=kda_step" in str(jax.make_jaxpr(step)(cache, tokens[:, 0]))) == (
            kda.step_heads(2, 128, 128) is not None)
        out = []
        for _ in range(4):
            logits, cache = step(cache, jnp.argmax(logits, -1).astype(jnp.int32).reshape(2))
            out.append(logits)
        return jnp.stack(out), cache["kda_state"]

    got, got_state = run()
    monkeypatch.setattr(kda, "step_heads", lambda *a: None)
    want, want_state = run()
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5, rtol=1e-5)


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


def test_forward_refuses_mixers_that_run_through_the_cache_only():
    with pytest.raises(NotImplementedError, match="run through the cache only"):
        forward(init_params(jax.random.PRNGKey(0), CFG), jnp.zeros((1, 4), jnp.int32), CFG)


# --------------------------------------------------- the gate on attention


def _gate_case(form):
    """A laguna-tiny layer's attention output through ``_attn_out`` with the
    gate a head or a channel, and what the plain line gives."""
    cfg = LlamaConfig.laguna_tiny(attn_gate=form)
    pl = patterned.plan(cfg)
    lay = patterned._Layer(pl, 0, 0, 0, 0, (0, 0, 0))
    h_, hd, e = 6, cfg.head_dim, cfg.d_model
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x, h = jax.random.normal(ks[0], (2, 5, e)), jax.random.normal(ks[1], (2, 5, e))
    attn = jax.random.normal(ks[2], (2, 5, h_, hd))
    shape = _param_shapes(cfg)["wg_full"]
    params = {"wg_full": jax.random.normal(ks[3], shape) * 0.3,
              "wo_full": jax.random.normal(ks[4], (shape[0], h_, hd, e)) * 0.1}
    got = patterned._attn_out(params, lay, x, h, attn, cfg)
    gate = jax.nn.sigmoid(h @ params["wg_full"][0])
    gate = gate.reshape(2, 5, h_, hd) if form == "channel" else gate[..., None]
    want = x + jnp.einsum("bthd,hde->bte", attn * gate, params["wo_full"][0])
    return shape, got, want


@pytest.mark.parametrize("form", [True, "head", "channel"])
def test_the_gate_a_head_and_the_gate_a_channel_are_forms_of_one_field(form):
    """``attn_gate``: True (Laguna's, as it was) or 'head' a value a head,
    ``wg`` [e, h]; 'channel' a value a channel of each head, ``wg``
    [e, h * hd]: the same leaf, scope and line, another width."""
    shape, got, want = _gate_case(form)
    assert shape == ((2, 64, 6 * 16) if form == "channel" else (2, 64, 6))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_a_gate_a_channel_whose_rows_are_equal_a_head_is_the_gate_a_head():
    """A channel gate whose 16 columns a head are that head's one column
    gives what the head gate gives."""
    cfg = LlamaConfig.laguna_tiny()
    lay = patterned._Layer(patterned.plan(cfg), 0, 0, 0, 0, (0, 0, 0))
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x, h = jax.random.normal(ks[0], (1, 3, 64)), jax.random.normal(ks[1], (1, 3, 64))
    attn = jax.random.normal(ks[2], (1, 3, 6, 16))
    wg, wo = jax.random.normal(ks[3], (2, 64, 6)), jax.random.normal(ks[4], (2, 6, 16, 64))
    a_head = patterned._attn_out({"wg_full": wg, "wo_full": wo}, lay, x, h, attn, cfg)
    a_channel = patterned._attn_out(
        {"wg_full": jnp.repeat(wg, 16, axis=-1), "wo_full": wo}, lay, x, h, attn,
        dataclasses.replace(cfg, attn_gate="channel"))
    np.testing.assert_allclose(a_channel, a_head, atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="unknown attn_gate"):
        LlamaConfig.laguna_tiny(attn_gate="token")


# ------------------------------------- a chunk's attention over a long stripe


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


# ------------------------------------------------- a device's share of a layer


@pytest.mark.parametrize("tokens", [12, 200], ids=["a-block-is-all", "a-block-is-a-third"])
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(tokens):
    """16 experts over 8 devices, 2 each. Each share routes over all 16 and
    computes its own experts' part; what the eight add to a token, with what
    every device computes alike counted once (the shared expert), is what the
    plain reference gives for the layer with all 16 experts. Every assignment
    falls on exactly one share. At 12 tokens a share's block of sorted rows is
    all 48 assignments, at 200 it is 256 of the 800."""
    from benchmark.reference_kda_moe import Reference

    assert patterned.held_block(tokens * CFG.moe_top_k, 2, 16) == {12: 48, 200: 256}[tokens]
    params = init_params(jax.random.PRNGKey(5), dataclasses.replace(CFG, moe_experts_held=0))
    assert params["moe_w_up"].shape[:2] == (4, 16)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, tokens, CFG.d_model))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + CFG.rms_eps)  # mlp_norm is ones
    row = 2
    shared = patterned._shared_expert(
        {k: params[k][row] for k in ("moe_shared_gate", "moe_shared_up", "moe_shared_down")}, h[0])
    total, held, made = shared, 0, None
    banks = ("moe_w_gate", "moe_w_up", "moe_w_down")
    for first in range(0, 16, 2):
        cfg = dataclasses.replace(CFG, moe_experts_held=2, moe_experts_first=first)
        share = {**params, **{k: params[k][:, first:first + 2] for k in banks}}
        y, stats = patterned._moe_decode_ffn(share, row, h, cfg)
        total = total + (y[0] - shared)
        counts = dict(zip(patterned.moe_stats_names(cfg), np.asarray(stats)))
        held, made = held + counts["assignments_held"], counts["assignments"]
        assert counts["experts_touched"] <= 2 and counts["passes"] == 1
    assert made == tokens * CFG.moe_top_k == held
    whole = Reference(dict(PUBLISHED, n_routed_experts=16), jax.local_devices()[:1])
    (after,), _ = whole._experts(params, row, [x])
    np.testing.assert_allclose(total, (after - x)[0], atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("fell", sorted(held_experts.HELD))
def test_a_share_works_through_what_fell_on_it_a_block_at_a_time(fell, monkeypatch):
    """4 of 32 experts held, 128 tokens of 4 choices: a block is 128 of the
    512 sorted rows. Whatever the router does (every assignment on the held
    experts: four blocks; none: the shared expert alone, counted as one
    block; a block's rows exactly, and one more: a second block for one row)
    the layer is what the form that works on all 512 rows gives, token for
    token within float32 rounding, nothing dropped, and the counts are what
    that form made of the same choices."""
    cfg = dataclasses.replace(CFG, moe_experts=32, moe_experts_first=8)
    held_experts.check_a_block_at_a_time(cfg, 128, 128, fell, monkeypatch, atol=2e-6)


def test_the_eight_slices_of_the_vocabulary_add_up_to_the_whole_head():
    """A sliced vocabulary is a smaller vocabulary: the logits over rows
    32 i .. 32 i + 31 of the head, slice by slice, are the whole head's."""
    params = init_params(jax.random.PRNGKey(5), CFG)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 1, CFG.d_model))
    whole = patterned._project_logits(x, params, CFG, None)
    parts = [
        patterned._project_logits(
            x, {**params, "unembed": params["unembed"][:, at:at + 32]},
            dataclasses.replace(CFG, vocab_size=32), None)
        for at in range(0, 256, 32)
    ]
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), whole, atol=1e-6)


def test_one_list_names_every_leaf_a_slot_holds_whatever_its_length():
    """``STATE_LEAVES`` is what the engine's pool, its chunk programs and
    ``init_kv_cache`` read: each family's cache holds ``k``, ``v``, ``length``
    and its own leaves of that list, and nothing else."""
    for cfg, want in ((LlamaConfig.tiny(), ()), (LlamaConfig.laguna_tiny(), ()),
                      (LlamaConfig.kanana_tiny(), ()),
                      (LlamaConfig.nemotron_tiny(), ("ssm_state", "ssm_conv")),
                      (CFG, ("kda_state", "kda_conv"))):
        cache = jax.eval_shape(lambda cfg=cfg: init_kv_cache(cfg, 2, 64))
        assert tuple(state_cache_shapes(cfg, 2)) == want
        assert set(cache) == {"k", "v", "length", *want} and set(want) <= set(STATE_LEAVES)
        for name, (shape, dtype) in state_cache_shapes(cfg, 2).items():
            assert cache[name].shape == shape and cache[name].dtype == dtype and shape[1] == 2


# ------------------------------------- the other families, as the parent had them

# the decode step of each other family's tiny preset as the parent commit
# (PR 41) lowered it: operations in all, a digest of their histogram by name,
# and its matrix products (``/root/scratch`` holds no copy of this: the numbers
# were taken from a checkout of the parent, with this file's ``_digest``)
_PARENT_DECODE = {
    "tiny": (2189, "72306fc03fc3", 12),
    "laguna_tiny": (11403, "582b0fc462ba", 73),
    "kanana_tiny": (5562, "8942df0a7722", 29),
    # PR 45's lowering (a block of the held assignments under one loop); the
    # parent's was (4404, "08738863b0fd", 53)
    "nemotron_tiny": (4667, "8438b9700414", 48),
}


def _digest(cfg):
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, 128))
    text = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg)).lower(
        params, cache, jax.ShapeDtypeStruct((2,), jnp.int32)).as_text()
    ops = dict(sorted(collections.Counter(
        re.findall(r"= \"?((?:stablehlo|func|chlo)\.[\w.]+)", text)).items()))
    return (sum(ops.values()), hashlib.sha1(json.dumps(ops).encode()).hexdigest()[:12],
            ops.get("stablehlo.dot_general"))


@pytest.mark.parametrize("preset", sorted(_PARENT_DECODE))
def test_the_other_families_decode_programs_are_what_the_parent_traced(preset):
    """No operation more, fewer or other in the decode step of a dense GQA
    decoder, a window/full expert model, a latent-attention expert model and a
    state-space hybrid than before the delta-rule kind came in, and nothing
    of its leaves in their trees."""
    cfg = getattr(LlamaConfig, preset)()
    assert _digest(cfg) == _PARENT_DECODE[preset]
    assert not [k for k in _param_shapes(cfg) if k.startswith("kda_")]


def test_pattern_errors_are_named():
    with pytest.raises(ValueError, match="kda layers need"):
        patterned.plan(dataclasses.replace(CFG, kda_heads=0))
    with pytest.raises(ValueError, match="kda layers need"):
        patterned.plan(dataclasses.replace(CFG, kda_chunk=6))
    with pytest.raises(ValueError, match="unknown layer kind"):
        patterned.plan(dataclasses.replace(CFG, layer_types=("gla",) * 4))
