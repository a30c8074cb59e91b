"""Blocks that are a mixer or a feed-forward alone, state-space mixers, and
experts in a latent of which a device holds a share (``models/patterned.py``
layer kinds ``ssm`` and ``none``, ``moe_latent_dim``, ``moe_experts_held``;
NVIDIA Nemotron-3-Super at test size, ``LlamaConfig.nemotron_tiny``): the
path through the cache against the benchmark's plain reference (a
token-by-token recurrence), the chunked scan against the one-token step, a
prompt in chunks and in rows of one launch against the prompt whole, a padded
row against the row alone, the shares of an expert layer and of the head
against the whole, the engine's slots, counters and refusals."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
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
from ray_tpu.models.patterned import _param_shapes, state_cache_shapes
from ray_tpu.ops import ssm
from ray_tpu.ops.ssm import causal_conv, ssm_scan, ssm_step, ssm_step_in_place
from tests import held_experts

CFG = LlamaConfig.nemotron_tiny()
# the leaves a slot of this model holds whatever its length
STATE = tuple(state_cache_shapes(CFG, 1))
# what benchmark/families/ssm_latent_moe.py reads, for the reference: the
# configuration holds 4 of the router's 16 experts
PUBLISHED = {
    "attention_bias": False, "chunk_size": 8, "conv_kernel": 4, "expand": 2, "head_dim": 16,
    "hidden_size": 64, "hybrid_override_pattern": "MEMEMEM*EME", "intermediate_size": 48,
    "mamba_head_dim": 16, "mamba_hidden_act": "silu", "mamba_num_heads": 8,
    "mamba_proj_bias": False, "max_position_embeddings": 128, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 48,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96, "n_group": 1,
    "n_groups": 2, "n_routed_experts": 4, "n_shared_experts": 1, "norm_eps": 1e-5,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts_per_tok": 6,
    "num_hidden_layers": 11, "num_key_value_heads": 2, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 16,
    "tie_word_embeddings": False, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True, "vocab_size": 256,
    "published": {"n_routed_experts": 16},
}
T = 44
TOL = dict(atol=5e-5, rtol=1e-4)


@pytest.fixture(scope="module")
def model():
    """(the benchmark's seeded params, tokens [2, T], the reference's logits
    [2, T, V] and keys and values of the attention block)."""
    from benchmark.families import ssm_latent_moe as family

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
    from benchmark.families import ssm_latent_moe as family

    assert LlamaConfig.nemotron_tiny(**family.model_kwargs(PUBLISHED)) == CFG
    assert {k: s for k, (s, _) in family.param_shapes(PUBLISHED).items()} == _param_shapes(CFG)


def test_published_depth_counts_its_parameters_and_the_cut_its_cache():
    """88 blocks of Nemotron-3-Super: 120.67 B parameters (the published
    "120B"); the cut of 11 blocks with 128 of 512 experts and a quarter of
    the vocabulary 4,648 M, as ISSUE 35 reckons them; the cache holds keys
    and values of the one attention block, and a state and a convolution tail
    a slot for each of the five state-space blocks."""
    assert LlamaConfig.nemotron3_super().num_params() == 120_668_707_840
    cut = LlamaConfig.nemotron3_super(n_layers=11, moe_experts_held=128, vocab_size=32768)
    assert cut.num_params() == 4_648_163_712
    pl = patterned.plan(cut)
    assert (pl.n_ssm, pl.n_attention, pl.n_ffn, pl.n_mixer, pl.whole) == (5, 1, 5, 6, False)
    cache = jax.eval_shape(lambda: init_kv_cache(cut, 64, 2048))
    assert cache["k"].shape == cache["v"].shape == (1, 64, 2, 2048, 128)
    assert cache["ssm_state"].shape == (5, 64, 128, 64, 128) and cache["ssm_state"].dtype == jnp.float32
    assert cache["ssm_conv"].shape == (5, 64, 3, 10240)
    per_slot = sum(cache[k].size * cache[k].dtype.itemsize for k in STATE) // 64
    assert per_slot == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2) == 21_278_720


@pytest.mark.parametrize("chunks", [(30,), (16, 14), (5, 16, 9), (8, 8, 8, 6)],
                         ids=["one-chunk", "at-a-scan-chunk", "three-chunks", "four-chunks"])
def test_prefill_then_decode_equals_the_reference(model, chunks):
    """Logits, not tokens, and the attention block's keys and values, which
    lie behind four of the five state-space blocks and three of the five
    expert blocks: the prompt's 30 tokens go in as ``chunks`` (the scan's own
    chunk is 8: widths that are and are not multiples of it, state and
    convolution tail carried from one to the next), the rest a token at a
    time, against the reference's token-by-token recurrence over the whole
    row."""
    params, tokens, want, want_kv = model
    got, cache = _through_the_cache(params, tokens, chunks)
    np.testing.assert_allclose(got, want[:, 29:T - 1], **TOL)
    for b in range(2):
        for name, ref_kv in zip(("k", "v"), want_kv[b]):  # [1, T, KV, D]
            have = np.asarray(cache[name][:, b, :, :T - 1]).transpose(0, 2, 1, 3)
            np.testing.assert_allclose(have, ref_kv[:, :T - 1], atol=2e-5, rtol=1e-4)


def _ssm_inputs(T, b=2, H=8, P=16, N=16, G=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, H)) - 3.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    B, C = jax.random.normal(ks[3], (b, T, G, N)), jax.random.normal(ks[4], (b, T, G, N))
    state = jax.random.normal(ks[5], (b, H, P, N))
    return state, x, dt, a, B, C, jnp.ones((H,))


@pytest.mark.parametrize("T", [1, 7, 8, 16, 21, 40])
def test_the_chunked_scan_equals_the_step_token_by_token(T):
    """Lengths under, at, and over whole chunks of 8, from a state that is not
    zero: the outputs and the state after the last token."""
    state, x, dt, a, B, C, D = _ssm_inputs(T)
    ys, s = [], state
    for t in range(T):
        y, s = ssm_step(s, x[:, t], dt[:, t], a, B[:, t], C[:, t], D)
        ys.append(y)
    got_y, got_s = ssm_scan(state, x, dt, a, B, C, D, chunk=8)
    np.testing.assert_allclose(got_y, jnp.stack(ys, axis=1), atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got_s, s, atol=2e-5, rtol=1e-5)


def test_a_step_of_zero_leaves_the_state_and_adds_nothing():
    """How a right-padded row stops at its own length: tokens whose ``dt`` is
    0 behind 11 real ones change neither the state nor any real output."""
    state, x, dt, a, B, C, D = _ssm_inputs(20)
    real = jnp.arange(20) < 11
    y_pad, s_pad = ssm_scan(state, x, jnp.where(real[None, :, None], dt, 0.0), a, B, C, D, chunk=8)
    y, s = ssm_scan(state, x[:, :11], dt[:, :11], a, B[:, :11], C[:, :11], D, chunk=8)
    np.testing.assert_allclose(y_pad[:, :11], y, atol=1e-6)
    np.testing.assert_allclose(s_pad, s, atol=1e-6)


# a stacked leaf that tiles: 2 layers, 3 slots, 16 heads of [8, 128] in 2 groups
TILED = dict(b=3, H=16, P=8, N=128, G=2)


def _stacked(steps, seed=0):
    """``steps`` tokens' operands a slot and a stacked leaf of 2 rows."""
    _, x, dt, a, B, C, D = _ssm_inputs(steps, seed=seed, **TILED)
    leaf = jax.random.normal(jax.random.PRNGKey(seed + 9), (2, 3, 16, 8, 128))
    return leaf, x, dt, a, B, C, D * 0.5


def _steps_in_place(leaf, layer, x, dt, a, B, C, D):
    """One ``ssm_step_in_place`` a token on row ``layer`` (traced, as under
    the layer loop) -> (y [steps, b, H, P], the leaf), jitted as a function
    of its own each call: the tile is read when it is traced."""
    def steps(leaf, layer, x, dt, a, B, C, D):
        def one(leaf, t):
            y, leaf = ssm_step_in_place(leaf, layer, x[:, t], dt[:, t], a, B[:, t], C[:, t], D)
            return leaf, y
        leaf, ys = jax.lax.scan(one, leaf, jnp.arange(x.shape[1]))
        return ys, leaf
    return jax.jit(steps)(leaf, jnp.int32(layer), x, dt, a, B, C, D)


@pytest.mark.parametrize("groups_a_tile", [2, 1], ids=["a-slot-a-tile", "a-group-a-tile"])
@pytest.mark.parametrize("steps", [1, 32])
@pytest.mark.parametrize("layer", [0, 1])
def test_the_fused_step_equals_the_plain_line_on_its_row_and_touches_no_other(
        layer, steps, groups_a_tile, monkeypatch):
    """The kernel (interpreted here) on row ``layer`` of a stacked leaf
    against ``ssm_step`` on that row taken out: ``y`` and the new state to
    float32 rounding after 1 step and after 32, with a tile a slot and with
    two (a group of heads each); the leaf's other row bit for bit what it
    was."""
    monkeypatch.setattr(ssm, "TILE_BYTES", groups_a_tile * 8 * 8 * 128 * 4)
    assert ssm.step_groups(16, 8, 128, 2) == groups_a_tile
    leaf, x, dt, a, B, C, D = _stacked(steps)
    ys, got = _steps_in_place(leaf, layer, x, dt, a, B, C, D)
    want = leaf[layer]
    for t in range(steps):
        y, want = ssm_step(want, x[:, t], dt[:, t], a, B[:, t], C[:, t], D)
        np.testing.assert_allclose(ys[t], y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got[layer], want, atol=2e-5, rtol=1e-5)
    assert np.array_equal(got[1 - layer], leaf[1 - layer])


@pytest.mark.parametrize("layer", [0, 1])
def test_the_fused_step_keeps_a_row_whose_step_is_zero_bit_for_bit(layer):
    """A slot whose ``dt`` is 0 (a dead slot, a padded token): its state
    after the step is its state before, every bit, while its neighbours'
    move; from a state of zeros its ``y`` is ``D x`` and nothing else."""
    leaf, x, dt, a, B, C, D = _stacked(1, seed=3)
    dt = dt.at[1].set(0.0)
    ys, got = _steps_in_place(leaf, layer, x, dt, a, B, C, D)
    assert np.array_equal(got[layer, 1], leaf[layer, 1])
    assert not np.array_equal(got[layer, 0], leaf[layer, 0])
    y, _ = ssm_step(leaf[layer], x[:, 0], dt[:, 0], a, B[:, 0], C[:, 0], D)
    np.testing.assert_allclose(ys[0], y, atol=2e-5, rtol=1e-5)
    ys, got = _steps_in_place(leaf.at[layer, 1].set(0.0), layer, x, dt, a, B, C, D)
    assert np.array_equal(ys[0, 1], D[:, None] * x[1, 0])
    assert not np.asarray(got[layer, 1]).any()


def test_a_state_that_does_not_tile_takes_the_plain_line():
    """The ``nemotron-tiny`` preset's 16 x 16 state a head is no whole lane
    tile: ``ssm_step_in_place`` is then ``ssm_step`` on the row taken out and
    put back, bit for bit, and no kernel is traced; the shape that tiles
    traces one."""
    assert ssm.step_groups(8, 16, 16, 2) is None  # N
    assert ssm.step_groups(16, 4, 128, 2) is None  # P
    assert ssm.step_groups(16, 8, 128, 3) is None  # heads in no whole groups
    assert ssm.step_groups(128, 64, 128, 8) is not None  # Nemotron-3-Super's
    state, x, dt, a, B, C, D = _ssm_inputs(1)
    leaf = jnp.stack([state, state + 1])
    args = (leaf, jnp.int32(1), x[:, 0], dt[:, 0], a, B[:, 0], C[:, 0], D)
    y, got = ssm_step_in_place(*args)
    want_y, want = ssm_step(leaf[1], *args[2:])
    assert np.array_equal(y, want_y) and np.array_equal(got[1], want)
    assert np.array_equal(got[0], leaf[0])
    assert "name=ssm_step" not in str(jax.make_jaxpr(ssm_step_in_place)(*args))
    leaf, x, dt, a, B, C, D = _stacked(1)
    assert "name=ssm_step" in str(jax.make_jaxpr(ssm_step_in_place)(
        leaf, jnp.int32(1), x[:, 0], dt[:, 0], a, B[:, 0], C[:, 0], D))


def test_a_decode_step_through_the_kernel_equals_the_plain_line(monkeypatch):
    """The call site (``models/patterned.py _ssm_mixer`` at one token a row,
    the layer's row as the layer loop hands it): the tiny preset with a state
    128 wide, which tiles, a 12-token prompt and 4 decode steps; logits and
    the state leaf against the same with the kernel's selection switched
    off."""
    cfg = dataclasses.replace(CFG, ssm_state=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)

    def run():
        logits, cache = prefill(params, init_kv_cache(cfg, 2, 64), tokens, cfg)
        step = jax.jit(lambda c, t: decode_step(params, c, t, cfg))  # traced anew
        assert ("name=ssm_step" in str(jax.make_jaxpr(step)(cache, tokens[:, 0]))) == (
            ssm.step_groups(8, 16, 128, 2) is not None)
        out = []
        for _ in range(4):
            logits, cache = step(cache, jnp.argmax(logits, -1).astype(jnp.int32).reshape(2))
            out.append(logits)
        return jnp.stack(out), cache["ssm_state"]

    got, got_state = run()
    monkeypatch.setattr(ssm, "step_groups", lambda *a: None)
    want, want_state = run()
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5, rtol=1e-5)


def test_the_convolution_reads_the_tail_in_front_of_its_tokens():
    tail = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 5))
    w, b = jax.random.normal(jax.random.PRNGKey(2), (4, 5)), jnp.arange(5.0)
    y, seen = causal_conv(tail, x, w, b)
    ext = np.concatenate([tail, x], axis=1)
    want = np.stack([sum(np.asarray(w)[j] * ext[:, t + j] for j in range(4)) for t in range(6)], 1)
    np.testing.assert_allclose(y, want + np.asarray(b), atol=1e-6)
    np.testing.assert_allclose(seen, ext)


def test_a_padded_rows_state_is_the_rows_own(model):
    """Two prompts of 30 and 19 tokens in one right-padded ``prefill`` of
    width 32: each row's state, convolution tail, keys, values and
    next-token logits are what the row alone, unpadded, gives."""
    params, tokens, _, _ = model
    lens = (30, 19)
    padded = np.zeros((2, 32), np.int32)
    for b, n in enumerate(lens):
        padded[b, :n] = tokens[b, :n]
    logits, cache = prefill(params, init_kv_cache(CFG, 2, 64), jnp.asarray(padded), CFG,
                            lengths=jnp.asarray(lens, jnp.int32))
    for b, n in enumerate(lens):
        alone_logits, alone = prefill(params, init_kv_cache(CFG, 1, 64),
                                      jnp.asarray(tokens[b:b + 1, :n]), CFG)
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
    tail with the keys and values), the shorter's last middle chunk beside
    the longer's (3 and 2 of them), then a final chunk of width 8 each into a
    pool of 3 slots; the slots' leaves and first tokens against each prompt
    whole through ``prefill``."""
    params, tokens, _, _ = model
    fns = programs(CFG)
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
        logits, whole = prefill(params, init_kv_cache(CFG, 1, 64), jnp.asarray(tokens[b:b + 1, :n]), CFG)
        assert first[b] == int(jnp.argmax(logits[0]))
        assert int(cache["length"][slot]) == n
        for name in STATE:
            np.testing.assert_allclose(cache[name][:, slot], whole[name][:, 0], atol=1e-5)
        for name in ("k", "v"):
            np.testing.assert_allclose(cache[name][:, slot, :, :n], whole[name][:, 0, :, :n], atol=1e-5)


def test_a_leaf_too_large_to_draw_whole_is_drawn_a_row_at_a_time(monkeypatch):
    """``JaxEngine._build_model`` draws the model's own weights before a
    caller hands it others (the benchmark's replica does: ``init_params`` at
    the cut's full width, then the family's): a leaf past
    ``_DRAW_WHOLE_MAX_BYTES`` of float32 is drawn a row of its leading axis
    at a time, with the scale and shape of the whole draw."""
    from ray_tpu.models import llama

    whole = init_params(jax.random.PRNGKey(0), CFG)
    limit = whole["moe_w_up"].size * 4 - 1  # the expert banks pass it
    monkeypatch.setattr(llama, "_DRAW_WHOLE_MAX_BYTES", limit)
    rows = init_params(jax.random.PRNGKey(0), CFG)
    assert {k: (v.shape, v.dtype) for k, v in rows.items()} == {
        k: (v.shape, v.dtype) for k, v in whole.items()}
    drawn_by_row = [k for k in whole if not np.array_equal(rows[k], whole[k])]
    assert {"moe_w_up", "moe_w_down"} <= set(drawn_by_row)
    assert sorted(drawn_by_row) == sorted(
        k for k, v in whole.items()
        if v.size * 4 > limit and "norm" not in k and k not in llama._SSM_VECTORS)
    for name in drawn_by_row:
        got, want = np.asarray(rows[name]), np.asarray(whole[name])
        np.testing.assert_allclose(got.std(), want.std(), rtol=0.05)
        assert abs(got.mean()) < 0.05 * got.std()
        # every row its own draw
        assert not np.array_equal(got[0], got[1])


def test_forward_refuses_blocks_that_run_through_the_cache_only():
    with pytest.raises(NotImplementedError, match="run through the cache only"):
        forward(init_params(jax.random.PRNGKey(0), CFG), jnp.zeros((1, 4), jnp.int32), CFG)


# ------------------------------------------------- a device's share of a layer


@pytest.mark.parametrize("tokens", [12, 100], ids=["a-block-is-all", "a-block-is-two-thirds"])
def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(tokens):
    """16 experts over 4 devices, 4 each. Each share routes over all 16 and
    computes its own experts' part; what the four add to a token, with what
    every device computes alike counted once (the shared expert; the
    up-projection is linear, so it may be applied share by share), is what
    the plain reference gives for the layer with all 16 experts. Every
    assignment falls on exactly one share. At 12 tokens a share's block of
    sorted rows is all 72 assignments, at 100 it is 384 of the 600."""
    from benchmark.reference_ssm_latent_moe import Reference

    assert patterned.held_block(tokens * CFG.moe_top_k, 4, 16) == {12: 72, 100: 384}[tokens]
    params = init_params(jax.random.PRNGKey(5), dataclasses.replace(CFG, moe_experts_held=0))
    assert params["moe_w_up"].shape[:2] == (5, 16)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, tokens, CFG.d_model))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + CFG.rms_eps)  # mlp_norm is ones
    row = 2
    shared = patterned._shared_expert(
        {k: params[k][row] for k in ("moe_shared_up", "moe_shared_down")}, h[0])
    total, held, made = shared, 0, None
    for first in range(0, 16, 4):
        cfg = dataclasses.replace(CFG, moe_experts_first=first)
        assert cfg.moe_experts_held == 4
        share = {**params, **{k: params[k][:, first:first + 4] for k in ("moe_w_up", "moe_w_down")}}
        y, stats = patterned._moe_decode_ffn(share, row, h, cfg)
        total = total + (y[0] - shared)
        counts = dict(zip(patterned.moe_stats_names(cfg), np.asarray(stats)))
        held, made = held + counts["assignments_held"], counts["assignments"]
        assert counts["experts_touched"] <= 4 and counts["passes"] == 1
    assert made == tokens * CFG.moe_top_k == held
    whole = Reference(dict(PUBLISHED, n_routed_experts=16), jax.local_devices()[:1])
    (after,), _ = whole._experts(params, row, [x])
    np.testing.assert_allclose(total, (after - x)[0], atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("fell", sorted(held_experts.HELD))
def test_a_share_works_through_what_fell_on_it_a_block_at_a_time(fell, monkeypatch):
    """8 of 48 relu^2 experts held in the latent, 64 tokens of 6 choices: a
    block is 128 of the 384 sorted rows. Whatever the router does (every
    assignment on the held experts: three blocks; none: the shared expert
    alone, counted as one block; a block's rows exactly, and one more: a
    second block for one row) the layer is what the form that works on all
    384 rows gives, token for token within float32 rounding, nothing dropped,
    and the counts are what that form made of the same choices."""
    cfg = dataclasses.replace(CFG, moe_experts=48, moe_experts_held=8, moe_experts_first=16)
    held_experts.check_a_block_at_a_time(cfg, 64, 128, fell, monkeypatch, atol=1e-5)


def test_the_four_slices_of_the_vocabulary_add_up_to_the_whole_head():
    """A sliced vocabulary is a smaller vocabulary: the logits over rows
    64 i .. 64 i + 63 of the head, slice by slice, are the whole head's."""
    params = init_params(jax.random.PRNGKey(5), CFG)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 1, CFG.d_model))
    whole = patterned._project_logits(x, params, CFG, None)
    parts = [
        patterned._project_logits(
            x, {**params, "unembed": params["unembed"][:, at:at + 64]},
            dataclasses.replace(CFG, vocab_size=64), None)
        for at in range(0, 256, 64)
    ]
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), whole, atol=1e-6)


# ------------------------------------------------------------------ the engine


@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="nemotron-tiny"),
        engine=EngineConfig(max_num_seqs=3, max_seq_len=64, dtype="float32",
                            prefill_buckets=(8, 16, 32), prefill_chunk=8),
    ))
    yield eng
    eng.shutdown()


def _greedy_by_the_reference(engine, prompt, out):
    """The reference's greedy token at each position the engine sampled one,
    teacher-forced on the engine's own tokens."""
    from benchmark.reference_ssm_latent_moe import Reference

    ref = Reference(PUBLISHED, jax.local_devices()[:1])
    row = np.asarray(prompt + out[:-1], np.int32)
    logits = ref.forward_rows(engine.params, [row], last=len(out))["logits"][0]
    return np.argmax(logits, -1).tolist()


SP = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 256, n)]


def test_engine_answers_as_the_reference_and_a_reused_slot_as_a_fresh_one(engine):
    """A 29-token prompt (three middle chunks and a final one), another
    through the same slot, then the first again: the slot's second and third
    tenants see nothing of the state the one before left, each answer is the
    reference's greedy one, and the request sent twice answers alike. The
    prefix cache is on and a pool that keeps a state a slot stores a snapshot
    of each prompt: the same prompt again is no hit (a token must remain), a
    prompt that goes on from the first is seeded from it at its exact length
    and answers as the reference does."""
    before = engine.get_stats()["counters"]
    a, b = _prompt(0, 29), _prompt(1, 21)
    first = engine.generate(prompt_token_ids=a, sampling_params=SP)
    other = engine.generate(prompt_token_ids=b, sampling_params=SP)
    again = engine.generate(prompt_token_ids=a, sampling_params=SP)
    assert first.token_ids == again.token_ids
    assert first.token_ids == _greedy_by_the_reference(engine, a, first.token_ids)
    assert other.token_ids == _greedy_by_the_reference(engine, b, other.token_ids)
    assert again.metrics["prefix_hit_tokens"] == 0
    longer = a + _prompt(2, 12)
    onward = engine.generate(prompt_token_ids=longer, sampling_params=SP)
    assert onward.metrics["prefix_hit_tokens"] == 29
    assert onward.token_ids == _greedy_by_the_reference(engine, longer, onward.token_ids)
    stats = engine.get_stats()
    c = stats["counters"]
    assert c["snapshots_stored"] - before["snapshots_stored"] == 3
    assert c["snapshots_hit"] - before["snapshots_hit"] == 1
    assert stats["prefix_cache_entries"] == 3 and stats["prefix_cache_bytes"] > 0


def test_requests_admitted_together_answer_as_each_alone(engine):
    """Five prompts at once on three slots: their middle chunks run as rows
    of one launch where they are due together, decode steps batch them, and
    two wait for a slot another has left. Every answer is the reference's."""
    before = engine.get_stats()["counters"]
    prompts = [_prompt(10 + i, n) for i, n in enumerate((29, 27, 30, 12, 25))]
    reqs = [engine.submit(prompt_token_ids=p, sampling_params=SP) for p in prompts]
    for req in reqs:
        engine._await_done(req)
        assert req.error is None
    for p, req in zip(prompts, reqs):
        assert list(req.out_tokens) == _greedy_by_the_reference(engine, p, list(req.out_tokens))
    now = engine.get_stats()["counters"]
    rows = now["prefill_chunks"]["mid"] - before["prefill_chunks"]["mid"]
    launches = now["prefill_programs"]["mid"] - before["prefill_programs"]["mid"]
    assert rows == 3 + 3 + 3 + 1 + 3 and launches < rows


def test_engine_counts_the_state_a_slot_holds_and_the_assignments_held(engine):
    engine.generate(prompt_token_ids=_prompt(3, 20), sampling_params=SP)
    stats = engine.get_stats()
    (pool,) = stats["pools"]
    # 5 state-space blocks: a float32 state [8, 16, 16] and 3 inputs of 192 channels
    assert pool["state_bytes_per_slot"] == 5 * (8 * 16 * 16 * 4 + 3 * 192 * 4)
    # the tiny preset's 16 x 16 state tiles for no kernel; a chunk has the one form
    assert pool["state_mixer_forms"] == {"ssm": {"chunk": "plain", "step": "plain"}}
    # keys and values of the one attention block: 2 heads of 16, float32
    assert pool["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    c = stats["counters"]
    for program in ("decode", "chunk_mid", "chunk_final"):
        made, held = c["moe_assignments"][program], c["moe_assignments_held"][program]
        # every routed row makes 6 assignments, a launch's rows in each of its layers
        assert 0 < held < made and made % 6 == 0 and made >= 6 * c["moe_layer_steps"][program]
        # a block of sorted rows a layer run: the tiny sizes overflow none
        assert c["moe_passes"][program] == c["moe_layer_steps"][program] > 0
    # 4 of 16 experts held: about a quarter of what the router assigns
    assert 0.1 < sum(c["moe_assignments_held"].values()) / sum(c["moe_assignments"].values()) < 0.4


def test_a_model_whose_slots_are_stripes_alone_counts_no_state():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="laguna-tiny"),
        engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32",
                            prefill_buckets=(16, 32), prefill_chunk=16),
    ))
    try:
        first = eng.generate(prompt_token_ids=_prompt(0, 40), sampling_params=SP)
        again = eng.generate(prompt_token_ids=_prompt(0, 40), sampling_params=SP)
        stats = eng.get_stats()
    finally:
        eng.shutdown()
    assert first.token_ids == again.token_ids and again.metrics["prefix_hit_tokens"] == 32
    assert stats["pools"][0]["state_bytes_per_slot"] == 0
    assert stats["pools"][0]["state_mixer_forms"] == {}
    assert stats["counters"]["snapshots_stored"] == stats["counters"]["snapshots_hit"] == 0
    assert set(stats["counters"]["moe_assignments_held"].values()) == {0}
    assert sum(stats["counters"]["moe_assignments"].values()) > 0


@pytest.mark.parametrize("module", ["llm/spmd.py", "llm/gang.py", "llm/disagg.py",
                                    "tensor_parallel_degree"])
def test_the_paths_with_their_own_cache_programs_refuse_a_stateful_model_by_name(module):
    cfg = LLMConfig(model=ModelConfig(model_id="nemotron-tiny"),
                    engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32"))
    if module == "llm/spmd.py":
        from ray_tpu.llm.spmd import SPMDGenerator

        build = lambda: SPMDGenerator(cfg)  # noqa: E731
    elif module == "llm/gang.py":
        from ray_tpu.llm.gang import GangLLMServer

        build = lambda: GangLLMServer(cfg, num_workers=2)  # noqa: E731
    elif module == "llm/disagg.py":
        from ray_tpu.llm.disagg import DecodeWorker, PrefillWorker

        with pytest.raises(NotImplementedError, match=r"llm/disagg\.py.*state-space"):
            DecodeWorker(cfg)
        build = lambda: PrefillWorker(cfg)  # noqa: E731
    else:
        cfg.engine.tensor_parallel_degree = 2
        build = lambda: JaxEngine(cfg)  # noqa: E731
        module = "llm/engine.py over a mesh"
    with pytest.raises(NotImplementedError, match=module.replace(".", r"\.") + ".*state-space"):
        build()


# what the three served families' trees were before blocks could lack a mixer
# or a feed-forward (the parent commit's ``_param_shapes`` at the serving
# cells' depths): every stack still has a row a layer, or a row a layer of its kind
_SERVED_SHAPES = {
    "mistral-7b-serve-l16": (
        lambda: LlamaConfig(vocab_size=32768, d_model=4096, n_layers=16, n_heads=32, n_kv_heads=8,
                            d_ff=14336, max_seq_len=1024, rope_theta=1e6, dtype=jnp.bfloat16),
        {"attn_norm": (16, 4096), "embed": (32768, 4096), "final_norm": (4096,),
         "mlp_norm": (16, 4096), "unembed": (4096, 32768), "w_down": (16, 14336, 4096),
         "w_gate": (16, 4096, 14336), "w_up": (16, 4096, 14336), "wk": (16, 4096, 8, 128),
         "wo": (16, 32, 128, 4096), "wq": (16, 4096, 32, 128), "wv": (16, 4096, 8, 128)}),
    "laguna-xs.2-serve-l5": (
        lambda: LlamaConfig.laguna_xs2(n_layers=5, max_seq_len=4096),
        {"attn_norm": (5, 2048), "embed": (100352, 2048), "final_norm": (2048,),
         "mlp_norm": (5, 2048), "moe_router": (4, 2048, 256), "moe_shared_down": (4, 512, 2048),
         "moe_shared_gate": (4, 2048, 512), "moe_shared_up": (4, 2048, 512),
         "moe_w_down": (4, 256, 512, 2048), "moe_w_gate": (4, 256, 2048, 512),
         "moe_w_up": (4, 256, 2048, 512), "unembed": (2048, 100352), "w_down": (1, 8192, 2048),
         "w_gate": (1, 2048, 8192), "w_up": (1, 2048, 8192), "wg_full": (2, 2048, 48),
         "wg_sliding": (3, 2048, 64), "wk": (5, 2048, 8, 128), "wo_full": (2, 48, 128, 2048),
         "wo_sliding": (3, 64, 128, 2048), "wq_full": (2, 2048, 48, 128),
         "wq_sliding": (3, 2048, 64, 128), "wv": (5, 2048, 8, 128)}),
    "kanana-2-30b-a3b-serve-l5": (
        lambda: LlamaConfig.kanana2_30b_a3b(n_layers=5, max_seq_len=24576),
        {"attn_norm": (5, 2048), "embed": (128256, 2048), "final_norm": (2048,),
         "kv_norm_latent": (5, 512), "mlp_norm": (5, 2048), "moe_router": (4, 2048, 128),
         "moe_router_bias": (4, 128), "moe_shared_down": (4, 1536, 2048),
         "moe_shared_gate": (4, 2048, 1536), "moe_shared_up": (4, 2048, 1536),
         "moe_w_down": (4, 128, 768, 2048), "moe_w_gate": (4, 128, 2048, 768),
         "moe_w_up": (4, 128, 2048, 768), "unembed": (2048, 128256), "w_down": (1, 6144, 2048),
         "w_gate": (1, 2048, 6144), "w_up": (1, 2048, 6144), "wkv_a_latent": (5, 2048, 576),
         "wo_latent": (5, 32, 128, 2048), "wq_latent": (5, 2048, 32, 192),
         "wuk_latent": (5, 32, 128, 512), "wuv_latent": (5, 32, 512, 128)}),
}


@pytest.mark.parametrize("served", sorted(_SERVED_SHAPES))
def test_the_served_families_keep_their_parameter_and_cache_shapes(served):
    make, shapes = _SERVED_SHAPES[served]
    cfg = make()
    assert _param_shapes(cfg) == shapes
    pl = patterned.plan(cfg)
    assert pl.whole and pl.n_ssm == 0 and pl.n_attention == pl.n_mixer == pl.n_ffn == cfg.n_layers
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, 256))
    assert set(cache) == {"k", "v", "length"} and cache["k"].shape[:2] == (cfg.n_layers, 2)
    assert patterned.moe_stats_names(cfg) == patterned.MOE_STATS


def test_pattern_errors_are_named():
    with pytest.raises(ValueError, match="neither a mixer nor a feed-forward"):
        patterned.plan(dataclasses.replace(CFG, mlp_types=("none",) * 11))
    with pytest.raises(ValueError, match="ssm layers need"):
        patterned.plan(dataclasses.replace(CFG, ssm_heads=0))
    with pytest.raises(ValueError, match="outside the router's experts"):
        patterned.plan(dataclasses.replace(CFG, moe_experts_first=13))
    with pytest.raises(ValueError, match="unknown moe_activation"):
        LlamaConfig.tiny(moe_activation="gelu")
