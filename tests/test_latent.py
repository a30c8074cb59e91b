"""Latent attention and the sigmoid router with a selection bias
(``models/patterned.py`` attention kind ``latent``; kakaocorp Kanana-2-30B-A3B
at test size, ``LlamaConfig.kanana_tiny``): ``forward`` against the
benchmark's plain reference of the expanded form, the path through the cache
against ``forward``, the absorbed form against the expanded one on the same
cache, chunk boundaries, a seeded prefix against a computed one, the gate
against NumPy, the decode kernel's latent walk in interpret mode, the engine's
counters, and the mesh paths' refusal."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from ray_tpu.models import patterned
from ray_tpu.models.llama import (
    EMBED_MINOR,
    LlamaConfig,
    decode_step,
    forward,
    init_kv_cache,
    init_params,
    prefill,
    serving_layouts,
)
from ray_tpu.models.patterned import _param_shapes
from ray_tpu.ops.decode_attention import (
    LATENT_BLOCKS,
    block_size,
    latent_decode_attention,
    positions_read,
)
from ray_tpu.parallel.moe import topk_gates

CFG = LlamaConfig.kanana_tiny()
# what benchmark/families/moe_latent.py reads, for the reference
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 8, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "kv_lora_rank": 32,
    "max_position_embeddings": 128, "model_type": "deepseek_v3", "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 16, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": None, "qk_head_dim": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 256,
}
T = 44
TOL = dict(atol=5e-5, rtol=1e-4)


@pytest.fixture(scope="module")
def model():
    """(params, tokens [2, T], ``forward``'s logits)."""
    params = init_params(jax.random.PRNGKey(7), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, CFG.vocab_size)
    return params, tokens, forward(params, tokens, CFG)


def test_the_family_maps_the_published_keys_onto_the_tiny_preset():
    from benchmark.families import moe_latent as family

    assert LlamaConfig.kanana_tiny(**family.model_kwargs(PUBLISHED)) == CFG
    assert {k: s for k, (s, _) in family.param_shapes(PUBLISHED).items()} == _param_shapes(CFG)


def test_published_depth_counts_its_parameters_and_traces_two_bodies():
    """48 layers of Kanana-2-30B-A3B: 30.67 B parameters, about 3 B of them a
    token's (6 of 128 experts), traced as layer 0 and one expert layer 47
    times; the cache holds 640 numbers a token and layer (576 and the key's
    row to whole lanes)."""
    cfg = LlamaConfig.kanana2_30b_a3b()
    pl = patterned.plan(cfg)
    assert (pl.lead, pl.period, pl.reps) == (1, 1, 47)
    assert cfg.num_params() == 30_670_815_104
    five = LlamaConfig.kanana2_30b_a3b(n_layers=5)
    cache = jax.eval_shape(lambda: init_kv_cache(five, 24, 24576))
    assert cache["k"].shape == (5, 24, 1, 24576, 128) and cache["v"].shape == (5, 24, 1, 24576, 512)
    assert five.mlp_types == ("dense",) + ("sparse",) * 4 and five.num_params() == 3_149_554_688


def test_forward_equals_the_reference_of_the_expanded_form(model):
    from benchmark.reference_moe_latent import Reference

    params, tokens, whole = model
    ref = Reference(PUBLISHED, jax.local_devices()[:1])
    want = ref.forward_rows(params, [np.asarray(r) for r in tokens], last=T, kv_rows=range(2))
    np.testing.assert_allclose(whole, np.stack(want["logits"]), **TOL)
    cache = init_kv_cache(CFG, 2, 64)
    _, cache = prefill(params, cache, tokens, CFG)
    for b in range(2):
        for name, ref_kv in zip(("k", "v"), want["kv"][b]):  # (rotated key, latent) [L, T, 1, D]
            have = np.asarray(cache[name][:, b, :, :T]).transpose(0, 2, 1, 3)
            np.testing.assert_allclose(have, ref_kv, atol=2e-5, rtol=1e-4)


_CHUNKS = {"one-chunk": (30,), "at-a-chunk-boundary": (16, 14), "three-chunks": (5, 16, 9)}


@pytest.mark.parametrize("chunks, form, stripe", [
    ("one-chunk", "absorbed", 128), ("at-a-chunk-boundary", "expanded", 128),
    ("three-chunks", "by-width", 128), ("one-chunk", "expanded", 64),
    ("at-a-chunk-boundary", "absorbed", 64), ("three-chunks", "expanded", 64),
], ids=lambda v: {64: "einsum-decode", 128: "kernel-decode"}.get(v, v))
def test_prefill_then_decode_equals_forward(monkeypatch, model, chunks, form, stripe):
    """Logits, not tokens: the prompt's 30 tokens go in as ``chunks``, the
    rest a token at a time, against ``forward`` (the expanded form over the
    whole sequence). A chunk takes the absorbed or the expanded form over the
    same cache (``by-width``: as the rule chooses); a decode step over a
    128-position stripe the kernel (always absorbed), over a 64-position one
    the blocked read in the form given."""
    params, tokens, whole = model
    if form != "by-width":
        monkeypatch.setattr(patterned, "_chunk_expands", lambda cfg, T: form == "expanded")
    assert patterned.reads_blocks(stripe, params["embed"], latent=True) == (stripe == 128)
    B = tokens.shape[0]
    pre = jax.jit(lambda p, c, t, s: prefill(p, c, t, CFG, start_pos=s))
    dec = jax.jit(lambda p, c, t: decode_step(p, c, t, CFG))
    cache = init_kv_cache(CFG, B, stripe)
    at = 0
    for n in _CHUNKS[chunks]:
        logits, cache = pre(params, cache, tokens[:, at:at + n], jnp.full((B,), at, jnp.int32))
        at += n
    got = [logits]
    for i in range(at, T - 1):
        logits, cache = dec(params, cache, tokens[:, i])
        got.append(logits)
    np.testing.assert_allclose(jnp.stack(got, axis=1), whole[:, 29:T - 1], **TOL)


def test_the_chunks_form_follows_its_width():
    """Published widths: absorbed up to 170 tokens a chunk, expanded from
    171; every bucket the cell's tails fall into but the widest is absorbed."""
    cfg = LlamaConfig.kanana2_30b_a3b(n_layers=5)
    assert [patterned._chunk_expands(cfg, t) for t in (1, 32, 64, 128, 170, 171, 256, 2496)] == [
        False, False, False, False, False, True, True, True]


def test_a_chunks_key_blocks_stop_at_the_furthest_row(monkeypatch, model):
    """Rows of unequal length over a stripe of several key blocks: the blocked
    read walks up to the longer row's last query and gives ``forward``'s
    logits for both; the padded row's cache keeps zeros past its length."""
    params, tokens, whole = model
    monkeypatch.setattr(patterned, "_LATENT_KEY_BLOCKS", (16,))
    cache = init_kv_cache(CFG, 2, 64)
    lengths = jnp.asarray([20, 9], jnp.int32)
    padded = jnp.where(jnp.arange(24)[None, :] < lengths[:, None], tokens[:, :24], 0)
    logits, cache = prefill(params, cache, padded, CFG, lengths=lengths)
    np.testing.assert_allclose(logits[0], whole[0, 19], **TOL)
    np.testing.assert_allclose(logits[1], whole[1, 8], **TOL)
    assert not np.asarray(cache["v"][:, 1, :, 9:]).any() and np.asarray(cache["v"][:, 1, :, 8]).any()


def test_a_prompt_behind_a_seeded_prefix_equals_the_prompt_computed(model):
    """What the engine's ``seed_prefix`` does: the first 16 tokens' keys and
    latents copied out of one cache into a fresh stripe, the rest prefilled
    behind them; logits and cache equal the prompt computed whole."""
    params, tokens, whole = model
    row = tokens[:1]
    _, computed = prefill(params, init_kv_cache(CFG, 1, 64), row[:, :30], CFG)
    seeded = init_kv_cache(CFG, 1, 64)
    seeded = {**seeded, **{n: seeded[n].at[:, 0, :, :16].set(computed[n][:, 0, :, :16])
                           for n in ("k", "v")}}
    logits, seeded = prefill(params, seeded, row[:, 16:30], CFG,
                             start_pos=jnp.asarray([16], jnp.int32))
    np.testing.assert_allclose(logits[0], whole[0, 29], **TOL)
    for n in ("k", "v"):
        np.testing.assert_allclose(seeded[n], computed[n], atol=2e-6, rtol=1e-5)


def _numpy_gate(x, router, bias, k):
    scores = 1.0 / (1.0 + np.exp(-(x @ router)))
    idx = np.argsort(-(scores + bias), axis=-1, kind="stable")[:, :k]
    top = np.take_along_axis(scores, idx, axis=-1)
    return top / top.sum(-1, keepdims=True), idx


def test_sigmoid_gate_with_a_bias_that_moves_the_choice_and_not_the_weight():
    rng = np.random.default_rng(0)
    x, router = rng.normal(size=(64, 32)).astype(np.float32), rng.normal(size=(32, 16)).astype(np.float32) / 6
    bias = rng.normal(0, 0.3, 16).astype(np.float32)
    params = {"router": jnp.asarray(router), "bias": jnp.asarray(bias)}
    probs, vals, idx = topk_gates(params, jnp.asarray(x), 3)
    want_vals, want_idx = _numpy_gate(x, router, bias, 3)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    order = np.argsort(np.asarray(idx), -1), np.argsort(want_idx, -1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(vals), order[0], -1),
                               np.take_along_axis(want_vals, order[1], -1), rtol=1e-5)
    # the bias changes which experts are chosen ...
    _, _, unbiased = topk_gates({**params, "bias": jnp.zeros(16)}, jnp.asarray(x), 3)
    assert (np.sort(unbiased, -1) != np.sort(idx, -1)).any()
    # ... and is no part of a chosen expert's weight: the scores alone, renormalised
    chosen = np.take_along_axis(np.asarray(probs), np.asarray(idx), -1)
    np.testing.assert_allclose(vals, chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    # without a bias the router is the softmax one, as before
    soft, soft_vals, _ = topk_gates({"router": params["router"]}, jnp.asarray(x), 3)
    np.testing.assert_allclose(soft.sum(-1), 1.0, rtol=1e-5)


def test_interleaved_rotation_against_numpy():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 5, 3, 8)).astype(np.float32)
    pos = np.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    inv, factor = patterned.rope_inv_freq(CFG, "latent")
    got = patterned._rope(jnp.asarray(x), jnp.asarray(pos), inv, factor, interleave=True)
    want = np.empty_like(x)
    for j in range(4):
        ang = pos[..., None] / 1e6 ** (2 * j / 8)
        a, b = x[..., 2 * j], x[..., 2 * j + 1]
        want[..., 2 * j] = a * np.cos(ang) - b * np.sin(ang)
        want[..., 2 * j + 1] = b * np.cos(ang) + a * np.sin(ang)
    np.testing.assert_allclose(got, want, atol=1e-5)


# ------------------------------------------------------------ the decode kernel

L, S, R, DR, H = 3, 4 * 128, 64, 128, 8
BOUNDS = {
    "one-position": 1,
    "one-position-past-a-block": 129,
    "ends-mid-block": 128 + 37,
    "one-whole-block": 128,
    "the-whole-stripe": S,
}


def _latent_einsum(q_rope, q_lat, ck, cv, layer, hi, scale):
    s = (jnp.einsum("bhd,bsd->bhs", q_rope, ck[layer, :, 0])
         + jnp.einsum("bhr,bsr->bhs", q_lat, cv[layer, :, 0])).astype(jnp.float32) * scale
    s = jnp.where(jnp.arange(ck.shape[3])[None, None, :] < hi[:, None, None], s, -1e30)
    return jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, -1).astype(cv.dtype), cv[layer, :, 0])


@pytest.mark.parametrize("dtype, tol", [(jnp.bfloat16, 0.03), (jnp.float32, 2e-5)],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(BOUNDS))
def test_latent_kernel_equals_the_einsum_over_rows_of_unequal_length(monkeypatch, name, dtype, tol):
    """Eight query heads on one shared key whose two parts lie in two leaves
    (rotated key, latent) and whose value is the latent; every row of the
    batch ends somewhere else, 128-position blocks so that rows span one to
    four of them."""
    import ray_tpu.ops.decode_attention as da

    monkeypatch.setattr(da, "LATENT_BLOCKS", (128,))
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    B = len(BOUNDS)
    q_rope = jax.random.normal(ks[0], (B, H, DR), dtype)
    q_lat = jax.random.normal(ks[1], (B, H, R), dtype)
    ck = jax.random.normal(ks[2], (L, B, 1, S, DR), dtype)
    cv = jax.random.normal(ks[3], (L, B, 1, S, R), dtype)
    order = list(BOUNDS)
    order = order[order.index(name):] + order[:order.index(name)]
    hi = jnp.asarray([BOUNDS[n] for n in order], jnp.int32)
    got = jax.jit(latent_decode_attention, static_argnames="scale")(
        q_rope, q_lat, ck, cv, jnp.int32(1), jnp.zeros_like(hi), hi, scale=0.1)
    want = _latent_einsum(q_rope, q_lat, ck, cv, 1, hi, 0.1)
    assert got.shape == (B, H, R) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_latent_blocks_are_the_longest_that_divide_the_stripe():
    assert LATENT_BLOCKS == (512, 256, 128)
    assert [block_size(s, latent=True) for s in (24576, 2560, 768, 128, 96)] == [512, 512, 256, 128, None]
    assert block_size(24576) == 128
    # a 700-token slot of a 24,576-position stripe: two 512-position blocks
    assert positions_read(0, np.asarray([700, 1]), 24576, latent=True).tolist() == [1024, 512]


# ------------------------------------------------------------------ the engine


@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="kanana-tiny"),
        engine=EngineConfig(max_num_seqs=4, max_seq_len=256, dtype="float32",
                            prefill_buckets=(32, 64, 128), prefill_chunk=32),
    ))
    yield eng
    eng.shutdown()


def test_engine_serves_a_hit_as_it_served_the_miss_and_counts_both(engine):
    """A 140-token prompt twice, greedy: the second is served behind the
    128-token prefix the first left in the store, token for token; the
    counters of latent layers, of each prefill program's attention and of
    the seeding say what ran."""
    before = engine.get_stats()["counters"]
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(0, 256, 140)]
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    miss = engine.generate(prompt_token_ids=prompt, sampling_params=sp)
    hit = engine.generate(prompt_token_ids=prompt, sampling_params=sp)
    assert (miss.metrics["prefix_hit_tokens"], hit.metrics["prefix_hit_tokens"]) == (0, 128)
    assert miss.token_ids == hit.token_ids
    # and both are greedy over the full forward pass
    tokens = jnp.asarray([prompt + miss.token_ids[:-1]], jnp.int32)
    greedy = np.argmax(np.asarray(forward(engine.params, tokens, engine.model_cfg))[0, 139:], -1)
    assert miss.token_ids == greedy.tolist()
    stats = engine.get_stats()
    now = stats["counters"]
    delta = lambda name, label=None: (  # noqa: E731
        now[name][label] - before[name][label] if label else now[name] - before[name])
    assert delta("prompt_tokens") == 280 and delta("prompt_tokens_from_prefix") == 128
    assert delta("prefix_seed_tokens") == 128
    # the miss: four 32-token middle chunks, then 12 tokens; the hit: 12 behind 128
    assert delta("prefill_query_tokens", "chunk_mid") == 128
    assert delta("prefill_query_tokens", "chunk_final") == 24
    assert delta("prefill_attended_positions", "chunk_mid") == 128 * 129 // 2
    assert delta("prefill_attended_positions", "chunk_final") == 2 * (12 * 128 + 12 * 13 // 2)
    assert delta("decode_kv_tokens_latent") > 0 and delta("decode_kv_tokens_global") == 0
    # a 256-position stripe: the kernel reads one 256-position block a slot and step
    assert engine._pools[0].reads_blocks
    assert delta("decode_kv_positions_read_latent") == 256 * delta("decode_slot_steps")
    (pool,) = stats["pools"]
    # 3 layers of 128 (the rotated key's lane row) + 32 (latent) float32 numbers
    assert pool["kv_bytes_per_token"] == 3 * (128 + 32) * 4
    assert "kv_bytes_per_token_held" in pool and stats["prefix_cache_bytes"] > 0


def test_the_layout_rule_holds_the_latent_query_projection_embed_minor():
    """A head of 128 + 64 is no whole number of lane tiles: ``embed`` goes on
    the lanes (``tests/test_chip_compile.py`` counts the copies it saves)."""
    assert serving_layouts(_param_shapes(CFG)) == {"wq_latent": EMBED_MINOR == (0, 2, 3, 1) and EMBED_MINOR}


@pytest.mark.parametrize("module", ["llm/spmd.py", "llm/gang.py", "tensor_parallel_degree"])
def test_the_mesh_paths_refuse_a_latent_model_by_name(module):
    cfg = LLMConfig(model=ModelConfig(model_id="kanana-tiny"),
                    engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32"))
    if module == "llm/spmd.py":
        from ray_tpu.llm.spmd import SPMDGenerator

        build = lambda: SPMDGenerator(cfg)  # noqa: E731
    elif module == "llm/gang.py":
        from ray_tpu.llm.gang import GangLLMServer

        build = lambda: GangLLMServer(cfg, num_workers=2)  # noqa: E731
    else:
        cfg.engine.tensor_parallel_degree = 2
        build = lambda: JaxEngine(cfg)  # noqa: E731
        module = "llm/engine.py over a mesh"
    with pytest.raises(NotImplementedError, match=module.replace(".", r"\.") + ".*latent"):
        build()


def test_latent_pattern_errors_are_named():
    with pytest.raises(ValueError, match="need layer_types"):
        patterned.plan(LlamaConfig.tiny(kv_latent_rank=32))
    with pytest.raises(ValueError, match="need layer_types"):
        patterned.plan(LlamaConfig.tiny(moe_experts=4, moe_scoring="sigmoid"))
    with pytest.raises(ValueError, match="do not mix"):
        patterned.plan(LlamaConfig.kanana_tiny(layer_types=("latent", "full", "latent")))
    with pytest.raises(ValueError, match="latent layers need"):
        patterned.plan(LlamaConfig.kanana_tiny(n_kv_heads=2))
    with pytest.raises(ValueError, match="unknown moe_scoring"):
        LlamaConfig.tiny(moe_scoring="tanh")
