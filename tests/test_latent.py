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

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, decode_step, init_kv_cache, prefill
from ray_tpu.models.patterned import _param_shapes
from tests.latent_models import CFG, T, TOL, model

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
