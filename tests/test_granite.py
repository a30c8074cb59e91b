"""IBM Granite-4.0-H-Micro (``LlamaConfig.granite4_h_micro``; ``granite-tiny``
at test size): every layer a mixer under a dense SwiGLU, the mixer Mamba-2 or
GQA without rotation by ``layer_types``, the four Granite scalars, a tied
head. The model and the engine are held to the plain reference of family
``ssm_gqa_dense`` (``benchmark/reference_ssm_gqa_dense.py``: float32, a token
at a time, nothing of ``ray_tpu``) on the benchmark's seeded weights; the
other families' programs are what the parent traced."""

import collections
import dataclasses
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, ModelConfig
from ray_tpu.llm.config import resolve_llama_config
from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, decode_step, init_kv_cache, init_params, prefill
from ray_tpu.models.patterned import _param_shapes, state_cache_shapes

CFG = LlamaConfig.granite_tiny()
# what benchmark/families/ssm_gqa_dense.py reads, for the reference
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.125, "embedding_multiplier": 3,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["mamba", "attention", "mamba", "mamba"] * 2, "logits_scaling": 2,
    "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
    "mamba_proj_bias": False, "max_position_embeddings": 128, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 4, "num_experts_per_tok": 0,
    "num_hidden_layers": 8, "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.5, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 128,
    "tie_word_embeddings": True, "vocab_size": 256,
}
T = 44
# float32 against float32 under ``highest``: the chunked scan and the
# reference's token-by-token recurrence sum in another order, and the logits
# reach 38 in size; measured 8e-6 at most (three chunkings). A missing
# multiplier reads 0.8 (the attention scale) to 34 (the logits' divisor).
TOL = dict(atol=5e-5, rtol=1e-4)


@pytest.fixture(scope="module")
def model():
    """(the benchmark's seeded params, tokens [2, T], the reference's logits
    [2, T, V] and keys and values of the two attention layers)."""
    from benchmark.families import ssm_gqa_dense as family

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
    from benchmark.families import ssm_gqa_dense as family

    assert LlamaConfig.granite_tiny(**family.model_kwargs(PUBLISHED)) == CFG
    assert {k: s for k, (s, _) in family.param_shapes(PUBLISHED).items()} == _param_shapes(CFG)
    pl = patterned.plan(CFG)
    assert (pl.n_ssm, pl.n_attention, pl.period, pl.reps, pl.whole) == (6, 2, 4, 2, False)
    # every scalar differs from 1 and from its default's effect
    assert CFG.attention_multiplier != CFG.head_dim ** -0.5
    assert 1.0 not in (CFG.embedding_multiplier, CFG.residual_multiplier, CFG.logits_scaling)


def test_the_published_model_counts_its_parameters_and_its_cache():
    """All 40 layers of Granite-4.0-H-Micro: 3,191,396,096 parameters (the
    published "3B", ISSUE 44's total), a mamba layer 76.18 M (the issue's
    76.21 M counts the mixer 31 k too high) and an attention layer 60.82 M,
    four periods of ten layers (ten traced bodies); the cache holds keys and
    values of the four attention layers, 8,192 bytes a token, and 76,437,504
    bytes of state and convolution tail a slot."""
    cfg = LlamaConfig.granite4_h_micro()
    assert cfg.num_params() == 3_191_396_096
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full"] == [5, 15, 25, 35]
    shapes = _param_shapes(cfg)
    per = lambda names: sum(int(np.prod(shapes[n][1:])) for n in names)  # noqa: E731
    ffn = per(("w_gate", "w_up", "w_down"))
    mixer = per([n for n in shapes if n.startswith("ssm_")])
    assert (ffn, mixer) == (50_331_648, 25_847_232)
    assert mixer + ffn + 2 * 2048 == 76_182_976
    assert per(("wq_full", "wk", "wv", "wo_full")) + ffn + 2 * 2048 == 60_821_504
    assert shapes["embed"] == (100_352, 2048) and "unembed" not in shapes
    pl = patterned.plan(cfg)
    assert (pl.lead, pl.period, pl.reps, pl.bodies, pl.n_ssm, pl.n_attention) == (0, 10, 4, 10, 36, 4)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 24, 4096))
    assert cache["k"].shape == cache["v"].shape == (4, 24, 8, 4096, 64)
    assert cache["ssm_state"].shape == (36, 24, 64, 64, 128)
    assert cache["ssm_state"].dtype == jnp.float32 and cache["ssm_conv"].shape == (36, 24, 3, 4352)
    state = sum(cache[k].size * cache[k].dtype.itemsize for k in state_cache_shapes(cfg, 1)) // 24
    assert state == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2) == 76_437_504
    assert 2 * cache["k"].size * 2 // (24 * 4096) == 8_192


@pytest.mark.parametrize("chunks, stripe", [((30,), 64), ((16, 14), 64), ((5, 16, 9), 64), ((30,), 128)],
                         ids=["one-chunk", "at-a-scan-chunk", "three-chunks", "whole-blocks"])
def test_prefill_then_decode_equals_the_reference(model, chunks, stripe):
    """Logits, not tokens, and the attention layers' keys and values, which
    lie behind one and four mamba layers: the prompt's 30 tokens go in as
    ``chunks`` (the scan's own chunk is 8), the rest a token at a time,
    against the reference's token-by-token recurrence over the whole row.
    A stripe of 64 is no whole block, so the decode steps read through the
    einsum, as the published model's 64-wide heads do on the chip
    (``ops/decode_attention.py takes_heads_of``); a stripe of 128 goes through
    the kernel, interpreted, which takes any width here."""
    from ray_tpu.models import patterned

    params, tokens, want, want_kv = model
    cache = init_kv_cache(CFG, 2, stripe)
    assert patterned.reads_blocks(stripe, cache["k"], *jax.tree.leaves(params)) == (stripe == 128)
    got, cache = _through_the_cache(params, tokens, chunks, stripe=stripe)
    np.testing.assert_allclose(got, want[:, 29:T - 1], **TOL)
    for b in range(2):
        for name, ref_kv in zip(("k", "v"), want_kv[b]):  # [2, T, KV, D]
            have = np.asarray(cache[name][:, b, :, :T - 1]).transpose(0, 2, 1, 3)
            np.testing.assert_allclose(have, ref_kv[:, :T - 1], atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("field, wrong", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.0), ("logits_scaling", 1.0),
], ids=lambda v: str(v))
def test_each_of_the_four_scalars_acts(model, field, wrong):
    """With one scalar at its default (0 for the attention scale: head_dim **
    -0.5, a quarter where the model says an eighth) the model no longer agrees
    with the reference: a hundred times the tolerance at the least."""
    params, tokens, want, _ = model
    got, _ = _through_the_cache(params, tokens, (30,), cfg=dataclasses.replace(CFG, **{field: wrong}))
    assert np.abs(got - want[:, 29:T - 1]).max() > 0.1


def test_the_scalars_at_their_defaults_add_no_operation():
    x = jnp.ones((2, 3))
    assert patterned._times(x, 1.0) is x
    assert patterned._score_rescale(LlamaConfig.nemotron_tiny()) == 1.0
    # Granite's 1/64 over 64 ** -0.5: an eighth, exact in bfloat16
    assert patterned._score_rescale(LlamaConfig.granite4_h_micro()) == 0.125


def test_the_multipliers_need_layers_that_are_not_alike():
    with pytest.raises(ValueError, match="layer_types"):
        LlamaConfig.tiny(residual_multiplier=0.22)
    with pytest.raises(ValueError, match="layer_types"):
        LlamaConfig.tiny(attention_multiplier=0.1)


@pytest.mark.parametrize("name, preset", [("granite-4.0-h-micro", LlamaConfig.granite4_h_micro),
                                          ("granite-tiny", LlamaConfig.granite_tiny)])
def test_llm_config_resolves_the_served_names(name, preset):
    cfg = resolve_llama_config(ModelConfig(model_id=name), EngineConfig(max_seq_len=4096))
    assert cfg == preset(max_seq_len=4096, dtype=cfg.dtype)


# the decode step and a 16-token prompt chunk of each other family's tiny
# preset: operations in all, a digest of their histogram by name, and their
# matrix products (taken with this file's ``_digest``). ``tiny``,
# ``laguna_tiny`` and ``kanana_tiny`` hold the lowering of PR 43's commit (the
# parent of PR 44), which PR 45 left as it was: a model that holds all its
# experts, or has none, keeps its programs operation for operation.
# ``nemotron_tiny`` and ``solar_tiny`` hold PR 45's: an expert layer that
# holds a share works on a block of its assignments under one loop
# (``models/patterned.py _moe_decode_ffn``; before it they lowered to
# (4404, "08738863b0fd", 53), (3197, "b11c0716118a", 71) and
# (4749, "e641a1fa2b18", 46), (4403, "bb8f9e0e53f5", 68))
_PARENT = {
    "tiny": ((2189, "72306fc03fc3", 12), (595, "40278391e201", 10)),
    "laguna_tiny": ((11403, "582b0fc462ba", 73), (2948, "1f39bc3a537e", 63)),
    "kanana_tiny": ((5562, "8942df0a7722", 29), (2145, "b0945fb3ed34", 29)),
    "nemotron_tiny": ((4667, "8438b9700414", 48), (3433, "bd252682d190", 66)),
    "solar_tiny": ((4974, "91bcd69ad991", 42), (4630, "8142dad38778", 64)),
}


def _histogram(text):
    """Of a lowered program's text: operations in all, a digest of their
    histogram by name, the histogram."""
    ops = dict(sorted(collections.Counter(
        re.findall(r"= \"?((?:stablehlo|func|chlo)\.[\w.]+)", text)).items()))
    return sum(ops.values()), hashlib.sha1(json.dumps(ops).encode()).hexdigest()[:12], ops


def _digest(cfg, chunk: bool):
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, 128))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    if chunk:
        text = jax.jit(lambda p, c, t, s: prefill(p, c, t, cfg, start_pos=s)).lower(
            params, cache, i32(2, 16), i32(2)).as_text()
    else:
        text = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg)).lower(
            params, cache, i32(2)).as_text()
    total, digest, ops = _histogram(text)
    return total, digest, ops.get("stablehlo.dot_general")


@pytest.mark.parametrize("chunk", [False, True], ids=["decode", "chunk"])
@pytest.mark.parametrize("preset", sorted(_PARENT))
def test_the_other_families_programs_are_what_the_parent_traced(preset, chunk):
    """No operation more, fewer or other in the decode step or a prompt chunk
    of any accepted family than before the four scalars came in: a scalar at
    its default is no operation."""
    assert _digest(getattr(LlamaConfig, preset)(), chunk) == _PARENT[preset][chunk]


# the engine's decode program (``llm/engine.py programs`` ``decode_fn``: the
# step and the sampler over 4 slots) of each family's tiny preset, as PR 49's
# commit lowered it: operations in all and the digest of their histogram. A
# 256-wide vocabulary is two blocks of the sampler's selection
# (``ops/topk.py``), so its 64 candidates come from the plain ``lax.top_k``
# and the program is the parent's, whatever the selection does to wide rows.
_PARENT_ENGINE = {
    "tiny": (2469, "d26e65b01eb9"),
    "laguna_tiny": (11755, "57af7d8db220"),
    "kanana_tiny": (5866, "bffe4e7d3de5"),
    "nemotron_tiny": (5052, "a9610bc367f1"),
    "solar_tiny": (5339, "8f4d1ddca193"),
}


@pytest.mark.parametrize("preset", sorted(_PARENT_ENGINE))
def test_a_narrow_vocabularys_decode_program_is_what_the_parent_traced(preset):
    from ray_tpu.llm.engine import programs

    cfg = getattr(LlamaConfig, preset)()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 4, 128))
    sds = jax.ShapeDtypeStruct
    text = jax.jit(programs(cfg)["decode_fn"]).lower(
        params, cache, sds((4,), jnp.int32), sds((4,), jnp.float32), sds((4,), jnp.int32),
        sds((4, 2), jnp.uint32)).as_text()
    assert _histogram(text)[:2] == _PARENT_ENGINE[preset]
