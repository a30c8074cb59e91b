"""The shares a device holds of a delta-rule model's expert layer and head
against the whole, the list that names every leaf a slot holds, the other
families' decode programs as the parent traced them, and pattern errors."""

import collections
import dataclasses
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, decode_step, init_kv_cache, init_params
from ray_tpu.models.patterned import STATE_LEAVES, _param_shapes, state_cache_shapes
from tests import held_experts
from tests.kda_models import CFG, PUBLISHED


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
