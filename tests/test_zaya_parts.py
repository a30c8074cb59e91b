"""The parts of ``tests/test_zaya.py``'s model one at a time: the expert
layer's top-1 weight and its shares of the experts, the parts at their
defaults, and the other families' programs, which are what the parent
traced."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, ModelConfig
from ray_tpu.llm.config import refuse_stateful, resolve_llama_config
from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig
from tests import test_granite
from tests.zaya_models import CFG, PUBLISHED, seeded_params

BANKS = ("moe_w_gate", "moe_w_up", "moe_w_down")
ROW = 1  # the expert layer asked about


@pytest.fixture(scope="module")
def uncut():
    """(the config that holds all 8 experts, its seeded params, a layer's
    normed input [1, 24, e], the router's vector of the layer before, the
    layer's output, counts and router vector)."""
    cfg = dataclasses.replace(CFG, moe_experts_held=0)
    params = seeded_params(published={**PUBLISHED, "num_experts": 8})
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, cfg.d_model))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_eps)
    r_prev = jax.random.normal(jax.random.PRNGKey(4), (1, 24, cfg.moe_router_hidden))
    with jax.default_matmul_precision("highest"):
        y, stats, r = patterned._moe_decode_ffn(params, ROW, h, cfg, r_prev)
    return cfg, params, h, r_prev, y, dict(zip(patterned.moe_stats_names(cfg), stats)), r


def _by_hand(params, h, r_prev, cfg):
    """Each token's probabilities over the experts, its choice, and its
    chosen expert's output, in numpy."""
    def leaf(name):
        return np.asarray(params["moe_router_" + name][ROW], np.float64)

    h, r_prev = np.asarray(h[0], np.float64), np.asarray(r_prev[0], np.float64)
    r = h @ leaf("down") + leaf("gamma") * r_prev
    u = r / np.sqrt((r * r).mean(-1, keepdims=True) + cfg.rms_eps) * leaf("norm")
    for j in ("1", "2"):
        u = np.asarray(jax.nn.gelu(jnp.asarray(u @ leaf("w" + j) + leaf("b" + j))), np.float64)
    s = u @ leaf("w3") + leaf("b3")
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    chosen = np.argmax(p + leaf("bias"), -1)
    gate, up, down = (np.asarray(params[k][ROW], np.float64) for k in BANKS)
    out = np.stack([
        (np.asarray(jax.nn.silu(jnp.asarray(h[g] @ gate[e]))) * (h[g] @ up[e])) @ down[e]
        for g, e in enumerate(chosen)])
    return p, chosen, out, r


def test_the_chosen_expert_is_weighted_by_its_probability_not_by_one(uncut):
    cfg, params, h, r_prev, y, stats, r = uncut
    p, chosen, out, r_want = _by_hand(params, h, r_prev, cfg)
    weight = p[np.arange(len(chosen)), chosen]
    assert weight.max() < 0.99 and len(set(chosen)) >= 3
    np.testing.assert_allclose(y[0], weight[:, None] * out, atol=1e-5, rtol=1e-4)
    assert np.abs(np.asarray(y[0]) - out).max() > 0.05  # a weight of 1 is another layer
    np.testing.assert_allclose(r[0], r_want, atol=1e-5, rtol=1e-5)
    assert int(stats["assignments"]) == 24 and int(stats["experts_touched"]) == len(set(chosen))


def test_the_two_shares_of_a_layer_add_up_to_the_uncut_layer(uncut):
    """Experts 0-3 on one chip and 4-7 on the other, attention, router and
    the joins whole on both (counted once): the shares' outputs sum to the
    uncut layer's, every assignment falls on one of the two, and both hand the
    same router vector on to the next layer."""
    cfg, params, h, r_prev, y, stats, r = uncut
    total, held = 0.0, 0
    for first in (0, 4):
        share_cfg = dataclasses.replace(cfg, moe_experts_held=4, moe_experts_first=first)
        share = {**params, **{k: params[k][:, first:first + 4] for k in BANKS}}
        with jax.default_matmul_precision("highest"):
            y_s, stats_s, r_s = patterned._moe_decode_ffn(share, ROW, h, share_cfg, r_prev)
        counts = dict(zip(patterned.moe_stats_names(share_cfg), np.asarray(stats_s)))
        assert 0 < counts["assignments_held"] < 24 and counts["passes"] == 1
        total, held = total + y_s, held + counts["assignments_held"]
        np.testing.assert_array_equal(r_s, r)
    np.testing.assert_allclose(total, y, atol=1e-5, rtol=1e-5)
    assert held == int(stats["assignments"]) == 24


def test_more_than_one_expert_a_token_keeps_the_renormalised_weights(uncut):
    cfg, params, h, r_prev, *_ = uncut
    two = dataclasses.replace(cfg, moe_top_k=2)
    vals, idx, _ = patterned._mlp_route(params, ROW, h[0], r_prev[0], two)
    np.testing.assert_allclose(vals.sum(-1), 1.0, rtol=1e-6)
    assert (np.asarray(idx[:, 0]) != np.asarray(idx[:, 1])).all()


def test_the_parts_at_their_defaults_add_no_operation():
    """A full-attention expert model with the router a matrix, no scales and
    no ``cca`` layer lowers as it did: the new fields at their defaults are no
    operation (the accepted presets' own digests: below and in
    ``tests/test_granite.py``)."""
    base = LlamaConfig.laguna_tiny()
    assert base.moe_router_hidden == 0 and not base.residual_scales and base.cca_taps == (2, 2)
    assert test_granite._digest(base, False) == test_granite._PARENT["laguna_tiny"][0]
    assert patterned._router_stream(base, jnp.zeros((1, 2, 8))) == ()


# ``granite_tiny``'s decode step and 16-token chunk as PR 47's commit (the
# parent of PR 48) lowers them, taken there with ``tests/test_granite.py
# _digest``; the other five presets' stand in that file's ``_PARENT``
_GRANITE_PARENT = ((3172, "96f4484c47ae", 27), (1851, "4d6762116775", 37))


@pytest.mark.parametrize("chunk", [False, True], ids=["decode", "chunk"])
def test_the_whole_hybrid_models_programs_are_what_the_parent_traced(chunk):
    assert test_granite._digest(LlamaConfig.granite_tiny(), chunk) == _GRANITE_PARENT[chunk]


@pytest.mark.parametrize("name,preset", [("zaya1-8b", LlamaConfig.zaya1_8b),
                                         ("zaya-tiny", LlamaConfig.zaya_tiny)])
def test_llm_config_resolves_the_served_names(name, preset):
    engine = EngineConfig(max_seq_len=128, dtype="float32")
    got = resolve_llama_config(ModelConfig(model_id=name), engine)
    assert got == preset(max_seq_len=128, dtype=jnp.float32, vocab_size=got.vocab_size)


def test_refuse_stateful_names_the_layer_by_its_leaves():
    with pytest.raises(NotImplementedError, match="somewhere: a model with convolved-attention"):
        refuse_stateful(CFG, "somewhere")
    refuse_stateful(LlamaConfig.laguna_tiny(), "somewhere")
