"""Zyphra ZAYA1-8B (``LlamaConfig.zaya1_8b``; ``zaya-tiny`` at test size):
every layer compressed convolutional attention (queries and keys through two
short convolutions, half of the value heads the token before's: a slot holds
tails beside its stripes) under top-1 experts routed by an MLP with a stream
of its own through the depth, learned scales at both joins, a tied head. The
model is held to the plain reference of family ``cca_moe``
(``benchmark/reference_cca_moe.py``: float32, whole sequences, nothing of
``ray_tpu``) on the benchmark's seeded weights, through ``forward`` and through
the cache in every split into chunks. The layer's parts, the shares and the
other families' programs: ``tests/test_zaya_parts.py``; the engine:
``tests/test_zaya_engine.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, forward, init_kv_cache, prefill
from ray_tpu.models.patterned import _param_shapes, cca_dims, plan, state_cache_shapes
from tests.zaya_models import CFG, PUBLISHED, reference, seeded_params, through_the_cache

T = 40
# float32 against float32 under ``highest``: the logits reach 30 in size (unit
# rows of a tied table); measured 2e-5 at most. A lost tail reads 0.5 and more.
TOL = dict(atol=2e-4, rtol=2e-4)


@pytest.fixture(scope="module")
def model():
    """(the seeded params, tokens [2, T], the reference's logits [2, T, V]
    and every layer's keys and values)."""
    params = seeded_params()
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, CFG.vocab_size))
    want = reference().forward_rows(params, list(tokens), last=T, kv_rows=range(2))
    assert len({int(e) for layer in want["choices"] for row in layer for e in row.ravel()}) > 4
    return params, tokens, np.stack(want["logits"]), want["kv"]


def test_the_family_maps_the_published_keys_onto_the_tiny_preset():
    from benchmark.families import cca_moe as family

    kw = family.model_kwargs(PUBLISHED)
    assert LlamaConfig.zaya_tiny(**kw) == CFG
    assert LlamaConfig.zaya1_8b(**kw, dtype=jnp.float32, remat=False, max_seq_len=128) == CFG
    shapes = {k: shape for k, (shape, _) in family.param_shapes(PUBLISHED).items()}
    assert shapes == _param_shapes(CFG)


def test_the_published_model_counts_its_parameters_and_its_cache():
    """A published layer is 207.57 M parameters (18.83 M of them a token's:
    the family's 8.3 B and A0.76B over 40 layers), the cut the cell serves 20
    layers of 106.91 M and the table; a slot holds 1,024 bytes of keys and
    values a token and layer and a tail of 2,688 numbers a layer."""
    whole = LlamaConfig.zaya1_8b()
    shapes = _param_shapes(whole)
    table = 262272 * 2048
    layer = (sum(np.prod(s) for k, s in shapes.items() if k not in ("embed", "final_norm"))) // 40
    assert layer == 207_575_074 and shapes["embed"] == (262272, 2048)
    assert 40 * layer + table + 2048 == whole.num_params() == 8_840_138_064
    cut = LlamaConfig.zaya1_8b(n_layers=20, moe_experts_held=8, max_seq_len=4608)
    assert cut.num_params() == 20 * (layer - 8 * 3 * 2048 * 2048) + table + 2048 == 2_675_370_664
    pl = plan(cut)
    assert (pl.bodies, pl.n_attention, pl.n_cca, pl.whole) == (1, 20, 20, True)
    assert pl.kv_index == tuple(range(20))
    cache = jax.eval_shape(lambda: init_kv_cache(cut, 64, 4608))
    assert cache["k"].shape == cache["v"].shape == (20, 64, 2, 4608, 128)
    assert cache["cca_tail"].shape == (20, 64, 1, 2688) and cache["cca_tail"].dtype == jnp.bfloat16
    assert cca_dims(cut) == {"heads": 10, "qk": 1280, "vprev": 128, "parts": (1280, 1280, 128),
                             "tail": 2688}
    assert set(state_cache_shapes(cut, 1)) == {"cca_tail"} <= set(patterned.STATE_LEAVES)
    # two more counts: this chip holds half of the router's experts
    assert patterned.moe_stats_names(cut)[-2:] == ("assignments_held", "passes")
    assert patterned.held_block(64, 8, 16) == 64 and patterned.held_block(4096, 8, 16) == 4096


def test_forward_equals_the_reference(model):
    params, tokens, want, _ = model
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(params, jnp.asarray(tokens), CFG))
    np.testing.assert_allclose(got, want, **TOL)


# the splits of two rows of T tokens into launches: (width, each row's tokens)
SPLITS = {
    "one-and-the-rest": [(8, (1, 1)), (32, (31, 31))],
    "bucket-edges": [(8, (8, 8)), (16, (16, 16)), (8, (8, 8))],
    "a-token-at-a-time": [(8, (3, 3))],
    "a-padded-row-beside-a-full-one": [(16, (16, 5)), (16, (16, 16)), (8, (3, 8)), (8, (0, 6))],
    "a-row-of-no-real-token": [(8, (8, 6)), (8, (0, 8)), (16, (16, 0)), (8, (8, 8)),
                               (16, (0, 10))],
    "whole": [(32, (32, 32))],
}


@pytest.mark.parametrize("split", sorted(SPLITS))
def test_prefill_in_every_split_then_decode_equals_the_whole_sequence_pass(model, split):
    """Logits wherever a launch gave one, the stripes of every layer up to
    each row's end (convolved, normed, rotated keys; values with their shifted
    half) and the tails: a chunk behind a chunk and a decode step behind a
    chunk start from the tails carried, a padded row's next tail is its own
    last real inputs, a row of no real token keeps its tail."""
    params, tokens, want, want_kv = model
    with jax.default_matmul_precision("highest"):
        got, cache, at = through_the_cache(params, tokens, SPLITS[split])
        _, whole = jax.jit(lambda p, c, t: prefill(p, c, t, CFG))(
            params, init_kv_cache(CFG, 2, 64), jnp.asarray(tokens))
    assert (at == T).all()
    for b in range(2):
        assert len(got[b]) >= T - 32
        for pos, logits in got[b]:
            np.testing.assert_allclose(logits, want[b, pos], err_msg=f"row {b} at {pos}", **TOL)
        for name, ref in zip(("k", "v"), want_kv[b]):
            have = np.asarray(cache[name][:, b, :, :T]).transpose(0, 2, 1, 3)
            np.testing.assert_allclose(have, ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(cache["cca_tail"], whole["cca_tail"], atol=2e-5, rtol=2e-5)


def test_the_tail_is_the_last_inputs_of_the_whole_pass(model):
    """Layer 0's tail after a prompt, reckoned here from the table and the
    layer's own leaves: the projections of the last token before the
    convolutions, the first convolution's output there, and the second half
    of the value heads."""
    params, tokens, _, _ = model
    n = 23
    with jax.default_matmul_precision("highest"):
        got, cache, _ = through_the_cache(params, tokens[:, :n], [(8, (8, 8)), (16, (15, 15))])
    x = np.asarray(params["embed"])[tokens[:, n - 2:n]]  # the last two tokens
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + CFG.rms_eps) * np.asarray(
        params["attn_norm"][0])
    qk = np.concatenate([np.einsum("bte,ehd->bthd", h, params["wq_cca"][0]),
                         np.einsum("bte,ehd->bthd", h, params["wk"][0])], axis=2).reshape(2, 2, -1)
    w, b = np.asarray(params["cca_conv0_w"][0]), np.asarray(params["cca_conv0_b"][0])
    u = w[0] * qk[:, 0] + w[1] * qk[:, 1] + b
    v2 = np.einsum("be,ed->bd", h[:, 1], params["wv"][0][:, 1])
    want = np.concatenate([qk[:, 1], u, v2], axis=-1)
    np.testing.assert_allclose(cache["cca_tail"][0, :, 0], want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("leaf,why", [
    ("moe_router_gamma", "the router's stream reaches the next layer"),
    ("cca_temp", "a key head's temperature"),
    ("attn_scale", "the scales at the attention's join"),
    ("mlp_scale", "the scales at the experts' join"),
    ("cca_conv1_w", "the convolution that mixes a head's channels"),
])
def test_each_learned_part_acts(model, leaf, why):
    """A model with ``gamma`` zero (no stream through the depth), or any other
    of the parts at another value, gives other logits, through ``forward``
    and through the cache alike."""
    params, tokens, want, _ = model
    other = {**params, leaf: params[leaf] * (0.0 if leaf == "moe_router_gamma" else 1.5)}
    got = np.asarray(forward(other, jnp.asarray(tokens), CFG))
    assert np.abs(got - want).max() > 0.05, why
    through, _, _ = through_the_cache(other, tokens, [(16, (16, 16))])
    pos, logits = through[0][-1]
    np.testing.assert_allclose(logits, got[0, pos], atol=2e-4, rtol=2e-4)


def test_a_router_stream_of_zero_width_and_no_scales_need_no_leaves():
    plain = dataclasses.replace(CFG, moe_router_hidden=0, residual_scales=False)
    names = set(_param_shapes(plain))
    assert "moe_router" in names and not any(
        n.startswith(("moe_router_", "attn_scale", "mlp_scale")) for n in names)
    assert "moe_router" not in _param_shapes(CFG)


@pytest.mark.parametrize("kw,match", [
    (dict(n_kv_heads=1, n_heads=4), "even n_kv_heads"),
    (dict(cca_taps=(2, 1)), "cca_taps"),
    (dict(attn_gate=True), "attn_gate"),
    (dict(moe_scoring="sigmoid"), "softmax router"),
])
def test_plan_refuses_what_the_layer_cannot_be(kw, match):
    with pytest.raises(ValueError, match=match):
        plan(LlamaConfig.zaya_tiny(**kw))


def test_the_router_and_the_scales_need_layers_that_are_not_alike():
    with pytest.raises(ValueError, match="need layer_types"):
        LlamaConfig.tiny(moe_router_hidden=16, moe_experts=4)
    with pytest.raises(ValueError, match="need layer_types"):
        LlamaConfig.tiny(residual_scales=True)
