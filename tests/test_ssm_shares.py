"""A padded row's state against the row alone, a leaf drawn a row at a time,
and the shares a device holds (``moe_latent_dim``, ``moe_experts_held``): of
an expert layer and of the head against the whole."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import forward, init_kv_cache, init_params, prefill
from tests import held_experts
from tests.ssm_models import CFG, PUBLISHED, STATE, TOL, model


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
