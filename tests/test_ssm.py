"""State-space mixers through the cache (``models/patterned.py`` layer kinds
``ssm`` and ``none``; NVIDIA Nemotron-3-Super at test size): prefill and
decode against the benchmark's plain reference (a token-by-token recurrence),
the chunked scan against the one-token step, and the family's published keys
and depth. The step as a kernel, rows of one launch, the shares a device holds
and the engine: ``tests/test_ssm_step.py``, ``test_ssm_rows.py``,
``test_ssm_shares.py``, ``test_ssm_engine.py`` (one file until PR 47, cut so
that xdist's workers can share it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, decode_step, init_kv_cache, prefill
from ray_tpu.models.patterned import _param_shapes
from ray_tpu.ops.ssm import ssm_scan, ssm_step
from tests.ssm_models import CFG, PUBLISHED, STATE, T, TOL, _ssm_inputs, model


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
