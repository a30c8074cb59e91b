"""The delta rule's forms (``ops/kda.py``): the chunked form against the step
token by token, the solve of keys that are alike, a token that is none, and
the gate a head and a channel as forms of one field."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.patterned import _param_shapes
from ray_tpu.ops.kda import kda_scan, kda_step
from tests.kda_models import T, _kda_inputs


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
