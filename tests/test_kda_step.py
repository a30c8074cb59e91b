"""The delta rule's one-token step as a kernel (``ops/kda.py
kda_step_in_place``, interpreted on the CPU): against the plain line on its
row, a row that has no token, a state that does not tile, a decode step
through the kernel, and the mixers ``forward`` refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import decode_step, forward, init_kv_cache, init_params, prefill
from ray_tpu.ops import kda
from ray_tpu.ops.kda import kda_step, kda_step_in_place
from tests.kda_models import CFG, TOL, _kda_inputs

# a stacked leaf that tiles: 2 layers, 3 slots, 4 heads of [16, 128]
TILED = dict(b=3, H=4, K=16, V=128)


def _stacked(steps, seed=0):
    """``steps`` tokens' operands a slot and a stacked leaf of 2 rows."""
    _, q, k, v, g, beta = _kda_inputs(steps, seed=seed, **TILED)
    return (jax.random.normal(jax.random.PRNGKey(seed + 9), (2, 3, 4, 16, 128)), q, k, v, g, beta)


def _steps_in_place(leaf, layer, q, k, v, g, beta):
    """One ``kda_step_in_place`` a token on row ``layer`` (traced, as under a
    layer loop) -> (o [steps, b, H, V], the leaf)."""
    def steps(leaf, layer, *ops):
        def one(leaf, t):
            o, leaf = kda_step_in_place(leaf, layer, *(x[:, t] for x in ops))
            return leaf, o
        leaf, os = jax.lax.scan(one, leaf, jnp.arange(q.shape[1]))
        return os, leaf
    return jax.jit(steps)(leaf, jnp.int32(layer), q, k, v, g, beta)


@pytest.mark.parametrize("heads_a_tile", [4, 2, 1])
@pytest.mark.parametrize("steps", [1, 32])
@pytest.mark.parametrize("layer", [0, 1])
def test_the_fused_step_equals_the_plain_line_on_its_row_and_touches_no_other(
        layer, steps, heads_a_tile, monkeypatch):
    """The kernel (interpreted here) on row ``layer`` of a stacked leaf
    against ``kda_step`` on that row taken out: ``o`` and the new state to
    float32 rounding after 1 step and after 32, with a tile a slot, two and
    four; the leaf's other row bit for bit what it was."""
    monkeypatch.setattr(kda, "TILE_BYTES", heads_a_tile * 16 * 128 * 4)
    assert kda.step_heads(4, 16, 128) == heads_a_tile
    leaf, *ops = _stacked(steps)
    os, got = _steps_in_place(leaf, layer, *ops)
    want = leaf[layer]
    for t in range(steps):
        o, want = kda_step(want, *(x[:, t] for x in ops))
        np.testing.assert_allclose(os[t], o, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got[layer], want, atol=2e-5, rtol=1e-5)
    assert np.array_equal(got[1 - layer], leaf[1 - layer])


@pytest.mark.parametrize("layer", [0, 1])
def test_the_fused_step_keeps_a_row_that_has_no_token_bit_for_bit(layer):
    """A slot whose ``beta`` and ``g`` are 0 (a dead slot, a padded token):
    its state after the step is its state before, every bit, while its
    neighbours' move."""
    leaf, q, k, v, g, beta = _stacked(1, seed=3)
    g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
    os, got = _steps_in_place(leaf, layer, q, k, v, g, beta)
    assert np.array_equal(got[layer, 1], leaf[layer, 1])
    assert not np.array_equal(got[layer, 0], leaf[layer, 0])
    o, _ = kda_step(leaf[layer], q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    np.testing.assert_allclose(os[0], o, atol=2e-5, rtol=1e-5)


def test_a_state_that_does_not_tile_takes_the_plain_line():
    """The ``solar-tiny`` preset's 16 x 16 state a head is no whole lane tile:
    ``kda_step_in_place`` is then ``kda_step`` on the row taken out and put
    back, bit for bit, and no kernel is traced; the shape that tiles traces
    one."""
    assert kda.step_heads(4, 16, 16) is None  # V
    assert kda.step_heads(4, 4, 128) is None  # K
    assert kda.step_heads(64, 128, 128) == 16  # Solar-Open2's: 1 MB a tile
    state, q, k, v, g, beta = _kda_inputs(1)
    leaf = jnp.stack([state, state + 1])
    args = (leaf, jnp.int32(1), q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    o, got = kda_step_in_place(*args)
    want_o, want = kda_step(leaf[1], *args[2:])
    assert np.array_equal(o, want_o) and np.array_equal(got[1], want)
    assert np.array_equal(got[0], leaf[0])
    assert "name=kda_step" not in str(jax.make_jaxpr(kda_step_in_place)(*args))
    leaf, *ops = _stacked(1)
    assert "name=kda_step" in str(jax.make_jaxpr(kda_step_in_place)(
        leaf, jnp.int32(1), *(x[:, 0] for x in ops)))


def test_a_decode_step_through_the_kernel_equals_the_plain_line(monkeypatch):
    """The call site (``models/patterned.py _kda_mix`` at one token a row):
    the tiny preset with heads 128 wide, whose state tiles, a 12-token prompt
    and 4 decode steps; logits and the state leaf against the same with the
    kernel's selection switched off."""
    cfg = dataclasses.replace(CFG, kda_head_dim=128, kda_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)

    def run():
        logits, cache = prefill(params, init_kv_cache(cfg, 2, 64), tokens, cfg)
        step = jax.jit(lambda c, t: decode_step(params, c, t, cfg))  # traced anew
        assert ("name=kda_step" in str(jax.make_jaxpr(step)(cache, tokens[:, 0]))) == (
            kda.step_heads(2, 128, 128) is not None)
        out = []
        for _ in range(4):
            logits, cache = step(cache, jnp.argmax(logits, -1).astype(jnp.int32).reshape(2))
            out.append(logits)
        return jnp.stack(out), cache["kda_state"]

    got, got_state = run()
    monkeypatch.setattr(kda, "step_heads", lambda *a: None)
    want, want_state = run()
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5, rtol=1e-5)


def test_forward_refuses_mixers_that_run_through_the_cache_only():
    with pytest.raises(NotImplementedError, match="run through the cache only"):
        forward(init_params(jax.random.PRNGKey(0), CFG), jnp.zeros((1, 4), jnp.int32), CFG)
