"""The one-token step of a state-space mixer as a kernel (``ops/ssm.py
ssm_step_in_place``, interpreted on the CPU): against the plain line on its
row, a row whose step is zero, a state that does not tile, a decode step
through the kernel, and the convolution's tail."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import decode_step, init_kv_cache, init_params, prefill
from ray_tpu.ops import ssm
from ray_tpu.ops.ssm import causal_conv, ssm_step, ssm_step_in_place
from tests.ssm_models import CFG, TOL, _ssm_inputs

# a stacked leaf that tiles: 2 layers, 3 slots, 16 heads of [8, 128] in 2 groups
TILED = dict(b=3, H=16, P=8, N=128, G=2)


def _stacked(steps, seed=0):
    """``steps`` tokens' operands a slot and a stacked leaf of 2 rows."""
    _, x, dt, a, B, C, D = _ssm_inputs(steps, seed=seed, **TILED)
    leaf = jax.random.normal(jax.random.PRNGKey(seed + 9), (2, 3, 16, 8, 128))
    return leaf, x, dt, a, B, C, D * 0.5


def _steps_in_place(leaf, layer, x, dt, a, B, C, D):
    """One ``ssm_step_in_place`` a token on row ``layer`` (traced, as under
    the layer loop) -> (y [steps, b, H, P], the leaf), jitted as a function
    of its own each call: the tile is read when it is traced."""
    def steps(leaf, layer, x, dt, a, B, C, D):
        def one(leaf, t):
            y, leaf = ssm_step_in_place(leaf, layer, x[:, t], dt[:, t], a, B[:, t], C[:, t], D)
            return leaf, y
        leaf, ys = jax.lax.scan(one, leaf, jnp.arange(x.shape[1]))
        return ys, leaf
    return jax.jit(steps)(leaf, jnp.int32(layer), x, dt, a, B, C, D)


@pytest.mark.parametrize("groups_a_tile", [2, 1], ids=["a-slot-a-tile", "a-group-a-tile"])
@pytest.mark.parametrize("steps", [1, 32])
@pytest.mark.parametrize("layer", [0, 1])
def test_the_fused_step_equals_the_plain_line_on_its_row_and_touches_no_other(
        layer, steps, groups_a_tile, monkeypatch):
    """The kernel (interpreted here) on row ``layer`` of a stacked leaf
    against ``ssm_step`` on that row taken out: ``y`` and the new state to
    float32 rounding after 1 step and after 32, with a tile a slot and with
    two (a group of heads each); the leaf's other row bit for bit what it
    was."""
    monkeypatch.setattr(ssm, "TILE_BYTES", groups_a_tile * 8 * 8 * 128 * 4)
    assert ssm.step_groups(16, 8, 128, 2) == groups_a_tile
    leaf, x, dt, a, B, C, D = _stacked(steps)
    ys, got = _steps_in_place(leaf, layer, x, dt, a, B, C, D)
    want = leaf[layer]
    for t in range(steps):
        y, want = ssm_step(want, x[:, t], dt[:, t], a, B[:, t], C[:, t], D)
        np.testing.assert_allclose(ys[t], y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got[layer], want, atol=2e-5, rtol=1e-5)
    assert np.array_equal(got[1 - layer], leaf[1 - layer])


@pytest.mark.parametrize("layer", [0, 1])
def test_the_fused_step_keeps_a_row_whose_step_is_zero_bit_for_bit(layer):
    """A slot whose ``dt`` is 0 (a dead slot, a padded token): its state
    after the step is its state before, every bit, while its neighbours'
    move; from a state of zeros its ``y`` is ``D x`` and nothing else."""
    leaf, x, dt, a, B, C, D = _stacked(1, seed=3)
    dt = dt.at[1].set(0.0)
    ys, got = _steps_in_place(leaf, layer, x, dt, a, B, C, D)
    assert np.array_equal(got[layer, 1], leaf[layer, 1])
    assert not np.array_equal(got[layer, 0], leaf[layer, 0])
    y, _ = ssm_step(leaf[layer], x[:, 0], dt[:, 0], a, B[:, 0], C[:, 0], D)
    np.testing.assert_allclose(ys[0], y, atol=2e-5, rtol=1e-5)
    ys, got = _steps_in_place(leaf.at[layer, 1].set(0.0), layer, x, dt, a, B, C, D)
    assert np.array_equal(ys[0, 1], D[:, None] * x[1, 0])
    assert not np.asarray(got[layer, 1]).any()


def test_a_state_that_does_not_tile_takes_the_plain_line():
    """The ``nemotron-tiny`` preset's 16 x 16 state a head is no whole lane
    tile: ``ssm_step_in_place`` is then ``ssm_step`` on the row taken out and
    put back, bit for bit, and no kernel is traced; the shape that tiles
    traces one."""
    assert ssm.step_groups(8, 16, 16, 2) is None  # N
    assert ssm.step_groups(16, 4, 128, 2) is None  # P
    assert ssm.step_groups(16, 8, 128, 3) is None  # heads in no whole groups
    assert ssm.step_groups(128, 64, 128, 8) is not None  # Nemotron-3-Super's
    state, x, dt, a, B, C, D = _ssm_inputs(1)
    leaf = jnp.stack([state, state + 1])
    args = (leaf, jnp.int32(1), x[:, 0], dt[:, 0], a, B[:, 0], C[:, 0], D)
    y, got = ssm_step_in_place(*args)
    want_y, want = ssm_step(leaf[1], *args[2:])
    assert np.array_equal(y, want_y) and np.array_equal(got[1], want)
    assert np.array_equal(got[0], leaf[0])
    assert "name=ssm_step" not in str(jax.make_jaxpr(ssm_step_in_place)(*args))
    leaf, x, dt, a, B, C, D = _stacked(1)
    assert "name=ssm_step" in str(jax.make_jaxpr(ssm_step_in_place)(
        leaf, jnp.int32(1), x[:, 0], dt[:, 0], a, B[:, 0], C[:, 0], D))


def test_a_decode_step_through_the_kernel_equals_the_plain_line(monkeypatch):
    """The call site (``models/patterned.py _ssm_mixer`` at one token a row,
    the layer's row as the layer loop hands it): the tiny preset with a state
    128 wide, which tiles, a 12-token prompt and 4 decode steps; logits and
    the state leaf against the same with the kernel's selection switched
    off."""
    cfg = dataclasses.replace(CFG, ssm_state=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)

    def run():
        logits, cache = prefill(params, init_kv_cache(cfg, 2, 64), tokens, cfg)
        step = jax.jit(lambda c, t: decode_step(params, c, t, cfg))  # traced anew
        assert ("name=ssm_step" in str(jax.make_jaxpr(step)(cache, tokens[:, 0]))) == (
            ssm.step_groups(8, 16, 128, 2) is not None)
        out = []
        for _ in range(4):
            logits, cache = step(cache, jnp.argmax(logits, -1).astype(jnp.int32).reshape(2))
            out.append(logits)
        return jnp.stack(out), cache["ssm_state"]

    got, got_state = run()
    monkeypatch.setattr(ssm, "step_groups", lambda *a: None)
    want, want_state = run()
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5, rtol=1e-5)


def test_the_convolution_reads_the_tail_in_front_of_its_tokens():
    tail = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 5))
    w, b = jax.random.normal(jax.random.PRNGKey(2), (4, 5)), jnp.arange(5.0)
    y, seen = causal_conv(tail, x, w, b)
    ext = np.concatenate([tail, x], axis=1)
    want = np.stack([sum(np.asarray(w)[j] * ext[:, t + j] for j in range(4)) for t in range(6)], 1)
    np.testing.assert_allclose(y, want + np.asarray(b), atol=1e-6)
    np.testing.assert_allclose(seen, ext)
