"""The chunked delta rule's kernel (``ops/kda.py kda_scan`` where the shapes
tile, interpreted here) against the plain form it stands for
(``kda_scan_plain``) and against the rule's own line a token at a time
(``kda_step``), at heads 128 wide in chunks of 64: decays of -40 a token, keys
nearly alike under writing strengths near 2, tokens that are none, a state
carried in from an earlier launch, rows of two lengths in one launch and a
length that is no whole number of chunks; that float32 at the highest precision
is what the tolerances hold; and that a shape which does not tile keeps the
plain form and says so. The model's path through the cache and the step's
kernel: ``tests/test_kda.py`` and its parts."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.patterned import state_mixer_forms
from ray_tpu.ops import kda
from ray_tpu.ops.kda import kda_scan, kda_scan_plain, kda_step

B, H, D, C = 2, 4, 128, 64
# float32's level: a product rounded to bfloat16 reads 1e-3 and more (below)
F32 = dict(atol=2e-5, rtol=2e-5)

# one traced program a form and shape for the whole file
KERNEL = jax.jit(functools.partial(kda_scan, chunk=C))
PLAIN = jax.jit(functools.partial(kda_scan_plain, chunk=C))


@jax.jit
def STEPS(state, q, k, v, g, beta):
    def one(state, x):
        o, state = kda_step(state, *x)
        return state, o
    state, o = jax.lax.scan(one, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _inputs(T, seed=0, rate=1.0, beta_shift=0.0, alike=False, b=B):
    """Operands as ``tests/kda_models.py _kda_inputs`` draws them: unit keys,
    queries times K ** -0.5, log-decays log-uniform down to ``-rate`` a token,
    writing strengths 2 sigmoid(. + ``beta_shift``), a state that is not zero;
    ``alike``: every key within 0.05 of the first, and hardly a decay."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (b, T, H, D)) for key in ks[:2])
    if alike:
        k = k[:, :1] + 0.05 * k
    q, k = (t / jnp.linalg.norm(t, axis=-1, keepdims=True) for t in (q, k))
    v = jax.random.normal(ks[2], (b, T, H, D))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, T, H, D), minval=-6.0, maxval=np.log(rate)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, H)) + beta_shift)
    return (jax.random.normal(ks[5], (b, H, D, D)), q * D ** -0.5, k, v,
            g * 1e-3 if alike else g, beta)


def test_the_shapes_that_tile_take_the_kernel_and_the_others_the_plain_form():
    """``scan_heads`` reads the shapes at trace time: Solar-Open2's 64 heads of
    128 x 128 in chunks of 64 go four heads a grid step (two sets of two, whose
    chunks lie side by side on the lanes), an odd pair count one set, the tiny
    preset's 16 x 16 state and its chunks of 8 none; and ``get_stats()`` hands
    out what ``state_mixer_forms`` says (``tests/test_kda_engine.py``,
    ``tests/test_ssm_engine.py`` read it off an engine)."""
    assert kda.scan_heads(64, 128, 128, 64) == 4
    assert kda.scan_heads(6, 128, 128, 64) == 2
    assert kda.scan_heads(16, 128, 256, 32) == 8 and kda.scan_heads(8, 128, 128, 128) == 2
    assert kda.scan_heads(3, 128, 128, 64) is None  # heads that are no whole sets
    assert kda.scan_heads(4, 16, 16, 8) is None  # the tiny preset
    assert kda.scan_heads(4, 128, 128, 8) is None  # a chunk under the solve's base
    assert kda.scan_heads(4, 128, 64, 64) is None and kda.scan_heads(4, 256, 128, 64) is None
    args = _inputs(C)
    assert "name=kda_scan" in str(jax.make_jaxpr(functools.partial(kda_scan, chunk=C))(*args))
    small = tuple(x[:, :16, :, :16] if x.ndim == 4 else x[:, :16] for x in args[1:])
    state = args[0][..., :16, :16]
    assert "name=kda_scan" not in str(
        jax.make_jaxpr(functools.partial(kda_scan, chunk=8))(state, *small))
    tiny = LlamaConfig.solar_tiny(n_layers=4, gqa_layers=(3,))
    assert state_mixer_forms(tiny) == {"kda": {"chunk": "plain", "step": "plain"}}
    served = LlamaConfig.solar_open2_250b(
        n_layers=4, gqa_layers=(3,), moe_experts_held=40, vocab_size=24576, max_seq_len=8192)
    assert state_mixer_forms(served) == {"kda": {"chunk": "kernel", "step": "kernel"}}
    nemotron = LlamaConfig.nemotron3_super(
        n_layers=11, moe_experts_held=128, vocab_size=32768, max_seq_len=2048)
    assert state_mixer_forms(nemotron) == {"ssm": {"chunk": "plain", "step": "kernel"}}
    assert state_mixer_forms(LlamaConfig.tiny()) == {}


@pytest.mark.parametrize("T,rate,beta_shift,alike", [
    (160, 1.0, 0.0, False),  # two chunks and a half
    (128, 1.0, 0.0, False),  # whole chunks
    # the strongest seeded decay, and far past it: exp(-G) alone overflows
    # inside a chunk and inside a block of 16
    (160, 1.6, 0.0, False), (160, 40.0, 0.0, False),
    # writing strengths near 2 (eigenvalues of I - beta k k^T near -1)
    (160, 1.6, 5.0, False),
    # keys nearly the same token after token under strengths near 2, no decay:
    # I + A has entries near 2 below its diagonal, where a product form of
    # the inverse would lose every digit over a block of 16
    (128, 1.0, 5.0, True),
], ids=lambda x: str(x))
def test_the_kernel_equals_the_plain_form_and_the_step_token_by_token(T, rate, beta_shift, alike):
    """From a state that is not zero: outputs and the state after the last
    token, against the plain form at float32's level and against ``kda_step``
    at the tolerances ``tests/test_kda_forms.py`` holds the plain form to."""
    args = _inputs(T, seed=T + int(10 * rate), rate=rate, beta_shift=beta_shift, alike=alike)
    got_o, got_s = KERNEL(*args)
    assert np.isfinite(np.asarray(got_o)).all() and np.isfinite(np.asarray(got_s)).all()
    plain_o, plain_s = PLAIN(*args)
    # keys alike: a solve that loses digits in any form (state entries to 15;
    # the plain form stands 3-5e-4 from the step there, the kernel 2-3e-4)
    solve = dict(atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(got_o, plain_o, **(solve if alike else F32))
    np.testing.assert_allclose(got_s, plain_s, **(solve if alike else F32))
    want_o, want_s = STEPS(*args)
    tol = solve if alike else dict(atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(got_o, want_o, **tol)
    np.testing.assert_allclose(got_s, want_s, **tol)


def test_a_chunk_of_32_tokens_lays_four_heads_side_by_side():
    """The kernel is written for any chunk of 16 to 128 tokens that divides a
    lane tile (``128 // chunk`` heads a set): chunks of 32, eight heads a grid
    step, a chunk and a half, against the step (chunks of 16 and 128 were held
    to it by hand and compiled for a described v5e: PERF.md section 6, PR 43)."""
    # eight heads: two draws side by side (the heads: axis 1 of the state, 2 of the rest)
    args = tuple(jnp.concatenate([a, b], axis=1 if i == 0 else 2) for i, (a, b) in enumerate(
        zip(_inputs(48, seed=1, rate=1.6), _inputs(48, seed=2, rate=1.6))))
    assert args[1].shape == (B, 48, 2 * H, D) and kda.scan_heads(2 * H, D, D, 32) == 8
    got_o, got_s = kda_scan(*args, 32)
    want_o, want_s = STEPS(*args)
    np.testing.assert_allclose(got_o, want_o, atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(got_s, want_s, atol=5e-5, rtol=1e-4)


def test_operands_rounded_to_bfloat16_fail_the_tolerances():
    """What a default-precision product on the chip does to its operands: the
    same launch with q, k and v rounded to bfloat16 stands twenty times (the
    outputs, which are of order 0.1) and some hundred times (the state) past
    the tolerance the kernel is held to, so a kernel that multiplied in
    bfloat16, or dropped the float32 passes, fails the tests above."""
    state, q, k, v, g, beta = _inputs(160, seed=161)
    want_o, want_s = PLAIN(state, q, k, v, g, beta)
    q, k, v = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (q, k, v))
    got_o, got_s = KERNEL(state, q, k, v, g, beta)
    assert float(jnp.abs(got_o - want_o).max()) > 10 * F32["atol"]
    assert float(jnp.abs(got_s - want_s).max()) > 100 * F32["atol"]
    for got, want in ((got_o, want_o), (got_s, want_s)):
        with pytest.raises(AssertionError):
            np.testing.assert_allclose(got, want, **F32)


def test_tokens_that_are_none_leave_the_state_bit_for_bit_and_rows_have_their_own_lengths():
    """Two rows of one launch, 160 tokens wide: the first whole, the second 75
    real tokens and behind them tokens whose ``beta`` and ``g`` are 0 (the rest
    of their chunk and a whole chunk of nothing). The second row's state and
    real outputs are, every bit, what its 75 tokens alone leave; the first
    row's are what the launch without the padding gives."""
    state, q, k, v, g, beta = _inputs(160, seed=7)
    real = jnp.arange(160)[None, :] < jnp.array([160, 75])[:, None]  # [b, T]
    g_pad, beta_pad = jnp.where(real[..., None, None], g, 0.0), jnp.where(real[..., None], beta, 0.0)
    o_pad, s_pad = KERNEL(state, q, k, v, g_pad, beta_pad)
    o_all, s_all = KERNEL(state, q, k, v, g, beta)
    assert np.array_equal(o_pad[0], o_all[0]) and np.array_equal(s_pad[0], s_all[0])
    alone = tuple(x[1:, :75] for x in (q, k, v, g, beta))
    o_own, s_own = kda_scan(state[1:], *alone, C)
    assert np.array_equal(s_pad[1:], s_own)
    assert np.array_equal(o_pad[1:, :75], o_own)
    assert not np.array_equal(s_pad[1], s_all[1])
    want_o, want_s = STEPS(state[1:], *alone)
    np.testing.assert_allclose(s_own, want_s, atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(o_own, want_o, atol=5e-5, rtol=1e-4)


def test_a_state_carried_in_from_an_earlier_launch_is_the_launch_whole():
    """A prompt's chunk programs hand the state on: 64 tokens, then the other
    96 from the state the first launch left, against the 160 in one launch
    (the same chunks at the same places: the same bits)."""
    state, q, k, v, g, beta = _inputs(160, seed=11)
    ops = (q, k, v, g, beta)
    o_whole, s_whole = KERNEL(state, *ops)
    o_first, s_first = kda_scan(state, *(x[:, :64] for x in ops), C)
    o_rest, s_rest = kda_scan(s_first, *(x[:, 64:] for x in ops), C)
    assert np.array_equal(jnp.concatenate([o_first, o_rest], axis=1), o_whole)
    assert np.array_equal(s_rest, s_whole)
    assert not np.array_equal(s_first, s_whole)
