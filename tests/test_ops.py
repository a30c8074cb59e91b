"""Pallas kernel tests (interpret mode on the CPU suite; native on TPU).

Correctness harness per SURVEY §7: compare against plain-jax references on
small shapes, including gradients through the custom VJP.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import dequantize_int8, quantize_int8, rmsnorm


def _ref_rmsnorm(x, w, eps=1e-5):
    xf = x.astype(jnp.float32)
    s = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * s).astype(x.dtype) * w


def test_rmsnorm_matches_reference():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 16, 128)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
    out = rmsnorm(x, w)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_rmsnorm(x, w)), rtol=1e-5, atol=1e-5
    )


def test_rmsnorm_grads_match_reference():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))

    def loss_kernel(x, w):
        return jnp.sum(rmsnorm(x, w) ** 2)

    def loss_ref(x, w):
        return jnp.sum(_ref_rmsnorm(x, w) ** 2)

    gx1, gw1 = jax.grad(loss_kernel, argnums=(0, 1))(x, w)
    gx2, gw2 = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2), rtol=1e-4, atol=1e-4)


def test_rmsnorm_ragged_rows():
    # row count not divisible by the block size -> single-block path
    x = jnp.ones((3, 5, 128), jnp.float32)
    w = jnp.full((128,), 2.0, jnp.float32)
    out = rmsnorm(x, w)
    np.testing.assert_allclose(np.asarray(out), np.full((3, 5, 128), 2.0), rtol=1e-5)


def test_model_forward_with_fused_rmsnorm():
    """fused_rmsnorm=True produces the same logits as the plain path."""
    from ray_tpu.models import LlamaConfig, forward, init_params

    cfg = LlamaConfig.tiny()
    cfg_fused = LlamaConfig.tiny(fused_rmsnorm=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)), jnp.int32
    )
    ref = forward(params, tokens, cfg)
    fused = forward(params, tokens, cfg_fused)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_int8_quant_roundtrip():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(64, 256)).astype(np.float32) * 3.0)
    q, scales = quantize_int8(x)
    assert q.dtype == jnp.int8 and scales.shape == (64,)
    back = dequantize_int8(q, scales, dtype=jnp.float32)
    err = np.abs(np.asarray(back) - np.asarray(x))
    # absmax int8: max error bounded by scale/2 per row
    bound = np.asarray(scales)[:, None] * 0.5 + 1e-6
    assert (err <= bound).all()


def test_int8_quant_preserves_matmul_quality():
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
    q, s = quantize_int8(w.T)  # per-output-row scales
    w_deq = dequantize_int8(q, s, dtype=jnp.float32).T
    ref = x @ w
    got = x @ w_deq
    rel = np.linalg.norm(np.asarray(got - ref)) / np.linalg.norm(np.asarray(ref))
    assert rel < 0.01, rel


@pytest.mark.parametrize(
    "rows,cols,expect",
    [
        (8192, 3072, 256),  # rmsnorm at Llama-3.2-3B width: the row cap
        (3072, 8192, 128),  # quant at d_ff width: the VMEM budget halves it
        (3072, 32768, 32),
        (24, 1 << 22, 8),  # never below the sublane tile
        (4, 128, 4),  # < 8 rows: one tiny block
    ],
)
def test_pick_block_respects_row_width(rows, cols, expect):
    """256 rows of 8192 bf16 are refused by the v5e compiler (scoped VMEM
    20 MB against 16 MB): the block shrinks with the row width."""
    from ray_tpu.ops._common import BLOCK_ELEMS, BLOCK_ROWS, pick_block

    block = pick_block(rows, cols)
    assert block == expect
    assert rows % block == 0 and block <= BLOCK_ROWS
    assert block * cols <= BLOCK_ELEMS or block <= 8


def test_kernel_attention_raises_off_chip():
    """'splash' is a TPU kernel: asking for it where it cannot run raises
    instead of returning dense attention under its name, and so does a name
    the config does not know."""
    from ray_tpu.models import LlamaConfig, forward, init_params

    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention="splash")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="needs a tpu backend"):
        forward(params, tokens, cfg)
    with pytest.raises(ValueError, match="attention"):
        LlamaConfig.tiny(attention="flash")
