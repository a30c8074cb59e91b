"""The main path's kernels, the chunk's cache rows and the expert banks;
compiled at real widths for a described v5e (``tests/chip_compile.py`` says
how, and what that proves)."""

import jax
import jax.numpy as jnp
import pytest

from tests.chip_compile import (
    _decode_kernel_blocks,
    _served_config,
    native_kernels,
    no_compile_cache,
    one_chip,
)


def _compiled_text(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


QKV = ((4, 2048, 24, 128), jnp.bfloat16)


def _splash_fwd(q, k, v):
    from ray_tpu.models.llama import _splash_attention

    return _splash_attention(q, k, v)


def _splash_bwd(q, k, v):
    return jax.grad(
        lambda *a: _splash_fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
    )(q, k, v)


def _rmsnorm_fwd(x, w):
    from ray_tpu.ops import rmsnorm

    return rmsnorm(x, w)


def _rmsnorm_grad(x, w):
    # the backward is plain jnp and recomputes from x: the value keeps the
    # forward kernel in the program, as a train step's loss does
    return jax.value_and_grad(
        lambda *a: _rmsnorm_fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1)
    )(x, w)


def _quantize(x):
    from ray_tpu.ops.quant import quantize_int8

    return quantize_int8(x)


def _dequantize(q, s):
    from ray_tpu.ops.quant import dequantize_int8

    return dequantize_int8(q, s)


def _grouped_matmul(rows, bank, sizes):
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    return grouped_matmul(rows, bank, sizes)


def _decode_attention(q, ck_all, cv_all, layer, lo, hi):
    from ray_tpu.ops.decode_attention import decode_attention

    return decode_attention(q, ck_all, cv_all, layer, lo, hi)


def _latent_decode_attention(q_rope, q_latent, ck_all, cv_all, layer, lo, hi):
    from ray_tpu.ops.decode_attention import latent_decode_attention

    return latent_decode_attention(q_rope, q_latent, ck_all, cv_all, layer, lo, hi, 192 ** -0.5)


def _ssm_step_in_place(state_all, layer, x, dt, a, B, C, D):
    from ray_tpu.ops.ssm import ssm_step_in_place

    return ssm_step_in_place(state_all, layer, x, dt, a, B, C, D)


def _ssm_step_shapes(layers=5, slots=64, heads=128, width=64, state=128, groups=8):
    """One new token a slot on the Nemotron-3-Super cell's stacked state: 128
    heads of [64, 128] float32 in 8 groups, the layer's row a scalar."""
    f32 = jnp.float32
    return (
        ((layers, slots, heads, width, state), f32), ((), jnp.int32), ((slots, heads, width), f32),
        ((slots, heads), f32), ((heads,), f32), ((slots, groups, state), f32),
        ((slots, groups, state), f32), ((heads,), f32),
    )


def _kda_step_in_place(state_all, layer, q, k, v, g, beta):
    from ray_tpu.ops.kda import kda_step_in_place

    return kda_step_in_place(state_all, layer, q, k, v, g, beta)


def _kda_step_shapes(layers=3, slots=64, heads=64, width=128):
    """One new token a slot on the Solar-Open2 cell's stacked state: 64 heads
    of [128, 128] float32, the layer's row a scalar."""
    f32 = jnp.float32
    a_head = ((slots, heads, width), f32)
    return (((layers, slots, heads, width, width), f32), ((), jnp.int32), a_head, a_head, a_head,
            a_head, ((slots, heads), f32))


def _kda_scan(state, q, k, v, g, beta):
    from ray_tpu.ops.kda import kda_scan

    return kda_scan(state, q, k, v, g, beta, 64)


def _kda_scan_shapes(rows=4, tokens=1024, heads=64, width=128):
    """The Solar-Open2 cell's widest chunk launch on the delta rule alone: four
    rows of 1,024 tokens, 64 heads of [128, 128] float32, chunks of 64."""
    f32 = jnp.float32
    a_token = ((rows, tokens, heads, width), f32)
    return (((rows, heads, width, width), f32), a_token, a_token, a_token, a_token,
            ((rows, tokens, heads), f32))


def _latent_decode_attention_shapes(layers=5, slots=24, stripe=24576, heads=32):
    """One new token a slot over the Kanana-2 cell's cache: 32 query heads on
    one shared key in two leaves, the rotated key in a 128-lane row (a 64-wide
    row is refused: its copies would take half a lane tile) and the 512-wide
    latent, which is the value too."""
    bounds = ((slots,), jnp.int32)
    return (
        ((slots, heads, 128), jnp.bfloat16), ((slots, heads, 512), jnp.bfloat16),
        ((layers, slots, 1, stripe, 128), jnp.bfloat16),
        ((layers, slots, 1, stripe, 512), jnp.bfloat16), ((), jnp.int32), bounds, bounds,
    )


def _decode_attention_shapes(layers, stripe, heads, slots=32, kv_heads=8):
    """One new token a slot over the serving cells' caches: ``heads`` query
    heads over ``kv_heads`` key-value heads of width 128."""
    cache = ((layers, slots, kv_heads, stripe, 128), jnp.bfloat16)
    bounds = ((slots,), jnp.int32)
    return (((slots, heads, 128), jnp.bfloat16), cache, cache, ((), jnp.int32), bounds, bounds)


# a 256-token chunk's 2,048 assignments over four stacked banks of 256 experts
# (Laguna-XS.2: 2,048 x 512 up, 512 x 2,048 down)
GROUPS = ((4 * 256,), jnp.int32)
X_NORM = ((8192, 3072), jnp.bfloat16)
W_NORM = ((3072,), jnp.bfloat16)

KERNELS = {
    "splash_fwd": (_splash_fwd, (QKV, QKV, QKV)),
    "splash_bwd": (_splash_bwd, (QKV, QKV, QKV)),
    "rmsnorm_fwd": (_rmsnorm_fwd, (X_NORM, W_NORM)),
    "rmsnorm_grad": (_rmsnorm_grad, (X_NORM, W_NORM)),
    "grouped_matmul_up": (
        _grouped_matmul,
        (((2048, 2048), jnp.bfloat16), ((1024, 2048, 512), jnp.bfloat16), GROUPS),
    ),
    "grouped_matmul_down": (
        _grouped_matmul,
        (((2048, 512), jnp.bfloat16), ((1024, 512, 2048), jnp.bfloat16), GROUPS),
    ),
    # Mistral-7B's 4 query heads a key-value head; Laguna-XS.2's 6 in a full
    # layer and 8 in a sliding one
    "decode_attention_4_a_group": (_decode_attention, _decode_attention_shapes(16, 1024, 32)),
    "decode_attention_6_a_group": (_decode_attention, _decode_attention_shapes(5, 4096, 48)),
    "decode_attention_8_a_group": (_decode_attention, _decode_attention_shapes(5, 4096, 64)),
    # two key-value heads, whose block is 512 positions: ZAYA1-8B's 4 query
    # heads a key-value head over 64 slots of 4,608, Nemotron-3-Super's 16
    # over 64 slots of 2,048
    "decode_attention_4_a_group_of_2_heads": (
        _decode_attention, _decode_attention_shapes(20, 4608, 8, slots=64, kv_heads=2)),
    "decode_attention_16_a_group_of_2_heads": (
        _decode_attention, _decode_attention_shapes(1, 2048, 32, slots=64, kv_heads=2)),
    "latent_decode_attention_32_on_one_key": (
        _latent_decode_attention, _latent_decode_attention_shapes()),
    "ssm_step_in_place": (_ssm_step_in_place, _ssm_step_shapes()),
    "kda_step_in_place": (_kda_step_in_place, _kda_step_shapes()),
    "kda_scan": (_kda_scan, _kda_scan_shapes()),
    "quantize_int8": (_quantize, (((3072, 8192), jnp.bfloat16),)),
    "dequantize_int8": (
        _dequantize,
        (((3072, 8192), jnp.int8), ((3072,), jnp.float32)),
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache, native_kernels):
    fn, shapes = KERNELS[name]
    text = _compiled_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text, f"{name}: no Pallas kernel in the program"


@pytest.mark.parametrize("name, block", [
    ("decode_attention_4_a_group", 128), ("decode_attention_6_a_group", 128),
    ("decode_attention_8_a_group", 128), ("decode_attention_4_a_group_of_2_heads", 512),
    ("decode_attention_16_a_group_of_2_heads", 512), ("latent_decode_attention_32_on_one_key", 512)])
def test_decode_kernel_walks_the_block_its_cache_gives(name, block):
    """Traced at the cells' shapes: 512 KB of keys and values a block at two
    heads as at eight, the latent by its stripe."""
    fn, shapes = KERNELS[name]
    [(_, traced)] = _decode_kernel_blocks(fn, *(jax.ShapeDtypeStruct(*shape) for shape in shapes))
    assert traced == block


def test_chunk_mid_writes_its_cache_rows_without_a_scatter(one_chip, no_compile_cache):
    """The engine's ``chunk_mid`` body at the serving cell's widths
    (Mistral-7B-v0.3, 16 layers, one 1,024-position stripe, a 256-token
    chunk): the chunk's 2,048 key and value rows a layer go into the cache
    as contiguous blocks, in place in the layer loop's carried cache. A
    general scatter there cost 4.8 of the program's 18.2 ms on the chip
    (PERF.md section 6, PR 27)."""
    from ray_tpu.models.llama import init_kv_cache, init_params, prefill

    cfg = _served_config("mistral-7b-serve-l16")

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    params = described(lambda: init_params(jax.random.PRNGKey(0), cfg))
    stripe = described(lambda: init_kv_cache(cfg, 1, 1024))
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)

    def chunk_mid(params, stripe, tokens, length, start):
        _, stripe = prefill(
            params, stripe, tokens, cfg, lengths=length, start_pos=start,
            with_logits=False,
        )
        return stripe

    text = (
        jax.jit(chunk_mid, donate_argnums=(1,))
        .lower(params, stripe, tokens, scalar, scalar)
        .compile()
        .as_text()
    )
    assert "scatter(" not in text
    updates = [
        line for line in text.splitlines()
        if "dynamic-update-slice(" in line and "bf16[16,1,8,1024,128]" in line
    ]
    assert len(updates) == 2, updates
    assert all("while/body" in line for line in updates), updates


def test_patterned_chunk_mid_keeps_its_expert_banks_in_place(
        one_chip, no_compile_cache, native_kernels):
    """The engine's ``chunk_mid`` body at the Laguna-XS.2 cell's widths (5
    layers, a 4,096-position stripe, a 256-token chunk): the grouped matmuls
    are Pallas kernels under ``moe_ffn/experts``, they take the stacked banks
    whole (a layer's slice handed to a kernel was a 1.6 GB copy a layer: 3.9
    GB of temporaries), and the program fits beside 7.7 GB of weights."""
    from ray_tpu.models.llama import init_kv_cache, init_params, prefill

    cfg = _served_config("laguna-xs.2-serve-l5")

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    params = described(lambda: init_params(jax.random.PRNGKey(0), cfg))
    stripe = described(lambda: init_kv_cache(cfg, 1, 4096))
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)

    def chunk_mid(params, stripe, tokens, length, start):
        return prefill(params, stripe, tokens, cfg, lengths=length, start_pos=start,
                       with_logits=False)[1]

    compiled = (
        jax.jit(chunk_mid, donate_argnums=(1,))
        .lower(params, stripe, tokens, scalar, scalar).compile()
    )
    kernels = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all("moe_ffn/experts" in line for line in kernels), kernels[:2]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
