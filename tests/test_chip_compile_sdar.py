"""The programs of the block-diffusion cut (SDAR-30B-A3B-Chat, layers 0-5, all
128 experts, 64 slots of 4,096) at real widths for a described v5e
(``tests/chip_compile.py`` says how, and what that proves): the engine's
``block_step`` (a forward of a block of 4 a slot with its sampler and the next
block state) compiles, its attention one decode kernel whose rows are the
block's four queries beside each key-value head's eight query heads; the chunk
programs lower under the block mask, the final one with no head and no
sampler of its own; the final chunk that carries the pool's block step
compiles with the rows' one folded kernel and copies no stripe, cache or
bank."""

import jax
import jax.numpy as jnp
import pytest

from tests.chip_compile import (
    _block_diffusion_cut as _cut,
    _decode_kernel_blocks,
    _served_programs,
    native_kernels,
    no_compile_cache,
    one_chip,
)

SLOTS, STRIPE = 64, 4096


def _described(one_chip, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)


def test_block_step_compiles_with_one_folded_kernel_and_no_copy_of_a_stripe_or_a_bank(
        one_chip, no_compile_cache, native_kernels):
    from ray_tpu.llm.engine import programs

    cfg = _cut()
    fns = programs(cfg)
    params, cache, _ = _served_programs(cfg, SLOTS, STRIPE, one_chip)["decode_step"][1]
    block = _described(one_chip, jax.eval_shape(lambda: fns["new_block"](SLOTS)))

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (params, cache, block, sds(jnp.float32, SLOTS), sds(jnp.int32, SLOTS),
            sds(jnp.uint32, SLOTS, 2))
    # four key-value heads of 2 x 128 bfloat16 numbers a position: 256
    # positions are the 512 KB a block of the walk holds
    assert _decode_kernel_blocks(fns["block_step"], *args) == [("decode_attention", 256)]
    compiled = jax.jit(fns["block_step"], donate_argnums=(1, 5)).lower(*args).compile()
    memory = compiled.memory_analysis()
    # weights 8.72 GB, stripes 3.22 GB
    assert 11.8e9 < memory.argument_size_in_bytes < 12.1e9
    assert memory.temp_size_in_bytes < 0.2e9  # the 256 rows of float32 logits, once
    lines = compiled.as_text().splitlines()
    kernels = [line for line in lines if 'custom_call_target="tpu_custom_call"' in line]
    assert len([k for k in kernels if "attn_core/block/decode_attention" in k]) == 1
    assert len([k for k in kernels if "moe_ffn/experts" in k]) == 3
    for scope in ("attn_qkv", "kv_write", "moe_ffn/router", "lm_head",
                  "sampling/confidence", "sampling/unmask"):
        assert any(scope in line for line in lines), scope
    whole = ("bf16[6,64,4,4096,128]", "bf16[6,128,2048,768]", "bf16[6,128,768,2048]",
             "bf16[768,2048,768]", "bf16[768,768,2048]")
    assert [line.strip()[:120] for line in lines
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(whole)] == []


def test_a_final_chunk_that_carries_the_block_step_holds_one_folded_kernel_and_copies_nothing(
        one_chip, no_compile_cache, native_kernels):
    """The engine's ``chunk_final`` of 256 tokens with the pool's block state
    and sampler arrays (``llm/engine.py programs``: a forward of every slot's
    block rides through the chunk's read of the banks): the rows' attention
    is one folded decode kernel a layer, under ``beside/attn_core/block``, the
    grouped matmuls are the chunk's three (the 256 block rows are rows of
    them), and no operation yields a copy of a stripe, of the pool's cache or
    of a bank: the slot's stripe goes into the pool the rows have written by
    a plain update (``tests/test_chip_compile_carried_final.py``)."""
    from ray_tpu.llm.engine import programs
    from ray_tpu.models.patterned import moe_stats_names

    cfg = _cut()
    fns = programs(cfg)
    served = _served_programs(cfg, SLOTS, STRIPE, one_chip)
    params, cache, _ = served["decode_step"][1]
    _, one, tokens, lengths, starts = served["chunk_mid"][1]

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    one = dict(one, moe_stats=sds(jnp.int32, len(moe_stats_names(cfg))))
    rows = dict(block=_described(one_chip, jax.eval_shape(lambda: fns["new_block"](SLOTS))),
                temps=sds(jnp.float32, SLOTS), top_ks=sds(jnp.int32, SLOTS),
                keys=sds(jnp.uint32, SLOTS, 2), live=sds(jnp.bool_, SLOTS))
    args = (params, cache, one, tokens, lengths, starts, sds(jnp.int32), sds(jnp.float32),
            sds(jnp.int32), sds(jnp.uint32, 2), rows)
    assert _decode_kernel_blocks(fns["chunk_final"], *args) == [("decode_attention", 256)]
    compiled = jax.jit(fns["chunk_final"], donate_argnums=(1, 2)).lower(*args).compile()
    memory = compiled.memory_analysis()
    # weights 8.72 GB, the pool's stripes 3.22 GB, a scratch stripe 50 MB
    assert 11.8e9 < memory.argument_size_in_bytes < 12.2e9
    assert memory.temp_size_in_bytes < 0.3e9  # the rows' float32 logits and the chunk's own
    lines = compiled.as_text().splitlines()
    kernels = [line for line in lines if 'custom_call_target="tpu_custom_call"' in line]
    assert len([k for k in kernels if "beside/attn_core/block/decode_attention" in k]) == 1
    assert len([k for k in kernels if "decode_attention" in k]) == 1
    assert len([k for k in kernels if "moe_ffn/experts" in k]) == 3
    for scope in ("beside/kv_write", "beside/lm_head", "beside/sampling/confidence",
                  "beside/sampling/unmask"):
        assert any(scope in line for line in lines), scope
    whole = ("bf16[6,64,4,4096,128]", "bf16[6,1,4,4096,128]", "bf16[6,128,2048,768]",
             "bf16[6,128,768,2048]", "bf16[768,2048,768]", "bf16[768,768,2048]")
    assert [line.strip()[:120] for line in lines
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(whole)] == []


@pytest.mark.parametrize("name", ["chunk_mid", "chunk_final"])
def test_chunk_programs_lower_under_the_block_mask(one_chip, native_kernels, name):
    """The engine's chunk programs of this model, 256 tokens over a scratch
    stripe: the final one projects no logits and samples nothing (the only
    vocabulary-wide operation of either is the embedding's gather)."""
    from ray_tpu.llm.engine import programs
    from ray_tpu.models.patterned import moe_stats_names

    cfg = _cut()
    fns = programs(cfg)
    params, one, tokens, lengths, starts = _served_programs(
        cfg, SLOTS, STRIPE, one_chip)["chunk_mid"][1]
    one = dict(one, moe_stats=jax.ShapeDtypeStruct(
        (len(moe_stats_names(cfg)),), jnp.int32, sharding=one_chip))

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if name == "chunk_mid":
        lowered = jax.jit(fns[name]).lower(params, (one,), tokens, lengths, starts)
    else:
        cache = _served_programs(cfg, SLOTS, STRIPE, one_chip)["decode_step"][1][1]
        lowered = jax.jit(fns[name]).lower(
            params, cache, one, tokens, lengths, starts, sds(jnp.int32), sds(jnp.float32),
            sds(jnp.int32), sds(jnp.uint32, 2))
    text = lowered.as_text()
    assert "151936" in text  # the table is there
    assert "x151936xf32" not in text  # and no row of logits
