"""The programs of the looped model (Ouro-2.6B whole: 48 layers run four times
a token, 12 slots of 384) at real widths for a described v5e
(``tests/chip_compile.py`` says how, and what that proves): the engine's
``decode_fn`` and the final chunk that carries the pool's decode step compile
with the decode kernel and the write kernel (``ops/cache_write.py``: a step's
new keys and values, no scatter under ``kv_write``) traced once each (one layer
body under the loop over layers under the loop over passes), the 7.25 GB cache
riding through both loops and both kernels as a donated carry that no operation
copies, and every pass's end named ``norm/loop_exit``."""

import jax
import jax.numpy as jnp
import pytest

from tests.chip_compile import (
    _decode_kernel_blocks,
    _kv_writes,
    _served_programs,
    native_kernels,
    no_compile_cache,
    one_chip,
)

SLOTS, STRIPE = 12, 384
CACHE = "bf16[192,12,16,384,128]"


def _cfg():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.ouro_2_6b(max_seq_len=STRIPE)


def _sds(one_chip):
    return lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _whole_copies(lines, *shapes):
    return [line.strip()[:120] for line in lines
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(shapes)]


def test_decode_fn_compiles_with_one_kernel_body_and_no_copy_of_the_cache(
        one_chip, no_compile_cache, native_kernels):
    from ray_tpu.llm.engine import programs

    cfg, sds = _cfg(), _sds(one_chip)
    fns = programs(cfg)
    params, cache, tokens = _served_programs(cfg, SLOTS, STRIPE, one_chip)["decode_step"][1]
    args = (params, cache, tokens, sds(jnp.float32, SLOTS), sds(jnp.int32, SLOTS),
            sds(jnp.uint32, SLOTS, 2))
    # sixteen key-value heads of 2 x 128 bfloat16 numbers a position: 8 KB, so
    # 128 positions a block of the walk (PR 54's rule at its floor)
    assert _decode_kernel_blocks(fns["decode_fn"], *args) == [("decode_attention", 128)]
    compiled = jax.jit(fns["decode_fn"], donate_argnums=(1,)).lower(*args).compile()
    memory = compiled.memory_analysis()
    # weights 5.34 GB, stripes 7.25 GB
    assert 12.5e9 < memory.argument_size_in_bytes < 12.7e9
    assert memory.temp_size_in_bytes < 0.2e9
    text = compiled.as_text()
    lines = text.splitlines()
    kernels = [line for line in lines if 'custom_call_target="tpu_custom_call"' in line]
    assert len([k for k in kernels if "attn_core/global/decode_attention" in k]) == 1
    # the twelve new rows of a pass and layer: one call of the write kernel for
    # keys and values, in the place of two scatters of 192 index rows
    written, scattered = _kv_writes(text)
    assert (len(written), scattered) == (1, [])
    for scope in ("attn_qkv", "kv_write", "attn_out", "ffn", "norm/loop_exit", "lm_head",
                  "sampling"):
        assert any(scope in line for line in lines), scope
    assert _whole_copies(lines, CACHE, "bf16[48,2048,5632]", "bf16[48,5632,2048]") == []


def test_a_final_chunk_that_carries_the_step_compiles_and_copies_no_cache(
        one_chip, no_compile_cache, native_kernels):
    """``chunk_final`` of 256 tokens with the pool's decode rows: the rows'
    attention is the one decode kernel under ``beside/attn_core``, the slot's
    stripe goes into the pool by a plain update, and the launch's passes and
    exits ride out in the stripe's ``loop_stats``."""
    from ray_tpu.llm.engine import programs

    cfg, sds = _cfg(), _sds(one_chip)
    fns = programs(cfg)
    served = _served_programs(cfg, SLOTS, STRIPE, one_chip)
    params, cache, _ = served["decode_step"][1]
    _, one, tokens, lengths, starts = served["chunk_mid"][1]
    one = dict(one, loop_stats=sds(jnp.int32, 2 + cfg.loop_passes))
    rows = dict(tokens=sds(jnp.int32, SLOTS), temps=sds(jnp.float32, SLOTS),
                top_ks=sds(jnp.int32, SLOTS), keys=sds(jnp.uint32, SLOTS, 2),
                live=sds(jnp.bool_, SLOTS))
    args = (params, cache, one, tokens, lengths, starts, sds(jnp.int32), sds(jnp.float32),
            sds(jnp.int32), sds(jnp.uint32, 2), rows)
    compiled = jax.jit(fns["chunk_final"], donate_argnums=(1, 2)).lower(*args).compile()
    memory = compiled.memory_analysis()
    # weights 5.34 GB, the pool's stripes 7.25 GB, a scratch stripe 0.60 GB
    assert 13.1e9 < memory.argument_size_in_bytes < 13.3e9
    assert memory.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    lines = text.splitlines()
    kernels = [line for line in lines if 'custom_call_target="tpu_custom_call"' in line]
    assert len([k for k in kernels if "beside/attn_core/global/decode_attention" in k]) == 1
    # the carried rows write through the kernel; the chunk's own 256 tokens as a block
    written, scattered = _kv_writes(text, beside=True)
    assert (len(written), scattered) == (1, [])
    assert _kv_writes(text) == ([], [])
    assert any("norm/loop_exit" in line for line in lines)
    assert _whole_copies(lines, CACHE, "bf16[192,1,16,384,128]") == []
    out = jax.eval_shape(fns["chunk_final"], *args)
    assert out[4].shape == (2, 2 + cfg.loop_passes)  # rows: chunk_mid, chunk_final
