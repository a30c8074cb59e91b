"""The programs of the compressed-convolutional-attention cut (ZAYA1-8B, 20
layers, 8 of 16 experts held, 64 slots of 4,608) compile at real widths for a
described v5e (``tests/chip_compile.py`` says how, and what that proves): the
decode step over every slot and the middle chunk at 1,024 tokens by one to
four rows."""

import jax
import jax.numpy as jnp
import pytest

from tests.chip_compile import (
    _convolved_attention_cut,
    _served_programs,
    native_kernels,
    no_compile_cache,
    one_chip,
)

SLOTS, STRIPE = 64, 4608


def _kernels(lines, scope):
    return [line for line in lines
            if 'custom_call_target="tpu_custom_call"' in line and scope in line]


def test_decode_step_reads_stripes_and_banks_through_their_kernels_and_the_tails_in_place(
        one_chip, no_compile_cache, native_kernels):
    """64 rows through 20 layers traced as one loop body: the stripes of 2
    heads of 128 through the decode kernel, the held banks through three
    grouped matmuls, both convolutions and the tail's read and write under
    ``attn_qkv/cca_conv``; the step holds 11.4 GB of arguments (weights 5.35,
    stripes 6.04, tails 0.007) and next to no temporary: nothing of a stripe's
    or a bank's size is copied, and the tail leaf is updated where it lies."""
    cfg = _convolved_attention_cut()
    fn, args = _served_programs(cfg, SLOTS, STRIPE, one_chip)["decode_step"]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    memory = compiled.memory_analysis()
    assert 11.3e9 < memory.argument_size_in_bytes < 11.5e9
    assert memory.temp_size_in_bytes < 0.1e9
    lines = compiled.as_text().splitlines()
    assert len(_kernels(lines, "attn_core/global/decode_attention")) == 1
    assert len(_kernels(lines, "moe_ffn/experts")) == 3
    assert any("attn_qkv/cca_conv" in line for line in lines)
    assert any("moe_ffn/router" in line for line in lines)
    whole = ("bf16[20,64,1,2688]", "bf16[20,64,2,4608,128]", "bf16[20,8,2048,2048]")
    assert [line.strip()[:120] for line in lines
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(whole)] == []


@pytest.mark.parametrize("rows", [1, 2, 4])
def test_middle_chunk_fits_at_every_row_count(rows, one_chip, no_compile_cache, native_kernels):
    """``rows`` rows of 1,024 tokens: each row's block write, attention over
    its 4,608-position stripe (scores of four rows: 0.6 GB in float32, under
    the bound that switches to blocks of key positions), k = 1 over 8 held
    experts (a block of all 1,024 x rows sorted rows: twice the half expected),
    the tails cut at each row's own end. Temporaries under 1 GB beside 5.35 GB
    of weights and 6.05 GB of pool."""
    from ray_tpu.models.llama import prefill

    cfg = _convolved_attention_cut()
    params, stripe, _, _, _ = _served_programs(cfg, SLOTS, STRIPE, one_chip)["chunk_mid"][1]
    stripes = {
        k: jax.ShapeDtypeStruct((rows,) if k == "length" else (v.shape[0], rows) + v.shape[2:],
                                v.dtype, sharding=one_chip)
        for k, v in stripe.items()
    }
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda p, o, t, n, s: prefill(p, o, t, cfg, lengths=n, start_pos=s, with_logits=False)[1],
        donate_argnums=(1,),
    ).lower(params, stripes, i32(rows, 1024), i32(rows), i32(rows)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
    lines = compiled.as_text().splitlines()
    assert len(_kernels(lines, "moe_ffn/experts")) == 3
    assert any("attn_qkv/cca_conv" in line for line in lines)
