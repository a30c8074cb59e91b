"""The delta-rule chunk programs fit at their widest and work on a block of the
held assignments; compiled at real widths for a described v5e
(``tests/chip_compile.py`` says how, and what that proves)."""

import re

import jax
import jax.numpy as jnp
import pytest

from tests.chip_compile import (
    _delta_rule_cut,
    _served_programs,
    native_kernels,
    no_compile_cache,
    one_chip,
)


@pytest.mark.parametrize("rows,width", [(4, 1024), (1, 128)], ids=["widest", "narrowest"])
def test_delta_rule_middle_chunk_fits_at_its_widest(
        rows, width, one_chip, no_compile_cache, native_kernels):
    """The cell's widest launch (four rows of 1,024 tokens, the chunked rule
    over sixteen 64-token chunks a row and layer, attention over the
    8,192-position stripes in blocks of 512 key positions: scores of the
    whole stripes would be 8.6 GB, and the launch did not load beside 6.6 GB
    of weights and a 3 GB pool): temporaries under 3.5 GB (3.18 with the
    chunked rule as a graph, 1.62 since it is a kernel). The chunked rule is
    one ``kda_scan`` kernel a layer, three a launch, under the scope the
    roofline share reads: Mosaic takes its blocks (a set of heads' [64, 256]
    slabs of the projections where they lie), its float32 products at the
    highest precision and its VMEM; and the same kernel at the narrowest
    final chunk, one row of 128 tokens."""
    from ray_tpu.models.llama import prefill

    cfg = _delta_rule_cut()
    params, stripe, _, _, _ = _served_programs(cfg, 64, 8192, one_chip)["chunk_mid"][1]
    stripes = {
        k: jax.ShapeDtypeStruct((rows,) if k == "length" else (v.shape[0], rows) + v.shape[2:],
                                v.dtype, sharding=one_chip)
        for k, v in stripe.items()
    }
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda p, o, t, n, s: prefill(p, o, t, cfg, lengths=n, start_pos=s, with_logits=False)[1],
        donate_argnums=(1,),
    ).lower(params, stripes, i32(rows, width), i32(rows), i32(rows)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5e9
    lines = compiled.as_text().splitlines()
    kernels = [line for line in lines if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("attn_core/kda_mixer/kda_scan" in line for line in kernels) == 3
    # nothing else of an operand's size runs under its scope: the kernel reads
    # the projections where the convolution and the decay's matmul wrote them
    whole = (f"f32[{rows},{width},8192]", f"f32[{rows},{width},64,128]")
    assert [line.strip()[:120] for line in lines
            if "kda_mixer/kda_scan" in line and (" fusion(" in line or " copy(" in line)
            and line.split(" = ", 1)[-1].startswith(whole)] == []


def test_delta_rule_chunk_works_on_a_block_of_the_held_assignments(
        one_chip, no_compile_cache, native_kernels):
    """A row of 1,024 tokens makes 8,192 assignments, of which an eighth falls
    on the 40 experts held: the chunk's expert scope works on a block of 2,048
    sorted rows under one loop, so nothing there is 8,192 rows of the model's
    width in float32 (the parent's kernel output, its select, its gather back
    to token order and the operand of the sum over a token's choices were:
    134 MB each, 5 of a chunk's 12.5 ms), nor 8,192 rows of it in bfloat16
    (the parent's gather of each assignment's token); the grouped matmuls
    are still three a layer under the scope."""
    from ray_tpu.models.llama import prefill
    from ray_tpu.models.patterned import held_block

    cfg = _delta_rule_cut()
    assert held_block(1024 * 8, 40, 320) == 2048 and held_block(64 * 8, 40, 320) == 128
    params, stripe, _, _, _ = _served_programs(cfg, 64, 8192, one_chip)["chunk_mid"][1]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    lines = jax.jit(
        lambda p, o, t, n, s: prefill(p, o, t, cfg, lengths=n, start_pos=s, with_logits=False)[1],
        donate_argnums=(1,),
    ).lower(params, stripe, i32(1, 1024), i32(1), i32(1)).compile().as_text().splitlines()
    experts = [line for line in lines if "moe_ffn/experts" in line]
    assert [line.strip()[:160] for line in experts
            if re.search(r"(f32|bf16)\[8192,4096\]|f32\[1024,8,4096\]", line)] == []
    assert any("f32[2048,4096]" in line for line in experts)
    kernels = [line for line in experts if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) in (3, 9)  # one loop body over the layers, or a body each
