"""The programs of the compressed-convolutional-attention cut (ZAYA1-8B, 20
layers, 8 of 16 experts held, 64 slots of 4,608) compile at real widths for a
described v5e (``tests/chip_compile.py`` says how, and what that proves): the
decode step over every slot, the engine's decode program with its sampler, a
final chunk that carries the pool's step, and the middle chunk at 1,024 tokens
by one to four rows. The decode kernel walks the stripes of two key-value
heads in blocks of 512 positions (``ops/decode_attention.py block_size``)."""

import jax
import jax.numpy as jnp
import pytest

from tests.chip_compile import (
    _convolved_attention_cut,
    _decode_kernel_blocks,
    _ops_outside_fusions,
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
    # two heads of 2 x 128 bfloat16 numbers a position: 512 positions are the
    # 512 KB a block that eight heads hold in 128
    assert _decode_kernel_blocks(fn, *args) == [("decode_attention", 512)]
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


def test_decode_program_sorts_block_maxima_and_winning_blocks_never_the_vocabulary(
        one_chip, no_compile_cache, native_kernels):
    """The engine's decode program (the step and the sampler over 64 slots of a
    262,272-wide vocabulary): the sampler's selection (``ops/topk.py``) sorts
    the 2,049 block maxima a row and the 64 winning blocks' 8,192 numbers, and
    nothing else under ``sampling`` is as wide as a row but the greedy
    ``argmax``. The maxima leave the head's own fusion (the rows are viewed
    in their tiles of eight, so no copy of the 67 MB of logits stands between
    the head and the sorts)."""
    import re

    from ray_tpu.llm.engine import programs
    from ray_tpu.ops import topk

    cfg = _convolved_attention_cut()
    params, cache, tokens = _served_programs(cfg, SLOTS, STRIPE, one_chip)["decode_step"][1]

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(programs(cfg)["decode_fn"], donate_argnums=(1,)).lower(
        params, cache, tokens, sds(jnp.float32, SLOTS), sds(jnp.int32, SLOTS),
        sds(jnp.uint32, SLOTS, 2)).compile()
    lines = compiled.as_text().splitlines()
    nb, k = cfg.vocab_size // topk.BLOCK, 64
    assert cfg.vocab_size == 262272 and nb == 2049 and topk.two_stage(cfg.vocab_size, k)
    shapes = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = \(?\w+\[([\d,]*)\]", "\n".join(lines), re.M))
    sorted_widths = set()
    for line in lines:
        called = re.search(r" (?:sort|custom-call)\((%[\w.\-]+)", line)
        if called and (" sort(" in line or 'custom_call_target="TopK"' in line):
            sorted_widths.add(max(int(n) for n in shapes[called.group(1)].split(",")))
    assert sorted_widths == {nb, k, k * topk.BLOCK}, sorted_widths
    sampling = [line for line in lines if "/sampling/" in line]
    assert sampling
    # no operation under the scope copies or reshapes the logits
    moved = [line.strip()[:160] for line in sampling
             if re.search(r"= f32\[(64,262272|64,2049,128|8,8,2049,128)\]\S* (copy|reshape|transpose)\(", line)]
    assert moved == []
    # the maxima are an output of the head's fusion
    assert any("f32[8,8,2049]" in line and "f32[64,262272]" in line and "lm_head" in line
               and " fusion(" in line for line in lines)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


def test_a_final_chunk_that_carries_the_step_walks_512_position_blocks_in_place(
        one_chip, no_compile_cache, native_kernels):
    """The engine's ``chunk_final`` of 128 tokens with the pool's decode rows:
    the rows' read of their stripes is the decode kernel under ``beside``, at
    the block the decode program walks (two 1 MB double buffers in VMEM, which
    the chip's compiler takes), the chunk's own attention stays the einsum over
    its row's stripe, and the pool, donated, is written where it lies."""
    import re

    from ray_tpu.llm.engine import programs
    from ray_tpu.models.llama import init_kv_cache
    from ray_tpu.models.patterned import moe_stats_names

    cfg = _convolved_attention_cut()
    params, cache, tokens = _served_programs(cfg, SLOTS, STRIPE, one_chip)["decode_step"][1]

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = lambda *shape: sds(jnp.int32, *shape)  # noqa: E731
    one = {k: sds(x.dtype, *x.shape)
           for k, x in jax.eval_shape(lambda: init_kv_cache(cfg, 1, STRIPE)).items()}
    one["moe_stats"] = i32(len(moe_stats_names(cfg)))
    rows = dict(tokens=tokens, temps=sds(jnp.float32, SLOTS), top_ks=i32(SLOTS),
                keys=sds(jnp.uint32, SLOTS, 2), live=sds(jnp.bool_, SLOTS))
    args = (params, cache, one, i32(1, 128), i32(1), i32(1), i32(), sds(jnp.float32), i32(),
            sds(jnp.uint32, 2), rows)
    fn = programs(cfg)["chunk_final"]
    assert _decode_kernel_blocks(fn, *args) == [("decode_attention", 512)]
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
    text = compiled.as_text()
    assert len(_kernels(text.splitlines(), "beside/attn_core/global/decode_attention")) == 1
    leaves = {",".join(map(str, x.shape)) for name, x in cache.items() if name != "length"}
    copies = [line.strip()[:160] for _, result, op, line in _ops_outside_fusions(text)
              if op == "copy" and (m := re.match(r"\w+\[([\d,]+)\]", result)) and m.group(1) in leaves]
    assert copies == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


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
