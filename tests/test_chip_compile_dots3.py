"""The programs of the two-width latent cut (dots3-note-prev, layers 0-4, 16
of 256 experts held, 16 slots of 24,576) compile at real widths for a
described v5e (``tests/chip_compile.py`` says how, and what that proves): the
decode step over every slot, a 256-token final chunk behind a long document,
and the probe's one 4,544-token chunk at two rows."""

import jax
import jax.numpy as jnp

from tests.chip_compile import (
    _served_programs,
    _sparse_latent_cut,
    native_kernels,
    no_compile_cache,
    one_chip,
)

SLOTS, STRIPE = 16, 24576


def _kernels(lines, scope):
    return [line for line in lines
            if 'custom_call_target="tpu_custom_call"' in line and scope in line]


def test_decode_step_scores_selects_gathers_and_walks_the_windows(
        one_chip, no_compile_cache, native_kernels):
    """16 rows through 5 layers (two indexed ones traced on their own, the
    three sliding ones as one loop body): the index scores of a stripe, the
    choice of 2,048, the gathered read under ``latent_sparse``; the windows
    through the decode kernel; the held banks through three grouped matmuls a
    traced expert layer (layer 1 and the loop's body).
    The step holds 9.1 GB of arguments (weights 5.15, stripes 3.93) and under
    0.2 GB of temporaries: no stripe and no bank is copied."""
    cfg = _sparse_latent_cut()
    fn, args = _served_programs(cfg, SLOTS, STRIPE, one_chip)["decode_step"]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    memory = compiled.memory_analysis()
    assert 9.0e9 < memory.argument_size_in_bytes < 9.2e9
    assert memory.temp_size_in_bytes < 0.2e9
    lines = compiled.as_text().splitlines()
    assert len(_kernels(lines, "attn_core/latent_window/latent_decode_attention")) == 1
    assert len(_kernels(lines, "moe_ffn/experts")) == 6  # layer 1's and the loop body's
    for scope in ("attn_qkv/attn_index", "attn_core/attn_index", "attn_core/attn_select",
                  "attn_core/latent_sparse", "attn_out/gate"):
        assert any(scope in line for line in lines), scope
    whole = ("bf16[2,16,1,24576,", "bf16[3,16,1,24576,", "bf16[4,16,5120,1536]",
             "bf16[4,16,1536,5120]")
    assert [line.strip()[:120] for line in lines
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(whole)] == []
    # nor is a layer's key or latent stripe sliced out for the gather (a fusion
    # that writes [16, 24576, D]: 0.5 GB a step until the gather took the
    # carried cache itself). What is left is the index scores' operand, a copy
    # of a layer's 50 MB of index keys a step (ROADMAP R11 b)
    sliced = [line.split(" = ", 1)[1].split("{")[0] for line in lines
              if " fusion(" in line and " = bf16[16,24576," in line]
    assert sorted(sliced) == ["bf16[16,24576,128]"] * 2 and all(
        "attn_index" in line for line in lines
        if " fusion(" in line and " = bf16[16,24576," in line)


def test_final_chunk_behind_a_document_fits(one_chip, no_compile_cache, native_kernels):
    """256 tokens a row of one: the index scores of the whole stripe a query
    (25 MB in float32), the choice without a sort (``_kept``), then the chunk
    kernel (``ops/latent_chunk_attention.py``) once a traced latent layer:
    the two indexed ones under the mask, the sliding ones' loop body from the
    window's first block. No block's float32 scores ([1, 128, 256, 1024]:
    134 MB) lie in HBM: the temporaries are 146 MB beside 5.15 GB of weights
    (403 MB with the walk in plain XLA, the parent's program; both figures
    from this compile, PR 52)."""
    cfg = _sparse_latent_cut()
    fn, args = _served_programs(cfg, SLOTS, STRIPE, one_chip)["chunk_final"]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9
    lines = compiled.as_text().splitlines()
    assert not any(" sort(" in line and "attn_select" in line for line in lines)
    assert len(_kernels(lines, "moe_ffn/experts")) == 6
    assert len(_kernels(lines, "attn_core/latent_sparse/latent_chunk_attention")) == 2
    assert len(_kernels(lines, "attn_core/latent_window/latent_chunk_attention")) == 1
    assert not any("f32[1,128,256,1024]" in line for line in lines)


def test_the_probes_one_chunk_of_two_rows_fits_beside_a_resident_engine(
        one_chip, no_compile_cache, native_kernels):
    """``benchmark/compare.py serve_program_logits``: two rows of 4,544 tokens
    in one chunk over a 4,608-position cache, beside an engine that holds
    9.1 GB then (weights and pool: the probe runs before the documents are
    stored). 4,544 tokens are 71 of the chunk kernel's tiles of 64 queries, so
    every latent layer reads through it, absorbed (the expanded form takes one
    tile of queries): the temporaries are 2.9 GB, the absorbed queries and the
    context [2, 128, 4544, 512] 1.2 GB each among them (3.2 GB with the walk in
    plain XLA, whose 4.7 MB of float32 scores a key position held it to blocks
    of 128: ``_LATENT_SCORES_MAX_BYTES``)."""
    from ray_tpu.models.llama import init_kv_cache, prefill

    cfg = _sparse_latent_cut()
    params = _served_programs(cfg, SLOTS, STRIPE, one_chip)["decode_step"][1][0]
    cache = {k: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
             for k, x in jax.eval_shape(lambda: init_kv_cache(cfg, 2, 4608)).items()}
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(lambda p, c, t, n: prefill(p, c, t, cfg, lengths=n)).lower(
        params, cache, i32(2, 4544), i32(2)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3.4e9
    assert len(_kernels(compiled.as_text().splitlines(), "latent_chunk_attention")) == 3
