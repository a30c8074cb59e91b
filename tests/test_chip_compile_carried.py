"""A chunk launch that carries the decode step, and Granite's chunk programs at
their widest; compiled at real widths for a described v5e
(``tests/chip_compile.py`` says how, and what that proves)."""

import re

import jax
import jax.numpy as jnp
import pytest

from tests.chip_compile import (
    _SERVED,
    _engine_text,
    _granite_whole,
    _ops_outside_fusions,
    _served_config,
    _served_programs,
    _state_space_cut,
    native_kernels,
    no_compile_cache,
    one_chip,
)


def _carrying_chunk_mid(cfg, slots, stripe, one_chip):
    """The engine's ``chunk_mid`` with one row of 256 tokens and the pool's
    decode rows, as ``JaxEngine._compile`` jits it: (function, donated,
    described arguments)."""
    from ray_tpu.llm.engine import programs
    from ray_tpu.models.llama import init_kv_cache
    from ray_tpu.models.patterned import moe_stats_names

    params, cache, tokens = _served_programs(cfg, slots, stripe, one_chip)["decode_step"][1]

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    one = {k: sds(x.dtype, *x.shape)
           for k, x in jax.eval_shape(lambda: init_kv_cache(cfg, 1, stripe)).items()}
    if cfg.moe_experts:
        one["moe_stats"] = sds(jnp.int32, len(moe_stats_names(cfg)))
    rows = dict(tokens=tokens, temps=sds(jnp.float32, slots), top_ks=sds(jnp.int32, slots),
                keys=sds(jnp.uint32, slots, 2), live=sds(jnp.bool_, slots))
    return programs(cfg)["chunk_mid"], (1, 5), (
        params, (one,), sds(jnp.int32, 1, 256), sds(jnp.int32, 1), sds(jnp.int32, 1), cache, rows)


@pytest.mark.parametrize("served", ["mistral-7b-serve-l16", "laguna-xs.2-serve-l5", "nemotron"])
def test_a_chunk_launch_that_carries_the_decode_step_compiles_for_the_chip(
        served, one_chip, no_compile_cache, native_kernels):
    """The engine's ``chunk_mid`` with the pool's decode rows, at the serving
    cells' shapes: the decode rows' own forms are in the program (the decode
    attention kernel a full and a window layer, the state-space step's kernel
    a block), the rows that multiply by a weight are the chunk's 256 and the
    pool's slots together, an expert model's banks go through one set of
    grouped matmuls a layer for both (the last layer's, which a chunk alone
    never runs, for the decode rows), and the pool's cache, donated, is
    written in place: no operation outside a fusion yields a copy of it."""
    import re

    from ray_tpu.models.llama import init_kv_cache

    if served == "nemotron":
        cfg, slots, stripe = _state_space_cut(), 64, 2048
    else:
        cfg, (slots, stripe, _) = _served_config(served), _SERVED[served]
    text = _engine_text(_carrying_chunk_mid(cfg, slots, stripe, one_chip))
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]

    def under(*scopes):
        return sum(all(s in line for s in scopes) for line in kernels)

    joint = 256 + slots
    assert re.search(r"\[(1,)?%d,\d+\]" % joint, text), "no matmul of the joined rows"
    if served.startswith("mistral"):
        assert under("attn_core") == 1  # one layer body under the loop
        assert f"bf16[{joint},14336]" in text
    elif served.startswith("laguna"):
        assert under("attn_core/global") == 2 and under("attn_core/window") == 3
        assert under("moe_ffn/experts") == 12
    else:
        assert under("attn_core/ssm_mixer/ssm_step") == 5 and under("ssm_mixer/ssm_scan") == 0
        assert under("attn_core", "global") + under("attn_core") - under("ssm_mixer") >= 1
        assert under("moe_ffn/experts") == 10
    pool = tuple(jax.eval_shape(lambda: init_kv_cache(cfg, slots, stripe))["k"].shape)
    whole = re.compile(r"\[%s\]" % ",".join(map(str, pool)))
    copies = [line.strip()[:160] for _, result, op, line in _ops_outside_fusions(text)
              if op == "copy" and whole.search(result)]
    assert copies == []


@pytest.mark.parametrize("rows,width", [(4, 1024), (1, 64)], ids=["widest", "narrowest"])
def test_granite_chunk_programs_fit_at_their_widest(one_chip, no_compile_cache, native_kernels,
                                                    rows, width):
    """The engine's own chunk programs at the cell's shapes: a middle chunk
    of four rows of 1,024 tokens (the scan over four 256-token chunks a layer,
    attention over 4,096-position stripes in key blocks) and a final chunk of
    64 tokens into a 24-slot pool, each well inside what the weights, the pool
    and a 4.9 GB store of snapshots leave of the chip's 16 GB."""
    from ray_tpu.llm.engine import programs
    from ray_tpu.models.llama import init_kv_cache

    cfg = _granite_whole()
    params, cache, _ = _served_programs(cfg, 24, 4096, one_chip)["decode_step"][1]

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    one = {k: sds(x.dtype, *x.shape)
           for k, x in jax.eval_shape(lambda: init_kv_cache(cfg, 1, 4096)).items()}
    fns = programs(cfg)
    i32 = lambda *shape: sds(jnp.int32, *shape)  # noqa: E731
    if rows > 1:
        compiled = jax.jit(fns["chunk_mid"], donate_argnums=(1,)).lower(
            params, tuple(dict(one) for _ in range(rows)), i32(rows, width), i32(rows), i32(rows)
        ).compile()
    else:
        compiled = jax.jit(fns["chunk_final"], donate_argnums=(1, 2)).lower(
            params, cache, one, i32(1, width), i32(1), i32(1), i32(), sds(jnp.float32), i32(),
            sds(jnp.uint32, 2)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.2e9
    assert "ssm_mixer/ssm_scan" in compiled.as_text()
