"""A final chunk that carries the decode step writes the slot it activates
into a pool the step has written: compiled at real widths for a described v5e
(``tests/chip_compile.py`` says how, and what that proves), the pool's keys,
values and state are updated where they lie."""

import re

import jax
import jax.numpy as jnp

from tests.chip_compile import (
    _delta_rule_cut,
    _ops_outside_fusions,
    _served_programs,
    native_kernels,
    no_compile_cache,
    one_chip,
)


def test_a_final_chunk_that_carries_the_step_copies_no_leaf_of_the_pool(
        one_chip, no_compile_cache, native_kernels):
    """The engine's ``chunk_final`` of 128 tokens with the pool's decode rows,
    at the Solar cell's shapes (one attention layer, traced on its own behind
    the layers that keep a state, 64 slots of 8,192). The slot's stripe and state
    go into the pool by a plain ``dynamic_update_slice``: as a scatter, which
    keeps the old slice for an index out of bounds, the write read the cache
    the carried step had just written under another shape, and the compiler
    copied Solar's 1.07 GB of keys and of values in and out again, 10 ms of a
    24 ms launch (PERF.md section 6, PR 49). No operation outside a fusion
    yields a copy of a leaf of the pool, and the program's temporaries stay
    far under one."""
    from ray_tpu.llm.engine import programs
    from ray_tpu.models.llama import init_kv_cache
    from ray_tpu.models.patterned import moe_stats_names

    cfg, slots, stripe = _delta_rule_cut(), 64, 8192
    params, cache, tokens = _served_programs(cfg, slots, stripe, one_chip)["decode_step"][1]

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = lambda *shape: sds(jnp.int32, *shape)  # noqa: E731
    one = {k: sds(x.dtype, *x.shape)
           for k, x in jax.eval_shape(lambda: init_kv_cache(cfg, 1, stripe)).items()}
    one["moe_stats"] = i32(len(moe_stats_names(cfg)))
    rows = dict(tokens=tokens, temps=sds(jnp.float32, slots), top_ks=i32(slots),
                keys=sds(jnp.uint32, slots, 2), live=sds(jnp.bool_, slots))
    compiled = jax.jit(programs(cfg)["chunk_final"], donate_argnums=(1, 2)).lower(
        params, cache, one, i32(1, 128), i32(1), i32(1), i32(), sds(jnp.float32), i32(),
        sds(jnp.uint32, 2), rows).compile()
    leaves = {",".join(map(str, x.shape)) for name, x in cache.items() if name != "length"}
    copies = [line.strip()[:160] for _, result, op, line in _ops_outside_fusions(compiled.as_text())
              if op == "copy" and (m := re.match(r"\w+\[([\d,]+)\]", result)) and m.group(1) in leaves]
    assert copies == []
    smallest = min(x.size * x.dtype.itemsize for name, x in cache.items() if name in ("k", "v"))
    assert compiled.memory_analysis().temp_size_in_bytes < max(smallest, 256e6)
