"""What the ``test_chip_compile_*`` files share: the described chip, the
kernels steered to lower for it, and the served configurations' programs.

No chip is attached here: the TPU compiler that ships with jaxlib compiles
for a ``v5e:2x2`` topology that is only described, and refuses what the chip
would refuse (block shapes the tiling cannot take, more scoped VMEM than a
kernel may use). Nothing runs, so these tests say nothing about results or
speed; interpret-mode correctness lives in ``tests/test_ops.py``.

The process that describes the topology loads libtpu and keeps it. The files
are several so that xdist's workers can share them (one file was a worker's
for 700 s), which needs ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` as the tier-1 command
sets it: without it the second process to describe the topology is refused
the lock and its file skips. The topology is described inside a fixture,
never at import, in a ``skipif`` or in ``parametrize``.
"""

import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def native_kernels(monkeypatch):
    """``interpret()`` asks ``jax.default_backend()``, which is the CPU here:
    steer the kernel modules to lower for the chip. ``ray_tpu.ops`` rebinds
    the name ``rmsnorm`` to the function, so the module comes from
    ``sys.modules``."""
    import ray_tpu.ops  # noqa: F401 — loads the kernel modules

    import ray_tpu.ops.grouped_matmul  # noqa: F401 — not in the package's __init__

    import ray_tpu.ops.decode_attention  # noqa: F401 — nor this one

    import ray_tpu.ops.ssm  # noqa: F401 — nor this one

    import ray_tpu.ops.kda  # noqa: F401 — nor this one

    import ray_tpu.ops.latent_chunk_attention  # noqa: F401 — nor this one

    import ray_tpu.ops.cache_write  # noqa: F401 — nor this one

    for name in ("ray_tpu.ops.rmsnorm", "ray_tpu.ops.quant", "ray_tpu.ops.grouped_matmul",
                 "ray_tpu.ops.decode_attention", "ray_tpu.ops.ssm", "ray_tpu.ops.kda",
                 "ray_tpu.ops.latent_chunk_attention", "ray_tpu.ops.cache_write"):
        monkeypatch.setattr(sys.modules[name], "interpret", lambda: False)


_SERVED = {
    # the serving cells' configurations: (slots, stripe), and the width e of
    # a layer's projection slice [1, e, h, 128]
    "mistral-7b-serve-l16": (32, 1024, 4096),
    "laguna-xs.2-serve-l5": (32, 4096, 2048),
}


def _served_config(name):
    from ray_tpu.models.llama import LlamaConfig

    if name.startswith("kanana"):
        return LlamaConfig.kanana2_30b_a3b(n_layers=5, max_seq_len=24576)
    if name.startswith("laguna"):
        return LlamaConfig.laguna_xs2(n_layers=5, max_seq_len=4096)
    return LlamaConfig(
        vocab_size=32768, d_model=4096, n_layers=16, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=1024, rope_theta=1e6, dtype=jnp.bfloat16,
    )


def _ops_outside_fusions(text):
    """(computation, result type, operation, line) of every instruction of
    the compiled text that is not inside a fusion's own computation: the
    entry computation's, a loop body's."""
    import re

    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.\-]+)", text))
    computation = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            head = line.split()
            computation = head[1] if head[0] == "ENTRY" else head[0]
            continue
        if computation in fused or " = " not in line:
            continue
        rest = line.split(" = ", 1)[1]
        depth = 0
        for end, ch in enumerate(rest):  # the result's type: an array or a tuple
            depth += (ch == "(") - (ch == ")")
            if ch == " " and depth == 0:
                break
        yield computation, rest[:end], rest[end + 1:].split("(", 1)[0], line


def _served_programs(cfg, slots, stripe, one_chip, relaid=True):
    """``decode_step`` over every slot, and the 256-token ``prefill`` without
    and with logits (the engine's ``chunk_mid`` and ``chunk_final`` bodies):
    name -> (function, described arguments), the parameters in the formats
    the engine's rule gives (``models/llama.py serving_layouts``) or, with
    ``relaid=False``, in the default ones."""
    from jax.experimental.layout import Format, Layout

    from ray_tpu.models.llama import (
        decode_step, init_kv_cache, init_params, prefill, serving_layouts,
    )

    def described(make, relaid):
        tree = jax.eval_shape(make)
        orders = serving_layouts(tree) if relaid else {}
        return {
            k: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=(
                Format(Layout(major_to_minor=orders[k]), one_chip) if k in orders else one_chip))
            for k, x in tree.items()
        }

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = described(lambda: init_params(jax.random.PRNGKey(0), cfg), relaid)
    chunk = (params, described(lambda: init_kv_cache(cfg, 1, stripe), False),
             i32(1, 256), i32(1), i32(1))
    return {
        "decode_step": (
            lambda p, c, t: decode_step(p, c, t, cfg),
            (params, described(lambda: init_kv_cache(cfg, slots, stripe), False), i32(slots)),
        ),
        "chunk_mid": (
            lambda p, o, t, n, s: prefill(p, o, t, cfg, lengths=n, start_pos=s,
                                          with_logits=False)[1], chunk),
        "chunk_final": (
            lambda p, o, t, n, s: prefill(p, o, t, cfg, lengths=n, start_pos=s), chunk),
    }


def _decode_kernel_blocks(fn, *args):
    """(kernel's name, positions a block of its walk) of every call of
    ``ops/decode_attention.py``'s kernel in ``fn`` traced with ``args``, loop
    bodies and all: the block is the kernel's double buffer of keys
    ``[2, K, block, D]``, which a compiled program's text no longer shows."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call" and "decode_attention" in (eqn.params["name"] or ""):
                yield eqn.params["name"], eqn.params["grid_mapping"].scratch_avals[0].shape[2]
            for value in eqn.params.values():
                for inner in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(inner, "jaxpr", inner)  # a closed one's own
                    if hasattr(inner, "eqns"):
                        yield from calls(inner)

    return sorted(calls(jax.make_jaxpr(fn)(*args).jaxpr))


def _kv_writes(text, beside=False):
    """(the write kernel's calls, the scatters) among a compiled program's
    instructions named under ``kv_write`` (a decode program's rows, or a
    chunk's own) or, with ``beside``, under ``beside/kv_write`` (the decode
    rows a chunk program carries): a decode step's new keys and values go
    through ``ops/cache_write.py``'s kernel, one call a traced layer for both
    tensors, and leave no scatter there."""
    named = [line for line in text.splitlines()
             if "/kv_write/" in line and ("beside/kv_write/" in line) == beside]
    return ([line for line in named
             if 'custom_call_target="tpu_custom_call"' in line and "cache_write_rows" in line],
            [line for line in named if " scatter(" in line])


def _program_text(program):
    fn, args = program
    return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()


def _state_space_cut():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.nemotron3_super(
        n_layers=11, moe_experts_held=128, vocab_size=32768, max_seq_len=2048)


def _delta_rule_cut():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.solar_open2_250b(
        n_layers=4, gqa_layers=(3,), moe_experts_held=40, vocab_size=24576, max_seq_len=8192)


def _convolved_attention_cut():
    """The cut the cell ``zaya1-8b-serve-long-chat`` serves: layers 0-19 of
    40, experts 0-7 of the router's 16, the whole vocabulary."""
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.zaya1_8b(n_layers=20, moe_experts_held=8, max_seq_len=4608)


def _sparse_latent_cut():
    """The cut the cell ``dots3-note-serve-docs-shared`` serves: layers 0-4 of
    46, experts 0-15 of the router's 256, an eighth of the vocabulary."""
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.dots3_note_prev(
        n_layers=5, moe_experts_held=16, vocab_size=19008, max_seq_len=24576)


def _block_diffusion_cut():
    """The cut the cell ``sdar-30b-a3b-serve-block-chat`` serves: layers 0-5
    of 48, all 128 experts, the whole vocabulary."""
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.sdar_30b_a3b(n_layers=6, max_seq_len=4096)


def _engine_programs(served, one_chip, rows=1):
    """The engine's own program bodies at a serving cell's shapes, as
    ``JaxEngine._compile`` jits them: name -> (function, donated, described
    arguments), the middle chunk with ``rows`` rows of 256 tokens."""
    from ray_tpu.llm.engine import programs
    from ray_tpu.models.llama import init_kv_cache

    cfg = _served_config(served)
    slots, stripe, _ = _SERVED[served]
    params, cache, tokens = _served_programs(cfg, slots, stripe, one_chip)["decode_step"][1]

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def i32(*shape):
        return sds(jnp.int32, *shape)

    one = {k: sds(x.dtype, *x.shape)
           for k, x in jax.eval_shape(lambda: init_kv_cache(cfg, 1, stripe)).items()}
    if cfg.moe_experts:
        one["moe_stats"] = i32(4)
    fns = programs(cfg)
    return {
        "decode_fn": (fns["decode_fn"], (1,),
                      (params, cache, tokens, sds(jnp.float32, slots), i32(slots),
                       sds(jnp.uint32, slots, 2))),
        "chunk_mid": (fns["chunk_mid"], (1,),
                      (params, tuple(dict(one) for _ in range(rows)), i32(rows, 256), i32(rows),
                       i32(rows))),
    }


def _engine_text(program):
    fn, donated, args = program
    return jax.jit(fn, donate_argnums=donated).lower(*args).compile().as_text()


def _granite_whole():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.granite4_h_micro(max_seq_len=4096)
