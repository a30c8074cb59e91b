"""The main path's kernels, compiled at real widths for a described v5e.

No chip is attached here: the TPU compiler that ships with jaxlib compiles
for a ``v5e:2x2`` topology that is only described, and refuses what the chip
would refuse (block shapes the tiling cannot take, more scoped VMEM than a
kernel may use). Nothing runs, so these tests say nothing about results or
speed; interpret-mode correctness lives in ``tests/test_ops.py``.

The only file of its kind: the process that describes the topology loads
libtpu and keeps it, so a second such file could land on another xdist
worker and skip. The topology is described inside a fixture, never at
import, in a ``skipif`` or in ``parametrize``.
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

    for name in ("ray_tpu.ops.rmsnorm", "ray_tpu.ops.quant", "ray_tpu.ops.grouped_matmul",
                 "ray_tpu.ops.decode_attention", "ray_tpu.ops.ssm", "ray_tpu.ops.kda"):
        monkeypatch.setattr(sys.modules[name], "interpret", lambda: False)


def _compiled_text(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


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


def _decode_attention_shapes(layers, stripe, heads, slots=32):
    """One new token a slot over the serving cells' caches: ``heads`` query
    heads over 8 key-value heads of width 128."""
    cache = ((layers, slots, 8, stripe, 128), jnp.bfloat16)
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


# ---- the engine's layout of the stacked attention input projections ---------


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


def _projection_slice_ops(text, e, head_width=128):
    """The operations of the entry computation and of the layer loop's body
    (not of a fusion's own computation) whose result, or one of whose
    results, has the shape of one layer's slice of a stacked attention input
    projection: ``[1, e, h, 128]`` or ``[e, h, 128]``. A matmul that reads
    the stacked leaf in place leaves none: its fusion takes the leaf whole
    and the layer's index."""
    import re

    shape = re.compile(r"\[(?:1,)?%d,\d+,%d\]" % (e, head_width))
    return [
        line.strip()[:200] for _, result, op, line in _ops_outside_fusions(text)
        if shape.search(result) and op not in ("parameter", "get-tuple-element")
        and not op.endswith("-done")
    ]


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


def _program_text(program):
    fn, args = program
    return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()


@pytest.mark.parametrize("program", ["decode_step", "chunk_mid"])
@pytest.mark.parametrize("served", sorted(_SERVED))
def test_served_programs_read_a_layers_projection_slice_in_place(
        served, program, one_chip, no_compile_cache, native_kernels):
    """``decode_step`` over every slot and the 256-token
    ``prefill(..., with_logits=False)`` at the serving cells' shapes, the
    parameters in the formats the engine's rule gives
    (``models/llama.py serving_layouts``): no operation outside a matmul's
    own fusion yields a layer's slice of ``wq``, ``wk`` or ``wv``. Under the
    default layout each slice is copied first (tiles over heads x head
    width, contraction over ``d_model``): 3 such operations in Mistral's
    decode step, 5 in its chunk, 13 and 24 in Laguna's (PERF.md section 6,
    PR 29; on the chip 1.1 of a 14.7 ms decode step)."""
    cfg = _served_config(served)
    slots, stripe, e = _SERVED[served]

    def count(relaid):
        programs = _served_programs(cfg, slots, stripe, one_chip, relaid)
        return _projection_slice_ops(_program_text(programs[program]), e)

    assert count(True) == []
    assert count(False)  # the guard sees the copies where the layout is the default


# ---- the decode step's read of the cache, and the chunk programs beside it ---


def _yields_a_layer_of_the_cache(text, slots, stripe, heads=8, width=128):
    """The operations, in any computation, whose result is one layer of the
    cache (``[slots, 8, stripe, 128]``, with or without a leading 1), and the
    copies of the whole cache."""
    import re

    layer = re.compile(r"= \w+\[(?:1,)?%d,%d,%d,%d\]\S* (?!parameter|get-tuple-element)"
                       % (slots, heads, stripe, width))
    whole = re.compile(r"= \w+\[\d+,%d,%d,%d,%d\]\S* copy\(" % (slots, heads, stripe, width))
    return [line.strip()[:160] for line in text.splitlines()
            if layer.search(line) or whole.search(line)]


def _same_program(text):
    """Compiled text without what names a source line: metadata, the
    stack-frame tables, a Mosaic kernel's bytecode; instructions renumbered
    by first appearance."""
    import re

    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r'backend_config="[^"]*"', "", text)
    text = "\n".join(
        line for line in text.splitlines()
        if not line.startswith(("FileNames", "FunctionNames", "FileLocations", "StackFrames"))
        and not re.match(r"\d+ ", line))
    seen = {}
    return re.sub(r"%[\w.\-]+", lambda m: seen.setdefault(m.group(0), f"%{len(seen)}"), text)


@pytest.mark.parametrize("served", sorted(_SERVED))
def test_decode_step_reads_the_cache_where_it_lies(
        served, one_chip, no_compile_cache, native_kernels, monkeypatch):
    """``decode_step`` at the serving cells' shapes runs one
    ``decode_attention`` kernel a traced layer on the carried cache whole:
    nothing in the program yields a layer of the cache (the einsum's
    ``ck_all[l]`` is a 134 MB slice a tensor and layer in Mistral's cell, 268
    MB in Laguna's, read whole at any length: PERF.md section 6, PR 31) and
    nothing copies the cache."""
    from ray_tpu.models import patterned

    cfg = _served_config(served)
    slots, stripe, _ = _SERVED[served]
    text = _program_text(_served_programs(cfg, slots, stripe, one_chip)["decode_step"])
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line and "attn_core" in line]
    # one a traced layer: Mistral's stack is one loop body, Laguna's five layers
    # are the leading one and one period
    assert len(kernels) == (cfg.n_layers if cfg.layer_types else 1), kernels
    assert _yields_a_layer_of_the_cache(text, slots, stripe) == []

    monkeypatch.setattr(patterned, "reads_blocks", lambda *a: False)
    einsum = _program_text(_served_programs(cfg, slots, stripe, one_chip)["decode_step"])
    assert _yields_a_layer_of_the_cache(einsum, slots, stripe)  # the guard sees the slices


@pytest.mark.parametrize("program", ["chunk_mid", "chunk_final"])
@pytest.mark.parametrize("served", sorted(_SERVED))
def test_chunk_programs_are_what_they_are_without_the_decode_kernel(
        served, program, one_chip, no_compile_cache, native_kernels, monkeypatch):
    """A prompt's chunk (``T > 1``) keeps the einsum: the 256-token ``prefill``
    without and with logits compiles to the text it compiles to with the
    kernel's selection switched off, source lines apart."""
    from ray_tpu.models import patterned

    cfg = _served_config(served)
    slots, stripe, _ = _SERVED[served]
    text = _same_program(_program_text(_served_programs(cfg, slots, stripe, one_chip)[program]))
    assert "decode_attention" not in text
    monkeypatch.setattr(patterned, "reads_blocks", lambda *a: False)
    # new functions, so that they are traced anew
    assert _same_program(
        _program_text(_served_programs(cfg, slots, stripe, one_chip)[program])) == text


def test_latent_programs_hold_nothing_as_long_as_the_stripe_and_copy_no_leaf(
        one_chip, no_compile_cache, native_kernels, monkeypatch):
    """The Kanana-2 cell's decode step (24 slots of 24,576) and a 256-token
    final chunk over one such stripe, 5 layers at published widths, the
    parameters in the formats the engine's rule gives. The decode step reads
    its latents through the kernel (one call in layer 0's body, one in the
    expert layers' loop, beside the three grouped matmuls); neither program's
    temporaries follow the stripe (a [32, 256, 24576] float32 score block
    alone is 0.8 GB; the chunk walks 1,024-position key blocks up to its
    row's length); and neither relays ``wq_latent`` whole, which both did
    with the leaf head-major or in the default layout (a 192-wide head is no
    whole number of lane tiles: 0.45 of a 9.06 ms decode step on the chip,
    PERF.md section 6, PR 33)."""
    cfg = _served_config("kanana-2-30b-a3b-serve-l5")
    whole_leaf = "bf16[5,2048,32,192]"

    def compiled(name):
        fn, args = _served_programs(cfg, 24, 24576, one_chip)[name]
        return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()

    def relays(text):
        return [line.strip()[:120] for line in text.splitlines()
                if " copy(" in line and line.split(" = ", 1)[-1].startswith(whole_leaf)]

    step = compiled("decode_step")
    text = step.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 5
    assert step.memory_analysis().temp_size_in_bytes < 64e6
    assert relays(text) == []
    chunk = compiled("chunk_final")
    assert chunk.memory_analysis().temp_size_in_bytes < 256e6
    assert relays(chunk.as_text()) == []
    # the guard sees the copy where the leaf is head-major as the other models' are
    from ray_tpu.models import llama

    monkeypatch.setattr(llama, "EMBED_MINOR", llama.HEAD_MAJOR)
    assert relays(compiled("decode_step").as_text())


# ---- blocks that keep a state a slot (Nemotron-3-Super's cut) -----------------


def _state_space_cut():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.nemotron3_super(
        n_layers=11, moe_experts_held=128, vocab_size=32768, max_seq_len=2048)


def _fusions_on_a_layer_of_the_state(text, scope="ssm_step"):
    """The fusions under ``scope`` with a layer of the Nemotron cut's state
    among their operands or results, in any view of its heads
    ([64, 128, 64, 128], [64, 8, 16, 64, 128], with or without the layers in
    front)."""
    import re

    a_layer = re.compile(r"f32\[(?:\d+,)?64,(?:128|8,16),64,128\]")
    fusions = {m.group(1): line for line in text.splitlines()
               if (m := re.search(r" fusion\(.*calls=(%[\w.\-]+)", line)) and scope in line}
    computation, found = None, set()
    for line in text.splitlines():
        if line and not line[0].isspace():
            computation = line.split()[0]
        elif computation in fusions and a_layer.search(line):
            found.add(computation)
    return sorted(fusions[c].strip()[:160] for c in found)


def test_state_space_decode_step_moves_its_state_where_it_lies(
        one_chip, no_compile_cache, native_kernels, monkeypatch):
    """The Nemotron-3-Super cell's decode step (64 slots of 2,048; 5 Mamba-2
    blocks, 5 expert blocks holding 128 of 512 experts, one GQA block, at
    published widths) compiles for the chip beside 9.3 GB of weights: the 1.3
    GB of float32 state is updated in the donated cache (no copy of the leaf,
    temporaries far under one layer's 0.27 GB) by one ``ssm_step`` kernel a
    block on the leaf whole, and no fusion under that scope reads or writes a
    layer of the state (XLA's own two made three passes over it: PERF.md
    section 6, PR 38); the held banks go through the grouped-matmul kernels
    whole, and the attention block reads its stripe through the decode
    kernel."""
    from ray_tpu.ops import ssm

    def compiled():
        fn, args = _served_programs(_state_space_cut(), 64, 2048, one_chip)["decode_step"]
        return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()

    step = compiled()
    text = step.as_text()
    state = "f32[5,64,128,64,128]"
    assert [line.strip()[:120] for line in text.splitlines()
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(state)] == []
    assert step.memory_analysis().temp_size_in_bytes < 128e6
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("moe_ffn/experts" in line for line in kernels) >= 2  # up and down, relu^2 between
    assert sum("attn_core" in line and "ssm_mixer" not in line for line in kernels) == 1
    assert sum("attn_core/ssm_mixer/ssm_step" in line for line in kernels) == 5
    assert _fusions_on_a_layer_of_the_state(text) == []
    for scope in ("ssm_mixer/ssm_step", "ssm_mixer/ssm_conv", "moe_ffn/moe_latent_proj"):
        assert scope in text, scope
    # every token of a decode step is real: the convolution's next tail is a
    # slice of its inputs, not a gather by each row's own end
    assert [line.strip()[:120] for line in text.splitlines()
            if " gather(" in line and "ssm_conv" in line] == []
    # the guard sees XLA's two fusions where the plain line runs
    monkeypatch.setattr(ssm, "step_groups", lambda *a: None)
    assert len(_fusions_on_a_layer_of_the_state(compiled().as_text())) >= 2


def test_state_space_final_chunk_fits_at_its_widest(one_chip, no_compile_cache, native_kernels):
    """The cell's widest final chunk (1,024 tokens into one stripe, the
    chunked scan over eight 128-token chunks a block): temporaries under 0.6
    GB beside the weights and a 1.5 GB pool."""
    from ray_tpu.models.llama import prefill

    cfg = _state_space_cut()
    _, (params, stripe, _, n, s) = _served_programs(cfg, 64, 2048, one_chip)["chunk_final"]
    tokens = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, o, t, n, s: prefill(p, o, t, cfg, lengths=n, start_pos=s), donate_argnums=(1,)
    ).lower(params, stripe, tokens, n, s).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 600e6
    assert "ssm_mixer/ssm_scan" in compiled.as_text()


@pytest.mark.parametrize("served", sorted(_SERVED) + ["kanana-2-30b-a3b-serve-l5"])
def test_the_other_families_decode_steps_hold_nothing_of_the_state_space_path(
        served, one_chip, no_compile_cache, native_kernels):
    """A model whose blocks all have attention and a feed-forward carries keys,
    values and lengths alone through its decode step: no state-space scope, no
    latent projection of experts, no fifth routing count."""
    cfg = _served_config(served)
    slots, stripe = {"kanana-2-30b-a3b-serve-l5": (24, 24576)}.get(served) or _SERVED[served][:2]
    fn, args = _served_programs(cfg, slots, stripe, one_chip)["decode_step"]
    assert set(args[1]) == {"k", "v", "length"}
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
    assert "ssm_" not in text and "kda_" not in text and "moe_latent_proj" not in text


# ---- layers that keep a delta-rule state a slot (Solar-Open2's cut) ----------


def _delta_rule_cut():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.solar_open2_250b(
        n_layers=4, gqa_layers=(3,), moe_experts_held=40, vocab_size=24576, max_seq_len=8192)


def test_delta_rule_decode_step_moves_its_state_where_it_lies(
        one_chip, no_compile_cache, native_kernels):
    """The Solar-Open2 cell's decode step (64 slots of 8,192; three delta-rule
    layers and one gated attention layer, each with 40 of 320 experts held, at
    published widths) compiles for the chip beside 6.6 GB of weights: the 0.8
    GB of float32 state is updated in the donated cache (no copy of the leaf,
    temporaries far under one layer's 0.27 GB) by one ``kda_step`` kernel a
    layer on the leaf whole, under ``kda_mixer``; the held banks go through
    the grouped-matmul kernels whole, the attention layer reads its stripe
    through the decode kernel and its gate is a channel's."""
    fn, args = _served_programs(_delta_rule_cut(), 64, 8192, one_chip)["decode_step"]
    assert set(args[1]) == {"k", "v", "length", "kda_state", "kda_conv"}
    step = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = step.as_text()
    state = "f32[3,64,64,128,128]"
    assert [line.strip()[:120] for line in text.splitlines()
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(state)] == []
    assert step.memory_analysis().temp_size_in_bytes < 128e6
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("moe_ffn/experts" in line for line in kernels) >= 3  # gate, up and down
    assert sum("attn_core" in line and "kda_mixer" not in line for line in kernels) == 1
    assert sum("attn_core/kda_mixer/kda_step" in line for line in kernels) == 3
    for scope in ("attn_qkv/kda_mixer", "kda_mixer/kda_step", "kda_mixer/kda_conv",
                  "attn_out/kda_mixer", "attn_out/gate"):
        assert scope in text, scope
    # every token of a decode step is real: the convolutions' next tail is a
    # slice of their inputs, not a gather by each row's own end
    assert [line.strip()[:120] for line in text.splitlines()
            if " gather(" in line and "kda_conv" in line] == []


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


# ---- middle chunks of several rows (``llm/engine.py programs``) -------------


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


def _whole_stripe_ops(text, layers, stripe, heads=8, width=128):
    """(computation, operation) of everything outside a fusion's own
    computation whose result has the shape of whole scratch stripes
    (``[layers, rows, 8, stripe, 128]``) or of a layer of them."""
    import re

    shape = re.compile(r"\[(?:%d,)?\d+,%d,%d,%d\]" % (layers, heads, stripe, width))
    return [
        (computation, op) for computation, result, op, _ in _ops_outside_fusions(text)
        if shape.search(result) and not op.endswith("-done")
        and op not in ("parameter", "get-tuple-element", "tuple", "while", "bitcast")
    ]


# whole-stripe operations that are not a layer's in-place block write, more in
# the program of several rows than in the 1-row one: the copies that stack the
# rows' stripes (keys and values) and hand each row's back
_STACKING_COPIES = {
    ("mistral-7b-serve-l16", 2): 5, ("mistral-7b-serve-l16", 4): 7,
    ("laguna-xs.2-serve-l5", 2): 9, ("laguna-xs.2-serve-l5", 4): 23,
}


@pytest.mark.parametrize("rows", [2, 4])
@pytest.mark.parametrize("served", sorted(_SERVED))
def test_a_middle_chunk_of_several_rows_copies_no_stripe_a_layer(
        served, rows, one_chip, no_compile_cache, native_kernels):
    """The engine's ``chunk_mid`` with two and with four rows of 256 tokens
    at the serving cells' shapes: the rows' scratch stripes are stacked once
    a launch and handed back once, and the layers write into the stack in
    place. Outside the in-place block writes (two a row and traced layer, as
    in the 1-row program) the program holds at most ``_STACKING_COPIES`` more
    whole-stripe operations than the 1-row one: a number that follows the
    rows and not the layers (a copy a layer would add 16 in Mistral's cell, 5
    in Laguna's, a tensor and row; an undonated stripe cost 0.64 ms a layer on
    the chip, PERF.md section 6, PR 27; Laguna's count holds the pieces the
    compiler moves a stripe in). In Mistral's cell the layers are one
    loop body, which holds nothing but those writes."""
    cfg = _served_config(served)
    stripe = _SERVED[served][1]

    def ops(n):
        text = _engine_text(_engine_programs(served, one_chip, n)["chunk_mid"])
        return _whole_stripe_ops(text, cfg.n_layers, stripe)

    def copies(found):
        return [op for _, op in found if op != "dynamic-update-slice"]

    one, several = ops(1), ops(rows)
    assert len(copies(several)) - len(copies(one)) <= _STACKING_COPIES[served, rows], (one, several)
    writes = lambda found: sum(op == "dynamic-update-slice" for _, op in found)  # noqa: E731
    assert writes(several) == rows * writes(one)
    if not cfg.layer_types:  # one loop body for all layers
        in_loop = [op for computation, op in several if "region" in computation]
        assert in_loop == ["dynamic-update-slice"] * 2 * rows, several


@pytest.mark.parametrize("served", sorted(_SERVED))
def test_the_engines_decode_program_is_decode_step_and_the_one_sampler(
        served, one_chip, no_compile_cache, native_kernels):
    """``jit_decode_fn`` is not touched by what groups the chunk programs:
    the engine's ``decode_fn`` compiles to the text of ``decode_step`` over
    every slot with the one sampler mapped over its rows, spelled out here as
    the engine had it before chunk programs took rows (PR 34), source lines
    apart."""
    from ray_tpu.models.llama import decode_step

    cfg = _served_config(served)
    fn, donated, args = _engine_programs(served, one_chip)["decode_fn"]
    K = min(64, cfg.vocab_size)

    def sample_row(logits_row, temp, top_k, key):
        greedy = jnp.argmax(logits_row, -1)
        vals, idxs = jax.lax.top_k(logits_row, K)
        rank_ok = jnp.arange(K) < top_k
        scaled = jnp.where(rank_ok, vals / jnp.maximum(temp, 1e-6), -jnp.inf)
        key, sub = jax.random.split(key)
        sampled = idxs[jax.random.categorical(sub, scaled)]
        tok = jnp.where(temp <= 0.0, greedy, sampled).astype(jnp.int32)
        return tok, key

    def decode_fn(params, cache, tokens, temps, top_ks, keys):
        if cfg.moe_experts:
            cache = dict(cache, moe_stats=jnp.zeros((4,), jnp.int32))
        logits, cache = decode_step(params, cache, tokens, cfg)
        stats = cache.pop("moe_stats", None)
        with jax.named_scope("sampling"):
            next_tokens, new_keys = jax.vmap(sample_row)(logits, temps, top_ks, keys)
        return next_tokens, cache, new_keys, stats

    def same(text):  # a Mosaic kernel's bytecode names source lines too
        import re

        return _same_program(re.sub(r"backend_config=\{.*?\}(?=[,)\s]|$)", "", text, flags=re.M))

    assert same(_engine_text((fn, donated, args))) == same(_engine_text((decode_fn, donated, args)))


# ---- a chunk launch that carries the pool's decode step ----------------------


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


# ------------------------------------------------- Granite-4.0-H-Micro, whole


def _granite_whole():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.granite4_h_micro(max_seq_len=4096)


def test_granite_decode_step_compiles_whole_and_moves_its_state_where_it_lies(
        one_chip, no_compile_cache, native_kernels):
    """The Granite cell's decode step, the model whole (24 slots of 4,096; 36
    Mamba-2 and 4 GQA layers at published widths, four periods of ten under
    one loop) compiles for the chip beside 6.4 GB of weights: the 1.8 GB of
    float32 state is updated in the donated cache by one ``ssm_step`` kernel a
    mamba layer of a period (nine: a tile is the one group's 64 heads, 2 MB),
    and the attention layers, whose heads are 64 wide, half a lane tile, keep
    the einsum over their stripes (the decode kernel's copies take no part of
    a lane tile: the chip's compiler refused it, "slice shape along dimension
    4 must be aligned to tiling (128), but is 64")."""
    fn, args = _served_programs(_granite_whole(), 24, 4096, one_chip)["decode_step"]
    step = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = step.as_text()
    state = "f32[36,24,64,64,128]"
    assert [line.strip()[:120] for line in text.splitlines()
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(state)] == []
    assert step.memory_analysis().temp_size_in_bytes < 256e6
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("attn_core/ssm_mixer/ssm_step" in line for line in kernels) == 9
    assert sum("attn_core" in line and "ssm_mixer" not in line for line in kernels) == 0


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
