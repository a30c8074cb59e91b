"""The one body that carries tokens through the cache (``models/patterned.py``),
for every kind of model it serves: layers alike (dense, with LoRA adapters,
with routed experts) and not alike (Laguna-XS.2's pattern at test size).
Prefill and decode through the cache against the full forward pass and,
for the patterned model, against the benchmark's plain reference; the window
cut out of the stripe, the two forms of the expert layer, the rotary tables
against a NumPy transcription of the published code, the published depth's
parameter count, the engine's routing and window counters and scopes, and
which way the model modules import each other."""

import ast
import dataclasses
import functools
import math
import pathlib
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from ray_tpu.models import patterned
from ray_tpu.models.llama import (
    LlamaConfig,
    decode_step,
    forward,
    init_kv_cache,
    init_lora_stack,
    init_params,
    prefill,
)
from ray_tpu.models.patterned import _moe_decode_ffn, _param_shapes

CFG = LlamaConfig.laguna_tiny()
# what benchmark/families/moe_window_gqa.py reads, for the reference
PUBLISHED = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 5,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "tie_word_embeddings": False, "gating": True,
    "sliding_window": 8, "moe_apply_router_weight_on_input": False, "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
                           "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 8,
                           "attention_factor": 1.2, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
    },
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
}


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(7), CFG)


_MOE = dict(moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0)  # ample: ``forward`` drops nothing
MODELS = {
    "dense": LlamaConfig.tiny(),
    "dense-lora": LlamaConfig.tiny(),
    "moe": LlamaConfig.tiny(**_MOE),
    "moe-shared": LlamaConfig.tiny(**_MOE, moe_d_ff=48, moe_shared_d_ff=32, moe_routed_scale=2.5),
    "laguna": CFG,
}
ADAPTERS = (1, 2)  # the adapter of each of the two rows, where there are any


@functools.lru_cache(maxsize=None)
def _model(name):
    """(cfg, params, the LoRA arguments of ``prefill`` / ``decode_step`` for
    the given rows, tokens [2, 44], ``forward``'s logits). With adapters,
    ``forward`` runs a row at a time on weights with the row's adapter
    folded into ``wq`` and ``wv``."""
    cfg = MODELS[name]
    params = init_params(jax.random.PRNGKey(7), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 44), 0, cfg.vocab_size)
    if "lora" not in name:
        return cfg, params, lambda rows=(0, 1): {}, toks, forward(params, toks, cfg)
    rng = np.random.default_rng(7)
    loras = {k: jnp.asarray(rng.normal(0, 0.1, v.shape), v.dtype)
             for k, v in init_lora_stack(cfg, 2, 4).items()}

    def folded(a):
        return dict(
            params,
            wq=params["wq"] + jnp.einsum("ler,lrhd->lehd", loras["wq_a"][:, a], loras["wq_b"][:, a]),
            wv=params["wv"] + jnp.einsum("ler,lrhd->lehd", loras["wv_a"][:, a], loras["wv_b"][:, a]),
        )

    whole = jnp.concatenate([forward(folded(a), toks[b:b + 1], cfg) for b, a in enumerate(ADAPTERS)])
    return cfg, params, lambda rows=(0, 1): dict(
        loras=loras, adapter_ids=jnp.asarray([ADAPTERS[b] for b in rows], jnp.int32)), toks, whole


def test_the_family_maps_the_published_keys_onto_the_tiny_preset():
    from benchmark.families import moe_window_gqa as family

    got = LlamaConfig.laguna_tiny(**family.model_kwargs(PUBLISHED))
    assert got == CFG
    shapes = {k: s for k, (s, _) in family.param_shapes(PUBLISHED).items()}
    assert shapes == _param_shapes(CFG)


def _tol(cfg):  # the capacity form of ``forward``'s expert layers sums in another order
    return dict(atol=5e-5, rtol=1e-4) if cfg.layer_types else dict(atol=2e-4, rtol=2e-4)


_CHUNKS = {"one-prefill": (44,), "chunked-across-the-window": (5, 16, 9)}


@pytest.mark.parametrize("model, chunks, align", [
    *((m, "chunked-across-the-window", 128) for m in MODELS if m != "laguna"),
    *(("laguna", c, a) for c in _CHUNKS for a in (4, 128)),
], ids=lambda v: {4: "window-cut-out", 128: "whole-stripe"}.get(v, v))
def test_prefill_then_decode_equals_forward_and_the_reference(monkeypatch, model, chunks, align):
    """Logits and every layer's keys and values: the prompt goes in as
    ``chunks`` (the second form crosses the 8-token window inside a chunk and
    between chunks), the rest a token at a time; against ``forward`` and, for
    the patterned model, against ``benchmark/reference_moe_window.py`` on the
    same weights."""
    cfg, params, lora_kw, tokens, whole = _model(model)
    monkeypatch.setattr(patterned, "_WINDOW_ALIGN", align)
    tol = _tol(cfg)
    B, T = tokens.shape
    cache = init_kv_cache(cfg, B, 64)
    at = 0
    for n in _CHUNKS[chunks]:
        if at + n > 30:
            n = 30 - at
        logits, cache = prefill(
            params, cache, tokens[:, at:at + n], cfg, start_pos=jnp.full((B,), at, jnp.int32),
            **lora_kw())
        at += n
    assert at == 30
    got = [logits]
    for i in range(at, T - 1):
        logits, cache = decode_step(params, cache, tokens[:, i], cfg, **lora_kw())
        got.append(logits)
    got = jnp.stack(got, axis=1)  # positions 29 .. T-2
    np.testing.assert_allclose(got, whole[:, 29:T - 1], **tol)
    if not cfg.layer_types:
        return
    from benchmark.reference_moe_window import Reference

    ref = Reference(PUBLISHED, jax.local_devices()[:1])
    want = ref.forward_rows(params, [np.asarray(r[:T - 1]) for r in tokens], last=T - 30,
                            kv_rows=range(B))
    np.testing.assert_allclose(got, np.stack(want["logits"]), **tol)
    for b in range(B):
        for name, ref_kv in zip(("k", "v"), want["kv"][b]):
            have = np.asarray(cache[name][:, b, :, :T - 1]).transpose(0, 2, 1, 3)  # [L, T, K, D]
            np.testing.assert_allclose(have, ref_kv, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("model", list(MODELS))
def test_padded_prefill_leaves_the_cache_and_logits_of_an_unpadded_one(monkeypatch, model):
    """Right-padded prompts write nothing past their length, and the decode
    steps after them (past the padded slots of the shorter row) give
    ``forward``'s logits."""
    cfg, params, lora_kw, tokens, whole = _model(model)
    monkeypatch.setattr(patterned, "_WINDOW_ALIGN", 4)
    tol = _tol(cfg)
    lengths = [21, 13]
    logits, cache = prefill(params, init_kv_cache(cfg, 2, 64), tokens[:, :32], cfg,
                            lengths=jnp.asarray(lengths, jnp.int32), **lora_kw())
    for b, n in enumerate(lengths):
        alone, c1 = prefill(params, init_kv_cache(cfg, 1, 64), tokens[b:b + 1, :n], cfg, **lora_kw((b,)))
        np.testing.assert_allclose(logits[b], alone[0], **tol)
        np.testing.assert_allclose(cache["k"][:, b, :, :n], c1["k"][:, 0, :, :n], atol=2e-5)
        assert not np.asarray(cache["k"][:, b, :, n:]).any()  # padding writes nothing
    for i in range(4):
        at = jnp.asarray(lengths) + i
        logits, cache = decode_step(params, cache, tokens[jnp.arange(2), at], cfg, **lora_kw())
        np.testing.assert_allclose(logits, whole[jnp.arange(2), at], **tol)


@pytest.mark.parametrize("model", ["dense", "dense-lora", "moe", "laguna"])
def test_decode_steps_through_the_kernel_give_the_einsums_tokens(monkeypatch, model):
    """16 greedy ``decode_step``s over a cache of whole blocks, which go
    through ``ops/decode_attention.py`` (interpreted), against the same steps
    with the kernel's selection switched off: the einsum over the whole
    stripe that every decode step ran before. Row 0 crosses a block's end on
    its way, row 1 stays inside the first block, and the patterned model's
    window starts mid-block."""
    from ray_tpu.ops.decode_attention import BLOCK

    cfg, params, lora_kw, _, _ = _model(model)
    lengths = jnp.asarray([BLOCK - 6, 30], jnp.int32)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, BLOCK), 0, cfg.vocab_size)

    traced = _count_kernel_calls(monkeypatch)

    def greedy(read_blocks):
        if not read_blocks:
            monkeypatch.setattr(patterned, "reads_blocks", lambda *a: False)
        step = jax.jit(lambda cache, toks: decode_step(params, cache, toks, cfg, **lora_kw()))
        logits, cache = prefill(params, init_kv_cache(cfg, 2, 2 * BLOCK), prompt, cfg,
                                lengths=lengths, **lora_kw())
        tokens, rows = [], [logits]
        for _ in range(16):
            tokens.append(jnp.argmax(rows[-1], -1))
            logits, cache = step(cache, tokens[-1])
            rows.append(logits)
        return np.asarray(jnp.stack(tokens)), np.asarray(jnp.stack(rows))

    tokens, logits = greedy(True)
    through_the_kernel = len(traced)
    want_tokens, want_logits = greedy(False)
    # one call a layer of the traced stack: the leading layers and one period
    assert through_the_kernel == (5 if cfg.layer_types else 1) == len(traced)
    np.testing.assert_array_equal(tokens, want_tokens)
    np.testing.assert_allclose(logits, want_logits, atol=5e-5, rtol=1e-4)


def _count_kernel_calls(monkeypatch):
    """The kernel's calls, as a layer loop's body is traced."""
    traced = []
    kernel = patterned.decode_attention
    monkeypatch.setattr(patterned, "decode_attention",
                        lambda *a: traced.append(a[3]) or kernel(*a))
    return traced


def _placed(how, cfg, slots, stripe):
    """``decode_step``'s arguments and ``jit`` options as each caller places
    them, on four virtual devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    def cache_on(mesh):  # ``llm/spmd.py``: key-value heads over ``tp``, made where they lie
        kv = NamedSharding(mesh, P(None, None, "tp", None, None))
        shardings = {"k": kv, "v": kv, "length": NamedSharding(mesh, P())}
        return jax.jit(lambda: init_kv_cache(cfg, slots, stripe), out_shardings=shardings)(), shardings

    if how == "one-device":  # ``JaxEngine`` with no mesh
        return init_params(jax.random.PRNGKey(7), cfg), init_kv_cache(cfg, slots, stripe), {}
    if how == "mesh-of-one-device":  # ``JaxEngine(config, mesh=<a mesh of one device>)``
        mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
        cache, _ = cache_on(mesh)
        return init_params(jax.random.PRNGKey(7), cfg, mesh=mesh), cache, {}
    mesh = build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
    params = init_params(jax.random.PRNGKey(7), cfg, mesh=mesh)
    if how == "engine-tp2":  # ``JaxEngine(tensor_parallel_degree=2)``: only the parameters on the mesh
        return params, init_kv_cache(cfg, slots, stripe), {}
    cache, shardings = cache_on(mesh)  # ``llm/spmd.py`` and, through it, ``llm/gang.py``
    return params, cache, dict(out_shardings=(NamedSharding(mesh, P()), shardings))


@pytest.mark.parametrize("how, kernel_calls", [
    ("one-device", 1), ("mesh-of-one-device", 1), ("engine-tp2", 0), ("spmd-tp2", 0)])
def test_a_decode_step_over_a_mesh_keeps_the_einsum(monkeypatch, how, kernel_calls):
    """``reads_blocks`` sees a mesh on the type of what the step is traced
    with: one kernel call a traced layer where everything lies on one device,
    none where the parameters or the cache lie on four, placed and jitted as
    ``llm/spmd.py`` and a ``JaxEngine`` under ``tensor_parallel_degree`` do
    (a Pallas call under the partitioner would be handed the whole gathered
    cache). Asked with the arrays themselves, as the engine asks for its
    counter, it answers what the trace does."""
    from ray_tpu.ops.decode_attention import BLOCK

    cfg = MODELS["dense"]
    params, cache, options = _placed(how, cfg, 2, BLOCK)
    traced = _count_kernel_calls(monkeypatch)
    step = jax.jit(lambda params, cache, toks: decode_step(params, cache, toks, cfg),
                   donate_argnums=(1,), **options)
    asked = patterned.reads_blocks(BLOCK, cache["k"], *jax.tree.leaves(params))
    logits, _ = step(params, cache, jnp.asarray([3, 5], jnp.int32))
    assert len(traced) == kernel_calls and asked == bool(kernel_calls)
    assert bool(jnp.isfinite(logits).all())
    assert not patterned.reads_blocks(BLOCK + 8, jnp.zeros(1))  # no whole blocks: the einsum anywhere


def test_grouped_expert_form_equals_every_expert_form(params):
    """``_moe_decode_ffn`` sorts tokens by expert; the same sum with every
    expert run over every token and a zero weight where a token did not
    choose it, written out here."""
    row, k, E = 2, CFG.moe_top_k, CFG.moe_experts
    for tokens in (3, 80):  # a decode batch, a chunk: less and more than one row tile
        h = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens, CFG.d_model), jnp.float32)
        grouped, stats = _moe_decode_ffn(params, row, h, CFG)
        g = h[0]
        probs = jax.nn.softmax(g @ params["moe_router"][row], axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        weights = (jax.nn.one_hot(idx, E) * (top / top.sum(-1, keepdims=True))[..., None]).sum(1)
        act = jax.nn.silu(jnp.einsum("gd,edf->egf", g, params["moe_w_gate"][row])) * jnp.einsum(
            "gd,edf->egf", g, params["moe_w_up"][row])
        every = jnp.einsum("egd,ge->gd", jnp.einsum("egf,efd->egd", act, params["moe_w_down"][row]), weights)
        shared = (jax.nn.silu(g @ params["moe_shared_gate"][row]) * (g @ params["moe_shared_up"][row])
                  ) @ params["moe_shared_down"][row]
        np.testing.assert_allclose(grouped[0], CFG.moe_routed_scale * every + shared, atol=2e-5, rtol=1e-4)
        layer_steps, assignments, touched, fullest = (int(x) for x in stats)
        assert (layer_steps, assignments) == (1, tokens * k)
        assert touched == len(set(np.asarray(idx).reshape(-1).tolist()))
        assert fullest == np.bincount(np.asarray(idx).reshape(-1)).max()


def _yarn_numpy(dim, base, factor, original, beta_fast, beta_slow):
    """transformers ``_compute_yarn_parameters``, transcribed."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2).astype(np.float32) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2).astype(np.float32) - low) / (high - low), 0, 1)
    inv_freq_extrapolation_factor = 1 - ramp
    return (inv_freq_interpolation * (1 - inv_freq_extrapolation_factor)
            + inv_freq_extrapolation * inv_freq_extrapolation_factor)


@pytest.mark.parametrize("cfg", [CFG, LlamaConfig.laguna_xs2()], ids=["tiny", "published"])
def test_yarn_and_the_half_rotation_against_numpy(cfg):
    inv, factor = patterned.rope_inv_freq(cfg, "full")
    rot = int(cfg.head_dim * cfg.rope_partial)
    want = _yarn_numpy(rot, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_len,
                       cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert factor == cfg.yarn_attention_factor and len(inv) == rot // 2
    # low frequencies are interpolated (divided by the factor), high ones kept
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, rot, 2) / rot)
    np.testing.assert_allclose(inv[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(inv[-1], plain[-1] / cfg.yarn_factor, rtol=1e-5)
    inv_s, factor_s = patterned.rope_inv_freq(cfg, "sliding")
    assert factor_s == 1.0 and len(inv_s) == cfg.head_dim // 2
    # the rotation itself: first `rot` dims rotated in halves, the rest untouched
    x = np.random.default_rng(0).normal(size=(1, 3, 2, cfg.head_dim)).astype(np.float32)
    pos = np.asarray([[0, 5, 901]], np.int32)
    got = np.asarray(patterned._rope(jnp.asarray(x), jnp.asarray(pos), inv, factor))
    ang = pos[..., None].astype(np.float64) * want
    cos, sin = np.cos(ang)[:, :, None, :] * factor, np.sin(ang)[:, :, None, :] * factor
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    np.testing.assert_allclose(got[..., :rot // 2], x1 * cos - x2 * sin, atol=2e-4)
    np.testing.assert_allclose(got[..., rot // 2:rot], x2 * cos + x1 * sin, atol=2e-4)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])


def test_published_depth_counts_its_parameters_and_traces_one_period():
    cfg = LlamaConfig.laguna_xs2()
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert n == cfg.num_params()
    assert abs(n / 33.44e9 - 1) < 1e-3
    pl = patterned.plan(cfg)
    assert (pl.lead, pl.period, pl.reps, cfg.n_layers - pl.tail_from) == (1, 4, 9, 3)
    # the served cut: layer 0 and one period, every layer its own body
    cut = LlamaConfig.laguna_xs2(n_layers=5)
    assert cut.layer_types == ("full", "sliding", "sliding", "sliding", "full")
    assert abs(cut.num_params() / 3.87e9 - 1) < 5e-3


_NINE = ("full",) + ("sliding", "sliding", "sliding", "full") * 2
LOOPED = {
    # layer 0 and two periods; a uniform stack is no lead and a period of one layer
    "laguna-9-layers": (LlamaConfig.laguna_tiny(
        n_layers=9, layer_types=_NINE, heads_per_layer=tuple(6 if t == "full" else 8 for t in _NINE),
        mlp_types=("dense",) + ("sparse",) * 8), (1, 4, 2)),
    "dense-3-layers": (LlamaConfig.tiny(n_layers=3), (0, 1, 3)),
    "moe-shared-3-layers": (dataclasses.replace(MODELS["moe-shared"], n_layers=3), (0, 1, 3)),
}


@pytest.mark.parametrize("name", list(LOOPED))
def test_a_repeated_period_runs_under_one_loop_and_equals_the_unrolled_stack(monkeypatch, name):
    """The loop's traced indices reach the same rows as static ones: logits
    and cache of the looped stack equal those of the stack traced a layer at
    a time."""
    cfg, split = LOOPED[name]
    pl = patterned.plan(cfg)
    assert (pl.lead, pl.period, pl.reps) == split
    params = init_params(jax.random.PRNGKey(2), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, 24), 0, cfg.vocab_size)

    def through_the_cache(p, t):
        return prefill(p, init_kv_cache(cfg, 1, 32), t, cfg)

    looped, looped_cache = through_the_cache(params, toks[:, :23])
    whole = forward(params, toks, cfg)
    np.testing.assert_allclose(looped, whole[:, 22], atol=5e-5, rtol=1e-4)
    traced = []
    feed_forward = patterned._feed_forward
    monkeypatch.setattr(patterned, "_feed_forward",
                        lambda *a: traced.append(1) or feed_forward(*a))
    jax.make_jaxpr(through_the_cache)(params, toks)
    assert len(traced) == pl.lead + pl.period  # 5 bodies for 9 layers, 1 for a uniform stack
    flat = dataclasses.replace(pl, lead=cfg.n_layers, period=1, reps=0)  # every layer its own body
    monkeypatch.setattr(patterned, "plan", lambda c: flat)
    unrolled, unrolled_cache = through_the_cache(params, toks[:, :23])
    assert len(traced) == pl.lead + pl.period + cfg.n_layers
    np.testing.assert_allclose(looped, unrolled, atol=5e-5, rtol=1e-4)
    for key in ("k", "v"):
        np.testing.assert_allclose(looped_cache[key], unrolled_cache[key], atol=2e-5, rtol=1e-4)
    if cfg.layer_types:  # the whole-sequence pass of a patterned model runs the same loop
        np.testing.assert_allclose(whole, forward(params, toks, cfg), atol=5e-5, rtol=1e-4)


def test_the_model_modules_import_one_way():
    """``models/patterned.py`` (the plan, the loop, the one body) needs
    nothing of ``ray_tpu.models``: loaded by its path in a fresh interpreter,
    no module of the package is imported (``import ray_tpu.models.patterned``
    would run the package's ``__init__``, which imports ``llama``). And no
    ``import`` inside a function of ``ray_tpu/models/`` names a sibling
    module: there is no cycle left to get round."""
    models = pathlib.Path(patterned.__file__).parent
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('lower', {str(models / 'patterned.py')!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "sys.modules['lower'] = mod\n"
        "spec.loader.exec_module(mod)\n"
        "print(sorted(m for m in sys.modules if m.startswith('ray_tpu.models')))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, check=True,
                         env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(models.parent.parent),
                              "PATH": ""})
    assert out.stdout.strip() == "[]", out.stdout

    def names_a_sibling(node):
        if isinstance(node, ast.ImportFrom):
            return node.level > 0 or (node.module or "").startswith("ray_tpu.models")
        return isinstance(node, ast.Import) and any(
            a.name.startswith("ray_tpu.models") for a in node.names)

    for path in sorted(models.glob("*.py")):
        tree = ast.parse(path.read_text())
        for fn in ast.walk(tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inside = [n.lineno for n in ast.walk(fn) if names_a_sibling(n)]
                assert not inside, f"{path.name}:{inside} imports a sibling inside {fn.name}"
        if path.name == "patterned.py":
            assert not [n.lineno for n in ast.walk(tree) if names_a_sibling(n)]


def test_pattern_errors_are_named():
    with pytest.raises(ValueError, match="entries for n_layers"):
        LlamaConfig.laguna_tiny(n_layers=4)
    with pytest.raises(ValueError, match="differ in their query heads"):
        patterned.plan(LlamaConfig.laguna_tiny(heads_per_layer=(6, 8, 8, 4, 6)))
    with pytest.raises(ValueError, match="sliding_window"):
        patterned.plan(LlamaConfig.laguna_tiny(sliding_window=0))


# ------------------------------------------------------------------ the engine


@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="laguna-tiny", seed=3),
        engine=EngineConfig(max_num_seqs=4, max_seq_len=128, dtype="float32",
                            prefill_chunk=16, prefill_buckets=(8, 16, 32)),
    ))
    yield eng
    eng.shutdown()


def _routing(eng):
    c = eng.get_stats()["counters"]
    return {k: dict(c[k]) for k in c if k.startswith("moe_")}, c


def test_engine_tokens_equal_greedy_over_the_full_forward(engine, monkeypatch):
    monkeypatch.setattr(patterned, "_WINDOW_ALIGN", 4)
    ids = [int(t) for t in np.random.default_rng(0).integers(32, 127, 50)]
    out = engine.generate(prompt_token_ids=ids, sampling_params=SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True))
    seq = jnp.asarray([ids + list(out.token_ids)])
    logits = forward(engine.params, seq, engine.model_cfg)
    assert [int(t) for t in jnp.argmax(logits[0, len(ids) - 1:-1], -1)] == list(out.token_ids)


def test_routing_and_window_counters_on_a_known_batch(engine):
    cfg = engine.model_cfg
    k, expert_layers, slots = cfg.moe_top_k, cfg.mlp_types.count("sparse"), 4
    before, c0 = _routing(engine)
    ids = [int(t) for t in np.random.default_rng(5).integers(32, 127, 37)]
    engine.generate(prompt_token_ids=ids, sampling_params=SamplingParams(
        max_tokens=5, temperature=0.0, ignore_eos=True))
    deadline = time.time() + 10.0
    while True:  # the run-ahead step's counts arrive with its fetch
        after, c1 = _routing(engine)
        if (after["moe_layer_steps"]["decode"] - before["moe_layer_steps"]["decode"]
                == (c1["decode_steps"] - c0["decode_steps"]) * expert_layers) or time.time() > deadline:
            break
        time.sleep(0.01)
    grew = {name: {p: after[name][p] - before[name][p] for p in after[name]} for name in after}
    # 37 tokens: two 16-token middle chunks and a final chunk of width 8 (5 real)
    assert c1["prefill_chunks"]["mid"] - c0["prefill_chunks"]["mid"] == 2
    # a prompt's middle chunks add theirs up on the device; its final chunk hands both out
    assert grew["moe_layer_steps"]["chunk_mid"] == 2 * expert_layers
    assert grew["moe_assignments"]["chunk_mid"] == k * (16 + 16) * expert_layers
    assert grew["moe_layer_steps"]["chunk_final"] == expert_layers
    assert grew["moe_assignments"]["chunk_final"] == k * 8 * expert_layers
    steps = c1["decode_steps"] - c0["decode_steps"]
    assert steps >= 4
    assert grew["moe_layer_steps"]["decode"] == steps * expert_layers
    assert grew["moe_assignments"]["decode"] == k * slots * steps * expert_layers
    for program in ("decode", "chunk_mid", "chunk_final"):
        runs = grew["moe_layer_steps"][program]
        assert runs <= grew["moe_experts_touched"][program] <= runs * cfg.moe_experts
        assert grew["moe_max_expert_load_sum"][program] * cfg.moe_experts >= grew["moe_assignments"][program]
    whole = c1["decode_kv_tokens_global"] - c0["decode_kv_tokens_global"]
    window = c1["decode_kv_tokens_window"] - c0["decode_kv_tokens_window"]
    assert 0 < window <= whole
    assert window == steps * cfg.sliding_window  # every step's slot is past the window
    assert whole >= steps * 37


def test_a_dense_engine_counts_no_routing_and_no_window():
    eng = JaxEngine(LLMConfig(model=ModelConfig(model_id="tiny", seed=1),
                              engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32")))
    try:
        eng.generate("hello there", sampling_params=SamplingParams(max_tokens=4, ignore_eos=True))
        routing, c = _routing(eng)
        assert all(v == {"decode": 0, "chunk_mid": 0, "chunk_final": 0} for v in routing.values())
        # the four routing counts, the assignments held (PR 35) and the blocks (PR 45)
        assert len(routing) == 6
        assert c["decode_kv_tokens_window"] == 0 < c["decode_kv_tokens_global"]
    finally:
        eng.shutdown()


def test_positions_read_counts_the_blocks_each_slots_bounds_cover():
    """Two requests at once in 256-position stripes, the longer crossing the
    first block's end while it decodes: ``decode_kv_positions_read`` (and the
    window layers' ``_window``) are ``ops/decode_attention.py positions_read``
    summed over the lengths the loop held at each launch, no less than the
    tokens needed and no more than the active slots' whole stripes."""
    from ray_tpu.ops.decode_attention import BLOCK, positions_read

    stripe = 2 * BLOCK
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="laguna-tiny", seed=3),
        engine=EngineConfig(max_num_seqs=4, max_seq_len=stripe, dtype="float32",
                            prefill_chunk=64, prefill_buckets=(16, 32, 64)),
    ))
    try:
        window = eng.model_cfg.sliding_window
        want = {"full": 0, "window": 0, "whole": 0}
        launch = eng._decode

        def counting(pool, *args):  # called by the loop right before it counts
            for r in pool.slots:
                if r is not None:
                    n = len(r.prompt_token_ids) + len(r.out_tokens)
                    want["full"] += eng._decode_n_steps * positions_read(0, n, stripe)
                    want["window"] += eng._decode_n_steps * positions_read(n - window, n, stripe)
                    want["whole"] += eng._decode_n_steps * stripe
            return launch(pool, *args)

        eng._decode = counting
        rng = np.random.default_rng(9)
        p = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
        reqs = [eng.submit(prompt_token_ids=[int(t) for t in rng.integers(32, 127, n)],
                           sampling_params=p) for n in (BLOCK - 10, 21)]
        for r in reqs:
            assert r.done.wait(timeout=120)
        c = eng.get_stats()["counters"]
        assert c["decode_kv_positions_read"] == want["full"] > 0
        assert c["decode_kv_positions_read_window"] == want["window"] > 0
        assert c["decode_kv_tokens_global"] <= c["decode_kv_positions_read"] <= want["whole"]
        assert c["decode_kv_tokens_window"] <= c["decode_kv_positions_read_window"] <= want["whole"]
        assert want["whole"] == c["decode_slot_steps"] * stripe
        # some steps read one block of the long request's stripe, some both; the
        # window never more than two
        assert BLOCK * c["decode_slot_steps"] < c["decode_kv_positions_read"] < want["whole"]
        assert c["decode_kv_positions_read_window"] <= c["decode_kv_positions_read"]
    finally:
        eng.shutdown()


@pytest.mark.parametrize("placed", ["no-mesh", "mesh-of-one-device", "tp2"])
def test_an_engine_counts_the_form_its_decode_steps_take(monkeypatch, placed):
    """The engine's counter and the body's choice are one answer
    (``patterned.reads_blocks``, asked once a pool): where the traced decode
    program calls the kernel, ``decode_kv_positions_read`` counts blocks;
    where it keeps the einsum (parameters over a mesh), whole stripes. A mesh
    of one device is one device on both sides."""
    from ray_tpu.ops.decode_attention import BLOCK
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = {"no-mesh": None,
            "mesh-of-one-device": build_mesh(MeshSpec(), devices=jax.devices()[:1]),
            "tp2": build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])}[placed]
    traced = _count_kernel_calls(monkeypatch)
    stripe = 2 * BLOCK
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=3),
        engine=EngineConfig(max_num_seqs=2, max_seq_len=stripe, dtype="float32",
                            prefill_buckets=(16, 32), enable_prefix_caching=False,
                            tensor_parallel_degree=2 if placed == "tp2" else 1),
    ), mesh=mesh)
    try:
        out = eng.generate(prompt_token_ids=list(range(40, 60)), sampling_params=SamplingParams(
            max_tokens=8, temperature=0.0, ignore_eos=True))
        assert len(out.token_ids) == 8
        c = eng.get_stats()["counters"]
        [pool] = eng._pools
        assert pool.reads_blocks == bool(traced) == (placed != "tp2")
        per_slot_step = BLOCK if pool.reads_blocks else stripe  # 28 positions at most: one block
        assert c["decode_kv_positions_read"] == c["decode_slot_steps"] * per_slot_step > 0
        assert c["decode_kv_positions_read_window"] == 0  # no window layers in this model
    finally:
        eng.shutdown()


INNER_SCOPES = {
    "decode_fn": ("attn_core/window", "attn_core/global", "moe_ffn/router", "moe_ffn/experts",
                  "moe_ffn/shared_expert", "attn_out/gate", "ffn", "kv_write", "sampling"),
    "chunk_mid": ("attn_core/window", "attn_core/global", "moe_ffn/router", "moe_ffn/experts",
                  "moe_ffn/shared_expert", "attn_out/gate"),
}


@pytest.fixture(scope="module")
def lowered_paths(engine):
    pool = engine._pools[0]
    while pool.keys is None:  # the loop thread makes them on its first pass
        time.sleep(0.01)
    shapes = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    params, cache = shapes(engine.params), shapes(pool.cache)
    one = dict(jax.eval_shape(lambda: init_kv_cache(engine.model_cfg, 1, pool.stripe_len)),
               moe_stats=i32(4))
    low = {
        "decode_fn": engine._decode_jit.lower(
            params, cache, i32(4), jax.ShapeDtypeStruct((4,), jnp.float32), i32(4), shapes(pool.keys)),
        "chunk_mid": engine._chunk_mid_jit.lower(params, (one,), i32(1, 16), i32(1), i32(1)),
    }
    return {k: set(re.findall(r'loc\("([^"]+)"', v.as_text(debug_info=True))) for k, v in low.items()}


@pytest.mark.parametrize("program, scope", [(p, s) for p, ss in INNER_SCOPES.items() for s in ss])
def test_inner_scopes_are_in_the_lowered_programs_op_names(lowered_paths, program, scope):
    pattern = re.compile(rf"(^|/){scope}(/|$)")
    assert any(pattern.search(path) for path in lowered_paths[program]), (program, scope)


def test_uniform_moe_with_a_shared_expert_and_scale_serves_what_it_trains():
    """Layers alike: ``models/llama.py _moe_ffn`` (the training
    path, capacity ample) and ``_moe_decode_ffn`` (the serving path) apply the
    same expert width, shared expert and routed scale."""
    cfg = LlamaConfig.tiny(moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0,
                           moe_d_ff=48, moe_shared_d_ff=32, moe_routed_scale=2.5)
    params = init_params(jax.random.PRNGKey(5), cfg)
    assert params["moe_w_gate"].shape == (2, 4, 64, 48) and params["moe_shared_down"].shape == (2, 32, 64)
    assert cfg.num_params() == sum(math.prod(p.shape) for p in params.values())
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 12), 0, cfg.vocab_size)
    whole = forward(params, toks, cfg)
    logits, cache = prefill(params, init_kv_cache(cfg, 2, 16), toks[:, :11], cfg)
    np.testing.assert_allclose(logits, whole[:, 10], atol=5e-5, rtol=1e-4)
    logits, _ = decode_step(params, cache, toks[:, 11], cfg)
    np.testing.assert_allclose(logits, whole[:, 11], atol=5e-5, rtol=1e-4)
    plain = LlamaConfig.tiny(moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0, moe_d_ff=48)
    assert not np.allclose(forward({k: v for k, v in params.items() if "shared" not in k}, toks, plain), whole)
