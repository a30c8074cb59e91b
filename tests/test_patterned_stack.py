"""The stack of ``models/patterned.py``: a repeated period runs under one loop
and equals the unrolled stack, a uniform expert model with a shared expert
serves what it trains, pattern errors are named, and which way the model
modules import each other."""

import ast
import dataclasses
import math
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import (
    LlamaConfig,
    decode_step,
    forward,
    init_kv_cache,
    init_params,
    prefill,
)
from tests.patterned_models import MODELS

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
