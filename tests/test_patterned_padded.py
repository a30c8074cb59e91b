"""A padded prefill leaves the cache and logits of an unpadded one
(``models/patterned.py``), for every kind of model the body serves, and the
benchmark's family maps the published keys onto the tiny preset."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, decode_step, init_kv_cache, prefill
from ray_tpu.models.patterned import _param_shapes
from tests.patterned_models import CFG, MODELS, PUBLISHED, _model, _tol


def test_the_family_maps_the_published_keys_onto_the_tiny_preset():
    from benchmark.families import moe_window_gqa as family

    got = LlamaConfig.laguna_tiny(**family.model_kwargs(PUBLISHED))
    assert got == CFG
    shapes = {k: s for k, (s, _) in family.param_shapes(PUBLISHED).items()}
    assert shapes == _param_shapes(CFG)


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
