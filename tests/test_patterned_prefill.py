"""Prefill and decode through the cache (``models/patterned.py``) against the
full forward pass and, for the patterned model, against the benchmark's plain
reference, for every kind of model the body serves; the window cut out of the
stripe and the whole stripe. One parametrised test: the longest of what was
``tests/test_patterned.py``, a file of its own so that a worker has it alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import decode_step, init_kv_cache, prefill
from tests.patterned_models import MODELS, PUBLISHED, _model, _tol

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
