"""Latent attention of two widths under an indexer (``dots3-tiny``, the
pattern of dots-studio dots3-note-prev) through the cache, against the plain
reference (``benchmark/reference_sparse_latent.py``) on the benchmark's seeded
weights: at 8 positions a query and a window of 5 both bind on every prompt
here. Engine: ``tests/test_dots3_engine.py``; parts: ``tests/test_dots3_parts.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, forward, init_kv_cache, init_params
from ray_tpu.models.patterned import _param_shapes
from tests.dots3_models import CFG, PUBLISHED, TOL, reference, seeded_params, through_the_cache

T = 40


@pytest.fixture(scope="module")
def model():
    params = seeded_params()
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, 256))
    want = reference().forward_rows(params, list(tokens), last=T, kv_rows=[0, 1])
    return params, tokens, want


def test_the_family_maps_the_published_keys_onto_the_tiny_preset():
    from benchmark.families import sparse_latent as family
    from ray_tpu.llm import EngineConfig, ModelConfig
    from ray_tpu.llm.config import resolve_llama_config

    assert {k: s for k, (s, _) in family.param_shapes(PUBLISHED).items()} == _param_shapes(CFG)
    model = ModelConfig(model_id="dots3-tiny", model_kwargs=family.model_kwargs(PUBLISHED))
    got = resolve_llama_config(model, EngineConfig(max_seq_len=128, dtype="float32"))
    assert got == CFG


def test_the_published_model_counts_its_parameters_and_its_cache():
    """46 layers, 13 of them indexed: 288 B parameters with the 256 experts a
    layer, and the cut's cache a token."""
    cfg = LlamaConfig.dots3_note_prev()
    kinds = [t for t, _, _ in patterned.plan(cfg).kinds]
    assert kinds.count("latent") == 13 and kinds.count("latent_sliding") == 33
    assert [i for i, t in enumerate(kinds) if t == "latent"][:4] == [0, 1, 5, 9]
    assert 279e9 < cfg.num_params() < 280e9  # the language model alone: the row's 288B holds the towers
    cut = LlamaConfig.dots3_note_prev(n_layers=5, moe_experts_held=16, vocab_size=19008)
    assert round(cut.num_params() / 1e6, 1) == 2577.2
    cache = jax.eval_shape(lambda: init_kv_cache(cut, 16, 24576))
    assert {k: v.shape for k, v in cache.items() if k != "length"} == {
        "k": (2, 16, 1, 24576, 128), "v": (2, 16, 1, 24576, 512),
        "k_index": (2, 16, 1, 24576, 128),
        "k_sliding": (3, 16, 1, 24576, 128), "v_sliding": (3, 16, 1, 24576, 1024)}
    a_token = sum(v.size * 2 for k, v in cache.items() if k != "length") // (16 * 24576)
    assert a_token == 2 * 1536 + 3 * 2304 == 9984


@pytest.mark.parametrize("stripe", [64, 128], ids=["einsum-decode", "kernel-decode"])
def test_prefill_then_decode_equals_the_reference(model, stripe):
    """28 tokens in one chunk, then 12 decode steps, two rows: every logit and
    what ``k`` and ``v`` hold of the indexed layers against the reference's
    whole pass, where each query past the 8th position attends 8 chosen
    positions and each sliding layer's 5. A stripe of 128 goes through the
    decode kernel between the window's bounds and the gathered read."""
    params, tokens, want = model
    got, cache, _ = through_the_cache(params, tokens, [(32, [28, 28])], stripe=stripe)
    for b in range(2):
        at, logits = zip(*got[b])
        assert list(at) == list(range(27, T))
        np.testing.assert_allclose(np.stack(logits), want["logits"][b][27:], **TOL)
        k, v = want["kv"][b]
        np.testing.assert_allclose(cache["k"][:, b, 0, :T], k[:, :, 0], atol=2e-5)
        np.testing.assert_allclose(cache["v"][:, b, 0, :T], v[:, :, 0], atol=2e-5)


SPLITS = {
    "one-chunk": [(32, [30, 30])],
    "two-chunks": [(16, [16, 16]), (16, [14, 14])],
    "ragged-rows": [(16, [16, 9]), (16, [14, 16]), (8, [0, 5])],
    "token-by-token-chunks": [(1, [1, 1])] * 30,
    "a-row-of-no-real-token-beside-a-full-one": [(16, [16, 0]), (16, [14, 0]), (32, [0, 30])],
}


@pytest.mark.parametrize("split", sorted(SPLITS))
def test_every_split_of_a_prompt_gives_the_same_cache(model, split):
    """30 tokens a row however they are cut into launches: every stripe leaf
    (the indexed layers' key, latent and index key, the sliding layers' key and
    latent) as one chunk leaves it, and the last logits the reference's."""
    params, tokens, want = model
    _, whole, _ = through_the_cache(params, tokens, SPLITS["one-chunk"])
    got, cache, at = through_the_cache(params, tokens[:, :30], SPLITS[split])
    assert at.tolist() == [30, 30]
    for name in ("k", "v", "k_index", "k_sliding", "v_sliding"):
        np.testing.assert_allclose(cache[name][:, :, :, :30], whole[name][:, :, :, :30],
                                   atol=2e-5, err_msg=name)
    for b in range(2):
        np.testing.assert_allclose(got[b][-1][1], want["logits"][b][29], **TOL)


@pytest.mark.parametrize("leaf,why", [
    ("index_wq", "the indexer's queries choose the positions"),
    ("index_ww", "the indexer's weight a head takes part in the score"),
    ("index_k_bias", "the index key's LayerNorm has a bias"),
    ("wg_latent", "the gate a head scales an indexed layer's heads"),
    ("wg_latent_sliding", "and a sliding layer's"),
    ("q_norm_latent_sliding", "the query latent is normed"),
])
def test_each_learned_part_acts(model, leaf, why):
    params, tokens, want = model
    moved = {**params, leaf: params[leaf] * -1.5 if "norm" not in leaf else params[leaf] * 2.0}
    got, _, _ = through_the_cache(moved, tokens, [(32, [30, 30])])
    assert np.abs(got[0][0][1] - want["logits"][0][29]).max() > 1e-3, why


@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=("latent", "full", "latent_sliding", "latent_sliding", "latent_sliding")),
     "do not mix"),
    (dict(layer_types=("latent_sliding",) * 5), "beside at least one latent layer"),
    (dict(kv_latent_rank_sliding=0), "latent_sliding layers need"),
    (dict(q_latent_rank=0), "index_topk"),
    (dict(index_head_dim=4), "index_topk"),
    (dict(attn_gate="channel"), "a gate a head or none"),
    (dict(sliding_window=0), "sliding layers need sliding_window"),
])
def test_plan_refuses_what_the_layers_cannot_be(kw, match):
    with pytest.raises(ValueError, match=match):
        patterned.plan(LlamaConfig.dots3_tiny(**kw))


def test_training_is_refused_by_name():
    """No whole-sequence path selects or windows a latent: ``forward`` (and so
    the train step) says so, and names the way that runs."""
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    with pytest.raises(NotImplementedError, match="run through the cache only"):
        jax.eval_shape(lambda p: forward(p, jnp.zeros((1, 8), jnp.int32), CFG), params)
