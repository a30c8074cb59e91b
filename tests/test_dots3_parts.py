"""The parts ``dots3-tiny`` adds, each alone: the choice of the k largest
without a sort, the gathered read of a decode step, the held shares of an
expert layer behind a dense one, and that the accepted families' programs are
what they were. The model through the cache: ``tests/test_dots3.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.decode_attention import latent_decode_attention, sparse_latent_decode_attention
from tests import test_granite
from tests.dots3_models import CFG, PUBLISHED, held, reference, seeded_params

BANKS = ("moe_w_gate", "moe_w_up", "moe_w_down")


@pytest.mark.parametrize("k", [1, 7, 40, 250, 300])
def test_kept_is_the_stable_sorts_first_k(k):
    """Ties (scores rounded to thirds, a run of zeros with a ``-0.0`` in it)
    go to the lower position; positions masked to ``-inf`` are kept only once
    the others are used up."""
    rng = np.random.default_rng(k)
    x = (np.round(rng.normal(size=(5, 300)) * 3) / 3).astype(np.float32)
    x[:, 250:] = -np.inf
    x[0, :10], x[0, 3] = 0.0, -0.0
    got = np.asarray(jax.jit(lambda s: patterned._kept(s, k))(jnp.asarray(x)))
    order = np.argsort(-x, axis=-1, kind="stable")
    want = np.zeros_like(got)
    np.put_along_axis(want, order[:, :k], True, axis=-1)
    np.testing.assert_array_equal(got, want)


def test_the_gathered_read_is_the_kernels_read_where_everything_is_chosen():
    """Rows of 300 and 37 live positions of a 512-position stripe, every live
    position chosen (in a shuffled order, the rest of the 320 entries past the
    row's end): the context the decode kernel gives between 0 and the length."""
    rng = np.random.default_rng(0)
    L, B, S, R, H = 2, 2, 512, 64, 8
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    ck, cv, q_rope, q_lat = f(L, B, 1, S, 128), f(L, B, 1, S, R), f(B, H, 128), f(B, H, R)
    hi = jnp.asarray([300, 37], jnp.int32)
    chosen = np.stack([rng.permutation(320) for _ in range(B)]).astype(np.int32)
    chosen[1] = np.where(chosen[1] < 37, chosen[1], 400 + chosen[1] % 100)
    got = sparse_latent_decode_attention(q_rope, q_lat, ck, cv, 1, jnp.asarray(chosen), hi, 0.1)
    want = latent_decode_attention(q_rope, q_lat, ck, cv, 1, jnp.zeros_like(hi), hi, 0.1)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_references_layer():
    """Expert layer 1 of ``dots3-tiny`` on the stream behind layer 0: each of
    sixteen chips holds one of the 16 experts, the router and the shared expert
    whole (counted once); the shares' routed parts and the shared expert sum
    to what the plain reference's uncut layer adds to the stream."""
    params = seeded_params()
    ref = reference()
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 12, 64), jnp.float32)
    want = np.asarray(ref._feed_forward(params, 1, [x])[0][0] - x)
    h = patterned._rmsnorm(x, params["mlp_norm"][1], CFG.rms_eps)
    with jax.default_matmul_precision("highest"):
        shared = patterned._shared_expert(
            {n: params[n][0] for n in params if n.startswith("moe_shared_")}, h)
        total, on_held = shared, 0
        for first in range(16):
            cfg = dataclasses.replace(CFG, moe_experts_held=1, moe_experts_first=first)
            share = {**params, **{k: params[k][:, first:first + 1] for k in BANKS}}
            y, stats = patterned._moe_decode_ffn(share, 0, h, cfg)
            counts = dict(zip(patterned.moe_stats_names(cfg), np.asarray(stats)))
            total, on_held = total + (y - shared), on_held + counts["assignments_held"]
    assert on_held == 12 * CFG.moe_top_k
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)


def test_a_held_share_behind_a_dense_layer_counts_six_numbers_a_layer():
    """Layer 0 is dense and the others hold a share: the dense layer's zeros
    are as long as a held layer's counts (``moe_stats_names``)."""
    from ray_tpu.models.llama import init_kv_cache, prefill

    cfg = dataclasses.replace(CFG, moe_experts_held=8)
    params = seeded_params(published=held(PUBLISHED, 8))
    cache = {**init_kv_cache(cfg, 1, 64), "moe_stats": jnp.zeros((6,), jnp.int32)}
    _, cache = jax.jit(lambda p, c, t: prefill(p, c, t, cfg))(
        params, cache, jnp.zeros((1, 8), jnp.int32))
    counts = dict(zip(patterned.moe_stats_names(cfg), np.asarray(cache["moe_stats"])))
    assert counts["layer_steps"] == 4 and counts["assignments"] == 4 * 8 * cfg.moe_top_k
    assert 0 < counts["assignments_held"] < counts["assignments"]


@pytest.mark.parametrize("chunk", [False, True], ids=["decode", "chunk"])
@pytest.mark.parametrize("preset", ["kanana_tiny", "laguna_tiny", "tiny"])
def test_the_accepted_forms_programs_are_what_the_parent_traced(preset, chunk):
    """Kanana's (one latent kind, no query latent, no gate), Laguna's (window
    and gate on grouped-query layers) and Mistral's (layers alike): no
    operation more, fewer or other than before the second latent kind, the
    query latent and the indexer came in (``tests/test_granite.py``'s
    digests, which PR 51 left as they were)."""
    cfg = getattr(LlamaConfig, preset)()
    assert not cfg.index_topk and not cfg.q_latent_rank and not cfg.latent_rescale
    assert test_granite._digest(cfg, chunk) == test_granite._PARENT[preset][chunk]


def test_the_layout_rule_holds_both_latent_query_projections_embed_minor():
    from ray_tpu.models.llama import EMBED_MINOR, serving_layouts

    assert serving_layouts(patterned._param_shapes(CFG)) == {
        "wq_latent": EMBED_MINOR, "wq_latent_sliding": EMBED_MINOR}
