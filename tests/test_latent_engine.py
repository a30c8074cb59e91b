"""A latent-attention model behind a seeded prefix and in the engine: a prompt
behind a seeded prefix against the prompt computed, the router's sigmoid gate,
the interleaved rotation, the latent decode kernel against the einsum, a hit
served as the miss was, the layout rule, and the paths that refuse a latent
model by name. The model and its path through the cache:
``tests/test_latent.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from ray_tpu.models import patterned
from ray_tpu.models.llama import (
    EMBED_MINOR,
    LlamaConfig,
    forward,
    init_kv_cache,
    prefill,
    serving_layouts,
)
from ray_tpu.models.patterned import _param_shapes
from ray_tpu.ops.decode_attention import (
    BLOCKS,
    block_size,
    latent_decode_attention,
    positions_read,
)
from ray_tpu.parallel.moe import topk_gates
from tests.latent_models import CFG, TOL, model


def test_a_prompt_behind_a_seeded_prefix_equals_the_prompt_computed(model):
    """What the engine's ``seed_prefix`` does: the first 16 tokens' keys and
    latents copied out of one cache into a fresh stripe, the rest prefilled
    behind them; logits and cache equal the prompt computed whole."""
    params, tokens, whole = model
    row = tokens[:1]
    _, computed = prefill(params, init_kv_cache(CFG, 1, 64), row[:, :30], CFG)
    seeded = init_kv_cache(CFG, 1, 64)
    seeded = {**seeded, **{n: seeded[n].at[:, 0, :, :16].set(computed[n][:, 0, :, :16])
                           for n in ("k", "v")}}
    logits, seeded = prefill(params, seeded, row[:, 16:30], CFG,
                             start_pos=jnp.asarray([16], jnp.int32))
    np.testing.assert_allclose(logits[0], whole[0, 29], **TOL)
    for n in ("k", "v"):
        np.testing.assert_allclose(seeded[n], computed[n], atol=2e-6, rtol=1e-5)


def _numpy_gate(x, router, bias, k):
    scores = 1.0 / (1.0 + np.exp(-(x @ router)))
    idx = np.argsort(-(scores + bias), axis=-1, kind="stable")[:, :k]
    top = np.take_along_axis(scores, idx, axis=-1)
    return top / top.sum(-1, keepdims=True), idx


def test_sigmoid_gate_with_a_bias_that_moves_the_choice_and_not_the_weight():
    rng = np.random.default_rng(0)
    x, router = rng.normal(size=(64, 32)).astype(np.float32), rng.normal(size=(32, 16)).astype(np.float32) / 6
    bias = rng.normal(0, 0.3, 16).astype(np.float32)
    params = {"router": jnp.asarray(router), "bias": jnp.asarray(bias)}
    probs, vals, idx = topk_gates(params, jnp.asarray(x), 3)
    want_vals, want_idx = _numpy_gate(x, router, bias, 3)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    order = np.argsort(np.asarray(idx), -1), np.argsort(want_idx, -1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(vals), order[0], -1),
                               np.take_along_axis(want_vals, order[1], -1), rtol=1e-5)
    # the bias changes which experts are chosen ...
    _, _, unbiased = topk_gates({**params, "bias": jnp.zeros(16)}, jnp.asarray(x), 3)
    assert (np.sort(unbiased, -1) != np.sort(idx, -1)).any()
    # ... and is no part of a chosen expert's weight: the scores alone, renormalised
    chosen = np.take_along_axis(np.asarray(probs), np.asarray(idx), -1)
    np.testing.assert_allclose(vals, chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    # without a bias the router is the softmax one, as before
    soft, soft_vals, _ = topk_gates({"router": params["router"]}, jnp.asarray(x), 3)
    np.testing.assert_allclose(soft.sum(-1), 1.0, rtol=1e-5)


def test_interleaved_rotation_against_numpy():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 5, 3, 8)).astype(np.float32)
    pos = np.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    inv, factor = patterned.rope_inv_freq(CFG, "latent")
    got = patterned._rope(jnp.asarray(x), jnp.asarray(pos), inv, factor, interleave=True)
    want = np.empty_like(x)
    for j in range(4):
        ang = pos[..., None] / 1e6 ** (2 * j / 8)
        a, b = x[..., 2 * j], x[..., 2 * j + 1]
        want[..., 2 * j] = a * np.cos(ang) - b * np.sin(ang)
        want[..., 2 * j + 1] = b * np.cos(ang) + a * np.sin(ang)
    np.testing.assert_allclose(got, want, atol=1e-5)


# ------------------------------------------------------------ the decode kernel

L, S, R, DR, H = 3, 4 * 128, 64, 128, 8
BOUNDS = {
    "one-position": 1,
    "one-position-past-a-block": 129,
    "ends-mid-block": 128 + 37,
    "one-whole-block": 128,
    "the-whole-stripe": S,
}


def _latent_einsum(q_rope, q_lat, ck, cv, layer, hi, scale):
    s = (jnp.einsum("bhd,bsd->bhs", q_rope, ck[layer, :, 0])
         + jnp.einsum("bhr,bsr->bhs", q_lat, cv[layer, :, 0])).astype(jnp.float32) * scale
    s = jnp.where(jnp.arange(ck.shape[3])[None, None, :] < hi[:, None, None], s, -1e30)
    return jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, -1).astype(cv.dtype), cv[layer, :, 0])


@pytest.mark.parametrize("dtype, tol", [(jnp.bfloat16, 0.03), (jnp.float32, 2e-5)],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(BOUNDS))
def test_latent_kernel_equals_the_einsum_over_rows_of_unequal_length(monkeypatch, name, dtype, tol):
    """Eight query heads on one shared key whose two parts lie in two leaves
    (rotated key, latent) and whose value is the latent; every row of the
    batch ends somewhere else, 128-position blocks so that rows span one to
    four of them."""
    import ray_tpu.ops.decode_attention as da

    monkeypatch.setattr(da, "BLOCKS", (128,))
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    B = len(BOUNDS)
    q_rope = jax.random.normal(ks[0], (B, H, DR), dtype)
    q_lat = jax.random.normal(ks[1], (B, H, R), dtype)
    ck = jax.random.normal(ks[2], (L, B, 1, S, DR), dtype)
    cv = jax.random.normal(ks[3], (L, B, 1, S, R), dtype)
    order = list(BOUNDS)
    order = order[order.index(name):] + order[:order.index(name)]
    hi = jnp.asarray([BOUNDS[n] for n in order], jnp.int32)
    got = jax.jit(latent_decode_attention, static_argnames="scale")(
        q_rope, q_lat, ck, cv, jnp.int32(1), jnp.zeros_like(hi), hi, scale=0.1)
    want = _latent_einsum(q_rope, q_lat, ck, cv, 1, hi, 0.1)
    assert got.shape == (B, H, R) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_latent_blocks_are_the_longest_that_divide_the_stripe():
    """Whatever a position holds (Kanana-2's 128-lane key and 512-wide latent,
    dots3's 1,024-wide sliding latent), where eight plain heads of as many
    bytes or more take 128."""
    assert BLOCKS == (512, 256, 128)
    for held in ((128 + 512) * 2, (128 + 1024) * 2):
        assert [block_size(s, held, latent=True)
                for s in (24576, 2560, 768, 128, 96)] == [512, 512, 256, 128, None]
    assert block_size(24576, 8 * 256 * 2) == 128
    # a 700-token slot of a 24,576-position stripe: two 512-position blocks
    assert positions_read(0, np.asarray([700, 1]), 24576, 1280, latent=True).tolist() == [1024, 512]


# ------------------------------------------------------------------ the engine


@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="kanana-tiny"),
        engine=EngineConfig(max_num_seqs=4, max_seq_len=256, dtype="float32",
                            prefill_buckets=(32, 64, 128), prefill_chunk=32),
    ))
    yield eng
    eng.shutdown()


def test_engine_serves_a_hit_as_it_served_the_miss_and_counts_both(engine):
    """A 140-token prompt twice, greedy: the second is served behind the
    128-token prefix the first left in the store, token for token; the
    counters of latent layers, of each prefill program's attention and of
    the seeding say what ran."""
    before = engine.get_stats()["counters"]
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(0, 256, 140)]
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    miss = engine.generate(prompt_token_ids=prompt, sampling_params=sp)
    hit = engine.generate(prompt_token_ids=prompt, sampling_params=sp)
    assert (miss.metrics["prefix_hit_tokens"], hit.metrics["prefix_hit_tokens"]) == (0, 128)
    assert miss.token_ids == hit.token_ids
    # and both are greedy over the full forward pass
    tokens = jnp.asarray([prompt + miss.token_ids[:-1]], jnp.int32)
    greedy = np.argmax(np.asarray(forward(engine.params, tokens, engine.model_cfg))[0, 139:], -1)
    assert miss.token_ids == greedy.tolist()
    stats = engine.get_stats()
    now = stats["counters"]
    delta = lambda name, label=None: (  # noqa: E731
        now[name][label] - before[name][label] if label else now[name] - before[name])
    assert delta("prompt_tokens") == 280 and delta("prompt_tokens_from_prefix") == 128
    assert delta("prefix_seed_tokens") == 128
    # the miss: four 32-token middle chunks, then 12 tokens; the hit: 12 behind 128
    assert delta("prefill_query_tokens", "chunk_mid") == 128
    assert delta("prefill_query_tokens", "chunk_final") == 24
    assert delta("prefill_attended_positions", "chunk_mid") == 128 * 129 // 2
    assert delta("prefill_attended_positions", "chunk_final") == 2 * (12 * 128 + 12 * 13 // 2)
    assert delta("decode_kv_tokens_latent") > 0 and delta("decode_kv_tokens_global") == 0
    # a 256-position stripe: the kernel reads one 256-position block a slot and step
    assert engine._pools[0].reads_blocks
    assert delta("decode_kv_positions_read_latent") == 256 * delta("decode_slot_steps")
    (pool,) = stats["pools"]
    assert pool["decode_write"] == "scatter"  # a latent pool's one row a slot
    # 3 layers of 128 (the rotated key's lane row) + 32 (latent) float32 numbers
    assert pool["kv_bytes_per_token"] == 3 * (128 + 32) * 4
    assert "kv_bytes_per_token_held" in pool and stats["prefix_cache_bytes"] > 0
    # every chunk program (all 32 wide) walked the stripe in plain XLA: four heads of 32
    # queries are under the size the chunk kernel is given (as the served model's 32 of 256)
    assert pool["chunk_walks"] == {"32": {"latent": "einsum"}}


def test_the_layout_rule_holds_the_latent_query_projection_embed_minor():
    """A head of 128 + 64 is no whole number of lane tiles: ``embed`` goes on
    the lanes (``tests/test_chip_compile_chunks.py`` counts the copies it saves)."""
    assert serving_layouts(_param_shapes(CFG)) == {"wq_latent": EMBED_MINOR == (0, 2, 3, 1) and EMBED_MINOR}


@pytest.mark.parametrize("module", ["llm/spmd.py", "llm/gang.py", "tensor_parallel_degree"])
def test_the_mesh_paths_refuse_a_latent_model_by_name(module):
    cfg = LLMConfig(model=ModelConfig(model_id="kanana-tiny"),
                    engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32"))
    if module == "llm/spmd.py":
        from ray_tpu.llm.spmd import SPMDGenerator

        build = lambda: SPMDGenerator(cfg)  # noqa: E731
    elif module == "llm/gang.py":
        from ray_tpu.llm.gang import GangLLMServer

        build = lambda: GangLLMServer(cfg, num_workers=2)  # noqa: E731
    else:
        cfg.engine.tensor_parallel_degree = 2
        build = lambda: JaxEngine(cfg)  # noqa: E731
        module = "llm/engine.py over a mesh"
    with pytest.raises(NotImplementedError, match=module.replace(".", r"\.") + ".*latent"):
        build()


def test_latent_pattern_errors_are_named():
    with pytest.raises(ValueError, match="need layer_types"):
        patterned.plan(LlamaConfig.tiny(kv_latent_rank=32))
    with pytest.raises(ValueError, match="need layer_types"):
        patterned.plan(LlamaConfig.tiny(moe_experts=4, moe_scoring="sigmoid"))
    with pytest.raises(ValueError, match="do not mix"):
        patterned.plan(LlamaConfig.kanana_tiny(layer_types=("latent", "full", "latent")))
    with pytest.raises(ValueError, match="latent layers need"):
        patterned.plan(LlamaConfig.kanana_tiny(n_kv_heads=2))
    with pytest.raises(ValueError, match="unknown moe_scoring"):
        LlamaConfig.tiny(moe_scoring="tanh")
