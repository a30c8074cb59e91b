"""A state-space model in the engine (``llm/engine.py``): its answers against
the reference's, a reused slot against a fresh one, requests admitted
together, the state a slot holds and the assignments held, the paths that
refuse a stateful model by name, and the served families' shapes. One engine
serves the module."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, init_kv_cache
from ray_tpu.models.patterned import _param_shapes
from tests.ssm_models import CFG, PUBLISHED


@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="nemotron-tiny"),
        engine=EngineConfig(max_num_seqs=3, max_seq_len=64, dtype="float32",
                            prefill_buckets=(8, 16, 32), prefill_chunk=8),
    ))
    yield eng
    eng.shutdown()


def _greedy_by_the_reference(engine, prompt, out):
    """The reference's greedy token at each position the engine sampled one,
    teacher-forced on the engine's own tokens."""
    from benchmark.reference_ssm_latent_moe import Reference

    ref = Reference(PUBLISHED, jax.local_devices()[:1])
    row = np.asarray(prompt + out[:-1], np.int32)
    logits = ref.forward_rows(engine.params, [row], last=len(out))["logits"][0]
    return np.argmax(logits, -1).tolist()


SP = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 256, n)]


def test_engine_answers_as_the_reference_and_a_reused_slot_as_a_fresh_one(engine):
    """A 29-token prompt (three middle chunks and a final one), another
    through the same slot, then the first again: the slot's second and third
    tenants see nothing of the state the one before left, each answer is the
    reference's greedy one, and the request sent twice answers alike. The
    prefix cache is on and a pool that keeps a state a slot stores a snapshot
    of each prompt: the same prompt again is no hit (a token must remain), a
    prompt that goes on from the first is seeded from it at its exact length
    and answers as the reference does."""
    before = engine.get_stats()["counters"]
    a, b = _prompt(0, 29), _prompt(1, 21)
    first = engine.generate(prompt_token_ids=a, sampling_params=SP)
    other = engine.generate(prompt_token_ids=b, sampling_params=SP)
    again = engine.generate(prompt_token_ids=a, sampling_params=SP)
    assert first.token_ids == again.token_ids
    assert first.token_ids == _greedy_by_the_reference(engine, a, first.token_ids)
    assert other.token_ids == _greedy_by_the_reference(engine, b, other.token_ids)
    assert again.metrics["prefix_hit_tokens"] == 0
    longer = a + _prompt(2, 12)
    onward = engine.generate(prompt_token_ids=longer, sampling_params=SP)
    assert onward.metrics["prefix_hit_tokens"] == 29
    assert onward.token_ids == _greedy_by_the_reference(engine, longer, onward.token_ids)
    stats = engine.get_stats()
    c = stats["counters"]
    assert c["snapshots_stored"] - before["snapshots_stored"] == 3
    assert c["snapshots_hit"] - before["snapshots_hit"] == 1
    assert stats["prefix_cache_entries"] == 3 and stats["prefix_cache_bytes"] > 0


def test_requests_admitted_together_answer_as_each_alone(engine):
    """Five prompts at once on three slots: their middle chunks run as rows
    of one launch where they are due together, decode steps batch them, and
    two wait for a slot another has left. Every answer is the reference's."""
    before = engine.get_stats()["counters"]
    prompts = [_prompt(10 + i, n) for i, n in enumerate((29, 27, 30, 12, 25))]
    reqs = [engine.submit(prompt_token_ids=p, sampling_params=SP) for p in prompts]
    for req in reqs:
        engine._await_done(req)
        assert req.error is None
    for p, req in zip(prompts, reqs):
        assert list(req.out_tokens) == _greedy_by_the_reference(engine, p, list(req.out_tokens))
    now = engine.get_stats()["counters"]
    rows = now["prefill_chunks"]["mid"] - before["prefill_chunks"]["mid"]
    launches = now["prefill_programs"]["mid"] - before["prefill_programs"]["mid"]
    assert rows == 3 + 3 + 3 + 1 + 3 and launches < rows


def test_engine_counts_the_state_a_slot_holds_and_the_assignments_held(engine):
    engine.generate(prompt_token_ids=_prompt(3, 20), sampling_params=SP)
    stats = engine.get_stats()
    (pool,) = stats["pools"]
    # 5 state-space blocks: a float32 state [8, 16, 16] and 3 inputs of 192 channels
    assert pool["state_bytes_per_slot"] == 5 * (8 * 16 * 16 * 4 + 3 * 192 * 4)
    # the tiny preset's 16 x 16 state tiles for no kernel; a chunk has the one form
    assert pool["state_mixer_forms"] == {"ssm": {"chunk": "plain", "step": "plain"}}
    # keys and values of the one attention block: 2 heads of 16, float32
    assert pool["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    c = stats["counters"]
    for program in ("decode", "chunk_mid", "chunk_final"):
        made, held = c["moe_assignments"][program], c["moe_assignments_held"][program]
        # every routed row makes 6 assignments, a launch's rows in each of its layers
        assert 0 < held < made and made % 6 == 0 and made >= 6 * c["moe_layer_steps"][program]
        # a block of sorted rows a layer run: the tiny sizes overflow none
        assert c["moe_passes"][program] == c["moe_layer_steps"][program] > 0
    # 4 of 16 experts held: about a quarter of what the router assigns
    assert 0.1 < sum(c["moe_assignments_held"].values()) / sum(c["moe_assignments"].values()) < 0.4


def test_a_model_whose_slots_are_stripes_alone_counts_no_state():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="laguna-tiny"),
        engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32",
                            prefill_buckets=(16, 32), prefill_chunk=16),
    ))
    try:
        first = eng.generate(prompt_token_ids=_prompt(0, 40), sampling_params=SP)
        again = eng.generate(prompt_token_ids=_prompt(0, 40), sampling_params=SP)
        stats = eng.get_stats()
    finally:
        eng.shutdown()
    assert first.token_ids == again.token_ids and again.metrics["prefix_hit_tokens"] == 32
    assert stats["pools"][0]["state_bytes_per_slot"] == 0
    assert stats["pools"][0]["state_mixer_forms"] == {}
    assert stats["pools"][0]["chunk_walks"] == {}  # no latent cache
    assert stats["counters"]["snapshots_stored"] == stats["counters"]["snapshots_hit"] == 0
    assert set(stats["counters"]["moe_assignments_held"].values()) == {0}
    assert sum(stats["counters"]["moe_assignments"].values()) > 0


@pytest.mark.parametrize("module", ["llm/spmd.py", "llm/gang.py", "llm/disagg.py",
                                    "tensor_parallel_degree"])
def test_the_paths_with_their_own_cache_programs_refuse_a_stateful_model_by_name(module):
    cfg = LLMConfig(model=ModelConfig(model_id="nemotron-tiny"),
                    engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32"))
    if module == "llm/spmd.py":
        from ray_tpu.llm.spmd import SPMDGenerator

        build = lambda: SPMDGenerator(cfg)  # noqa: E731
    elif module == "llm/gang.py":
        from ray_tpu.llm.gang import GangLLMServer

        build = lambda: GangLLMServer(cfg, num_workers=2)  # noqa: E731
    elif module == "llm/disagg.py":
        from ray_tpu.llm.disagg import DecodeWorker, PrefillWorker

        with pytest.raises(NotImplementedError, match=r"llm/disagg\.py.*state-space"):
            DecodeWorker(cfg)
        build = lambda: PrefillWorker(cfg)  # noqa: E731
    else:
        cfg.engine.tensor_parallel_degree = 2
        build = lambda: JaxEngine(cfg)  # noqa: E731
        module = "llm/engine.py over a mesh"
    with pytest.raises(NotImplementedError, match=module.replace(".", r"\.") + ".*state-space"):
        build()


# what the three served families' trees were before blocks could lack a mixer
# or a feed-forward (the parent commit's ``_param_shapes`` at the serving
# cells' depths): every stack still has a row a layer, or a row a layer of its kind
_SERVED_SHAPES = {
    "mistral-7b-serve-l16": (
        lambda: LlamaConfig(vocab_size=32768, d_model=4096, n_layers=16, n_heads=32, n_kv_heads=8,
                            d_ff=14336, max_seq_len=1024, rope_theta=1e6, dtype=jnp.bfloat16),
        {"attn_norm": (16, 4096), "embed": (32768, 4096), "final_norm": (4096,),
         "mlp_norm": (16, 4096), "unembed": (4096, 32768), "w_down": (16, 14336, 4096),
         "w_gate": (16, 4096, 14336), "w_up": (16, 4096, 14336), "wk": (16, 4096, 8, 128),
         "wo": (16, 32, 128, 4096), "wq": (16, 4096, 32, 128), "wv": (16, 4096, 8, 128)}),
    "laguna-xs.2-serve-l5": (
        lambda: LlamaConfig.laguna_xs2(n_layers=5, max_seq_len=4096),
        {"attn_norm": (5, 2048), "embed": (100352, 2048), "final_norm": (2048,),
         "mlp_norm": (5, 2048), "moe_router": (4, 2048, 256), "moe_shared_down": (4, 512, 2048),
         "moe_shared_gate": (4, 2048, 512), "moe_shared_up": (4, 2048, 512),
         "moe_w_down": (4, 256, 512, 2048), "moe_w_gate": (4, 256, 2048, 512),
         "moe_w_up": (4, 256, 2048, 512), "unembed": (2048, 100352), "w_down": (1, 8192, 2048),
         "w_gate": (1, 2048, 8192), "w_up": (1, 2048, 8192), "wg_full": (2, 2048, 48),
         "wg_sliding": (3, 2048, 64), "wk": (5, 2048, 8, 128), "wo_full": (2, 48, 128, 2048),
         "wo_sliding": (3, 64, 128, 2048), "wq_full": (2, 2048, 48, 128),
         "wq_sliding": (3, 2048, 64, 128), "wv": (5, 2048, 8, 128)}),
    "kanana-2-30b-a3b-serve-l5": (
        lambda: LlamaConfig.kanana2_30b_a3b(n_layers=5, max_seq_len=24576),
        {"attn_norm": (5, 2048), "embed": (128256, 2048), "final_norm": (2048,),
         "kv_norm_latent": (5, 512), "mlp_norm": (5, 2048), "moe_router": (4, 2048, 128),
         "moe_router_bias": (4, 128), "moe_shared_down": (4, 1536, 2048),
         "moe_shared_gate": (4, 2048, 1536), "moe_shared_up": (4, 2048, 1536),
         "moe_w_down": (4, 128, 768, 2048), "moe_w_gate": (4, 128, 2048, 768),
         "moe_w_up": (4, 128, 2048, 768), "unembed": (2048, 128256), "w_down": (1, 6144, 2048),
         "w_gate": (1, 2048, 6144), "w_up": (1, 2048, 6144), "wkv_a_latent": (5, 2048, 576),
         "wo_latent": (5, 32, 128, 2048), "wq_latent": (5, 2048, 32, 192),
         "wuk_latent": (5, 32, 128, 512), "wuv_latent": (5, 32, 512, 128)}),
}


@pytest.mark.parametrize("served", sorted(_SERVED_SHAPES))
def test_the_served_families_keep_their_parameter_and_cache_shapes(served):
    make, shapes = _SERVED_SHAPES[served]
    cfg = make()
    assert _param_shapes(cfg) == shapes
    pl = patterned.plan(cfg)
    assert pl.whole and pl.n_ssm == 0 and pl.n_attention == pl.n_mixer == pl.n_ffn == cfg.n_layers
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, 256))
    assert set(cache) == {"k", "v", "length"} and cache["k"].shape[:2] == (cfg.n_layers, 2)
    assert patterned.moe_stats_names(cfg) == patterned.MOE_STATS


def test_pattern_errors_are_named():
    with pytest.raises(ValueError, match="neither a mixer nor a feed-forward"):
        patterned.plan(dataclasses.replace(CFG, mlp_types=("none",) * 11))
    with pytest.raises(ValueError, match="ssm layers need"):
        patterned.plan(dataclasses.replace(CFG, ssm_heads=0))
    with pytest.raises(ValueError, match="outside the router's experts"):
        patterned.plan(dataclasses.replace(CFG, moe_experts_first=13))
    with pytest.raises(ValueError, match="unknown moe_activation"):
        LlamaConfig.tiny(moe_activation="gelu")
