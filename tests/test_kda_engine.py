"""A delta-rule linear-attention model (``LlamaConfig.solar_tiny``: the cut the
cell serves, at test size) through ``JaxEngine``: answers against the
benchmark's plain reference, a slot's second tenant and a fresh engine, rows
of one launch, the state a slot holds and the assignments held as the engine
counts them, and the paths with their own cache programs refusing the model by
name. The operation, the model and the programs: ``tests/test_kda.py`` and its
parts."""

import jax
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from tests.engine_helpers import decoding
from tests.kda_models import PUBLISHED

@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="solar-tiny", model_kwargs=dict(n_layers=4, gqa_layers=(3,))),
        engine=EngineConfig(max_num_seqs=3, max_seq_len=64, dtype="float32",
                            prefill_buckets=(8, 16, 32), prefill_chunk=8),
    ))
    yield eng
    eng.shutdown()


def _greedy_by_the_reference(engine, prompt, out):
    """The reference's greedy token at each position the engine sampled one,
    teacher-forced on the engine's own tokens."""
    from benchmark.reference_kda_moe import Reference

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


def test_a_reused_slot_answers_as_a_fresh_engine_does(engine):
    """The engine's slots have all held other requests by now; a fresh engine
    of the same seed gives the same tokens for a new prompt."""
    prompt = _prompt(7, 26)
    for i in range(4):  # every slot gets a tenant first
        engine.generate(prompt_token_ids=_prompt(20 + i, 17 + i), sampling_params=SP)
    used = engine.generate(prompt_token_ids=prompt, sampling_params=SP)
    fresh = JaxEngine(engine.config)
    try:
        new = fresh.generate(prompt_token_ids=prompt, sampling_params=SP)
    finally:
        fresh.shutdown()
    assert used.token_ids == new.token_ids


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


def test_requests_admitted_beside_decoding_rows_answer_as_each_alone(engine):
    """One request decodes a long answer while two more are admitted, their
    prompts of four chunks: the chunk launches carry its (then their) decode
    steps, so a live row's delta-rule state and convolution tails advance
    inside a chunk program, once a pass. Every request gets the tokens it
    gets when the engine serves it alone, where no launch carries anything."""
    assert all(pool.carries for pool in engine._pools)
    prompts = [_prompt(40 + i, n) for i, n in enumerate((9, 29, 26))]
    sampling = [SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True) for n in (40, 6, 9)]
    before = engine.get_stats()["counters"]["decode_steps_in_chunk"]
    alone = [engine.generate(prompt_token_ids=p, sampling_params=sp).token_ids
             for p, sp in zip(prompts, sampling)]
    assert engine.get_stats()["counters"]["decode_steps_in_chunk"] == before
    first = decoding(engine, prompts[0], sampling[0])
    rest = [engine.submit(prompt_token_ids=p, sampling_params=sp)
            for p, sp in zip(prompts[1:], sampling[1:])]
    for req in (first, *rest):
        engine._await_done(req)
        assert req.error is None
    assert [list(req.out_tokens) for req in (first, *rest)] == [list(t) for t in alone]
    assert engine.get_stats()["counters"]["decode_steps_in_chunk"] > before


def test_engine_counts_the_state_a_slot_holds_and_the_assignments_held(engine):
    engine.generate(prompt_token_ids=_prompt(3, 20), sampling_params=SP)
    stats = engine.get_stats()
    (pool,) = stats["pools"]
    # 3 delta-rule layers: a float32 state [4, 16, 16] and 3 inputs of 192 channels
    assert pool["state_bytes_per_slot"] == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    # which program the run measured: the tiny preset's 16 x 16 state and
    # chunks of 8 tile for neither kernel (``ops/kda.py scan_heads``, ``step_heads``)
    assert pool["state_mixer_forms"] == {"kda": {"chunk": "plain", "step": "plain"}}
    # keys and values of the one attention layer: 2 heads of 16, float32
    assert pool["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    c = stats["counters"]
    for program in ("decode", "chunk_mid", "chunk_final"):
        made, held = c["moe_assignments"][program], c["moe_assignments_held"][program]
        assert 0 < held < made and made % 4 == 0 and made >= 4 * c["moe_layer_steps"][program]
        # a block of sorted rows a layer run: the tiny sizes overflow none
        assert c["moe_passes"][program] == c["moe_layer_steps"][program] > 0
    # 4 of 16 experts held: about a quarter of what the router assigns
    assert 0.1 < sum(c["moe_assignments_held"].values()) / sum(c["moe_assignments"].values()) < 0.4


@pytest.mark.parametrize("module", ["llm/spmd.py", "llm/gang.py", "llm/disagg.py",
                                    "tensor_parallel_degree"])
def test_the_paths_with_their_own_cache_programs_refuse_a_delta_rule_model_by_name(module):
    cfg = LLMConfig(model=ModelConfig(model_id="solar-tiny"),
                    engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32"))
    if module == "llm/spmd.py":
        from ray_tpu.llm.spmd import SPMDGenerator

        build = lambda: SPMDGenerator(cfg)  # noqa: E731
    elif module == "llm/gang.py":
        from ray_tpu.llm.gang import GangLLMServer

        build = lambda: GangLLMServer(cfg, num_workers=2)  # noqa: E731
    elif module == "llm/disagg.py":
        from ray_tpu.llm.disagg import DecodeWorker, PrefillWorker

        with pytest.raises(NotImplementedError, match=r"llm/disagg\.py.*delta-rule"):
            DecodeWorker(cfg)
        build = lambda: PrefillWorker(cfg)  # noqa: E731
    else:
        cfg.engine.tensor_parallel_degree = 2
        build = lambda: JaxEngine(cfg)  # noqa: E731
        module = "llm/engine.py over a mesh"
    with pytest.raises(NotImplementedError, match=module.replace(".", r"\.") + ".*delta-rule"):
        build()
