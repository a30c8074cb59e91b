"""``LlamaConfig.zaya_tiny`` through ``JaxEngine``: answers against the
benchmark's plain reference on the benchmark's seeded weights, a slot's later
tenants, rows of one launch, a prompt seeded from a snapshot, the decode rows a
chunk launch carries with their tails, what the engine counts of a slot, and
the paths with their own cache programs refusing the model by its leaves. The
model and its parts: ``tests/test_zaya.py``, ``tests/test_zaya_parts.py``."""

import jax
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from tests.zaya_models import reference, seeded_params


@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="zaya-tiny"),
        engine=EngineConfig(max_num_seqs=3, max_seq_len=64, dtype="float32",
                            prefill_buckets=(8, 16, 32), prefill_chunk=8),
    ))
    # the benchmark's seeded weights, as its tools hand them to an engine
    eng.params = jax.device_put(
        seeded_params(), {k: v.sharding for k, v in eng.params.items()})
    yield eng
    eng.shutdown()


def _greedy_by_the_reference(engine, prompt, out):
    """The reference's greedy token at each position the engine sampled one,
    teacher-forced on the engine's own tokens."""
    row = np.asarray(prompt + out[:-1], np.int32)
    logits = reference().forward_rows(engine.params, [row], last=len(out))["logits"][0]
    return np.argmax(logits, -1).tolist()


SP = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 256, n)]


def test_engine_answers_as_the_reference_and_a_snapshot_seeds_a_longer_prompt(engine):
    """A 29-token prompt (three middle chunks and a final one: the tails cross
    chunk programs), another through the same slot, then the first again: each
    answer is the reference's greedy one. The pool keeps a state a slot (its
    tails), so it stores a snapshot at each prompt's end; a prompt that goes on
    from the first is seeded from it at its exact length, stripes and tails,
    and answers token for token as its miss does."""
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
    c = engine.get_stats()["counters"]
    assert c["snapshots_stored"] - before["snapshots_stored"] == 3
    assert c["snapshots_hit"] - before["snapshots_hit"] == 1
    engine._prefix_cache.clear()
    engine._prefix_bytes = 0
    missed = engine.generate(prompt_token_ids=longer, sampling_params=SP)
    assert missed.metrics["prefix_hit_tokens"] == 0 and missed.token_ids == onward.token_ids


def test_requests_admitted_together_answer_as_each_alone(engine):
    """Five prompts at once on three slots: their middle chunks run as rows
    of one launch where they are due together, a pass's first chunk launch
    carries the pool's decode step (the layers are alike under one loop: the
    decode rows take their tails through the chunk program too), and two wait
    for a slot another has left. Every answer is the reference's."""
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
    assert engine._pools[0].carries
    assert now["decode_steps_in_chunk"] > before["decode_steps_in_chunk"]


def test_a_reused_slot_answers_as_a_fresh_engine_does(engine):
    prompt = _prompt(7, 26)
    for i in range(4):  # every slot gets a tenant first
        engine.generate(prompt_token_ids=_prompt(20 + i, 17 + i), sampling_params=SP)
    used = engine.generate(prompt_token_ids=prompt, sampling_params=SP)
    fresh = JaxEngine(engine.config)
    try:
        fresh.params = engine.params
        new = fresh.generate(prompt_token_ids=prompt, sampling_params=SP)
    finally:
        fresh.shutdown()
    assert used.token_ids == new.token_ids


def test_engine_counts_the_tails_a_slot_holds_and_the_assignments_held(engine):
    engine.generate(prompt_token_ids=_prompt(3, 20), sampling_params=SP)
    stats = engine.get_stats()
    (pool,) = stats["pools"]
    # 3 layers: the last input of two convolutions over 6 heads of 16 and one
    # shifted value head of 16, float32
    assert pool["state_bytes_per_slot"] == 3 * (96 + 96 + 16) * 4
    assert pool["state_mixer_forms"] == {}
    # keys and values of three layers: 2 heads of 16, float32
    assert pool["kv_bytes_per_token"] == 3 * 2 * 2 * 16 * 4
    c = stats["counters"]
    for program in ("decode", "chunk_mid", "chunk_final"):
        made, held = c["moe_assignments"][program], c["moe_assignments_held"][program]
        assert 0 < held < made and made >= c["moe_layer_steps"][program]
        assert c["moe_passes"][program] == c["moe_layer_steps"][program] > 0


@pytest.mark.parametrize("module", ["llm/spmd.py", "llm/gang.py", "llm/disagg.py",
                                    "tensor_parallel_degree"])
def test_the_paths_with_their_own_cache_programs_refuse_the_model_by_its_leaves(module):
    cfg = LLMConfig(model=ModelConfig(model_id="zaya-tiny"),
                    engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32"))
    if module == "llm/spmd.py":
        from ray_tpu.llm.spmd import SPMDGenerator

        build = lambda: SPMDGenerator(cfg)  # noqa: E731
    elif module == "llm/gang.py":
        from ray_tpu.llm.gang import GangLLMServer

        build = lambda: GangLLMServer(cfg, num_workers=2)  # noqa: E731
    elif module == "llm/disagg.py":
        from ray_tpu.llm.disagg import DecodeWorker, PrefillWorker

        with pytest.raises(NotImplementedError, match=r"llm/disagg\.py.*convolved-attention"):
            DecodeWorker(cfg)
        build = lambda: PrefillWorker(cfg)  # noqa: E731
    else:
        cfg.engine.tensor_parallel_degree = 2
        build = lambda: JaxEngine(cfg)  # noqa: E731
        module = "llm/engine.py over a mesh"
    with pytest.raises(NotImplementedError,
                       match=module.replace(".", r"\.") + ".*convolved-attention"):
        build()
