"""Granite-4.0-H at test size in the engine (``llm/engine.py``): its greedy
tokens against the reference's, a turn seeded from a snapshot that a carrying
launch stored, and the state a slot holds. The model and its path through the
cache: ``tests/test_granite.py``."""

import jax
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from tests.engine_helpers import decoding, programs_replaced
from tests.test_granite import PUBLISHED


@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="granite-tiny"),
        engine=EngineConfig(max_num_seqs=3, max_seq_len=64, dtype="float32",
                            prefill_buckets=(8, 16, 32), prefill_chunk=8),
    ))
    yield eng
    eng.shutdown()


SP = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 256, n)]


def _greedy_by_the_reference(engine, prompt, out):
    from benchmark.reference_ssm_gqa_dense import Reference

    ref = Reference(PUBLISHED, jax.local_devices()[:1])
    row = np.asarray(prompt + out[:-1], np.int32)
    logits = ref.forward_rows(engine.params, [row], last=len(out))["logits"][0]
    return np.argmax(logits, -1).tolist()


def test_the_engines_greedy_tokens_are_the_references(engine):
    """Five prompts at once on three slots (middle chunks as rows of one
    launch, batched decode steps, two waiting for a slot): every answer is the
    reference's greedy one, teacher-forced on the engine's own tokens."""
    prompts = [_prompt(10 + i, n) for i, n in enumerate((29, 27, 30, 12, 25))]
    reqs = [engine.submit(prompt_token_ids=p, sampling_params=SP) for p in prompts]
    for req in reqs:
        engine._await_done(req)
        assert req.error is None
    for p, req in zip(prompts, reqs):
        assert list(req.out_tokens) == _greedy_by_the_reference(engine, p, list(req.out_tokens))


def test_a_turn_seeded_from_a_snapshot_a_carrying_launch_stored_answers_as_the_reference(engine):
    """One request decodes a long answer while a session's first turn is
    admitted: each of the turn's chunk launches carries the other's decode
    step, the final one too, whose scratch stripe the pool stores as the
    prompt's snapshot. Both answer as the reference does (a live row's state
    advanced once a pass, the turn's own state untouched by the rows beside
    it), and the session's next turn, seeded from that snapshot at the stored
    prompt's length, answers as the reference does on the whole prompt."""
    assert all(pool.carries for pool in engine._pools)
    finals = []  # whether a row was live in each final-chunk launch

    def recording(inner):
        def program(*args, **kw):
            finals.append(bool(np.asarray(args[-1]["live"]).any()))
            return inner(*args, **kw)
        return program

    before = engine.get_stats()["counters"]
    long, turn = _prompt(50, 9), _prompt(51, 29)
    with programs_replaced(engine, "chunk_final", recording):
        first = decoding(engine, long, SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True))
        second = engine.submit(prompt_token_ids=turn, sampling_params=SP)
        for req in (first, second):
            engine._await_done(req)
            assert req.error is None
    assert finals == [False, True]  # the turn's final chunk carried the other's step
    for p, req in ((long, first), (turn, second)):
        assert list(req.out_tokens) == _greedy_by_the_reference(engine, p, list(req.out_tokens))
    onward = turn + list(second.out_tokens) + _prompt(52, 7)
    out = engine.generate(prompt_token_ids=onward, sampling_params=SP)
    assert out.metrics["prefix_hit_tokens"] == len(turn)
    assert out.token_ids == _greedy_by_the_reference(engine, onward, out.token_ids)
    c = engine.get_stats()["counters"]
    assert c["decode_steps_in_chunk"] - before["decode_steps_in_chunk"] >= 4  # three middle chunks and the final
    assert c["snapshots_hit"] - before["snapshots_hit"] == 1


def test_the_engine_counts_the_state_a_slot_holds(engine):
    stats = engine.get_stats()
    (pool,) = stats["pools"]
    # 6 mamba layers: a float32 state [8, 16, 16] and 3 inputs of 160 channels
    assert pool["state_bytes_per_slot"] == 6 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert pool["state_mixer_forms"] == {"ssm": {"chunk": "plain", "step": "plain"}}
    # keys and values of the two attention layers: 2 heads of 16, float32
    assert pool["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
