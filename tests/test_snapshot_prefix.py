"""Prefix reuse by state snapshot (``llm/engine.py _snapshot_store``,
``_snapshot_lookup``, ``seed_prefix`` with a state): a pool whose slots hold a
state stores what a finished prompt left under the prompt's own length and
seeds a later prompt that starts with it, for a state-space pool (``granite-
tiny``) and a delta-rule pool (``solar-tiny``). A pool without state keeps the
bucket store."""

import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from ray_tpu.models.llama import decode_step, init_kv_cache
from ray_tpu.models.patterned import STATE_LEAVES

SP = SamplingParams(max_tokens=3, temperature=0.0, ignore_eos=True)
# A hit is not computed as its miss was: the scan's chunks and the prompt
# chunks fall elsewhere, so sums are made in another order. In float32 on the
# CPU what a hit leaves (state, keys and values, and the logits behind them)
# differs from a miss's by 0.5e-6 to 1.7e-6 of its size (27 readings over
# granite-tiny, solar-tiny and nemotron-tiny); with the seeding state rounded
# to bfloat16 the state reads 2.2e-4 to 4.2e-3. The tolerance stands ten times
# over the one and ten times under the other.
STATE_TOL = 2e-5
LOGITS_TOL = 2e-5


def _engine(model_id, **engine_kw):
    kw = dict(max_num_seqs=3, max_seq_len=64, dtype="float32",
              prefill_buckets=(8, 16, 32), prefill_chunk=8)
    kw.update(engine_kw)
    return JaxEngine(LLMConfig(model=ModelConfig(model_id=model_id), engine=EngineConfig(**kw)))


@pytest.fixture(scope="module", params=["granite-tiny", "solar-tiny"])
def engine(request):
    eng = _engine(request.param)
    yield eng
    eng.shutdown()


def _bytes(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 256, n)]


def _session(seed):
    """Three turns' prompts: each is the one before, a scripted reply and a
    new user message (19, 31 and 45 tokens behind a 10-token system prompt)."""
    system = _bytes(999, 10)
    one = system + _bytes(seed, 9)
    two = one + _bytes(seed + 1, 5) + _bytes(seed + 2, 7)
    three = two + _bytes(seed + 3, 6) + _bytes(seed + 4, 8)
    return system, [one, two, three]


def _key(ids):
    return hashlib.sha1(np.asarray(ids, np.int32).tobytes()).digest()


def _forget(engine):
    engine._prefix_cache.clear()
    engine._prefix_bytes = 0


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(((a - b) ** 2).sum() / (b ** 2).sum()))


def _entry_errors(engine, got, want, length):
    """How far the snapshot ``got`` lies from ``want`` (of the same prompt):
    the largest relative error over the state leaves and over the keys and
    values up to the prompt's end, and of the logits a decode step of the same
    token gives behind each."""
    cfg = engine.model_cfg
    state = max(_rel(got["state"][n], want["state"][n]) for n in got["state"])
    kv = max(_rel(got[n][:, :, :length], want[n][:, :, :length]) for n in ("k", "v"))

    def logits(entry):
        cache = init_kv_cache(cfg, 1, 64)
        m = entry["k"].shape[2]
        cache = {**cache, **entry["state"],
                 "k": cache["k"].at[:, 0, :, :m].set(entry["k"]),
                 "v": cache["v"].at[:, 0, :, :m].set(entry["v"]),
                 "length": jnp.asarray([length], jnp.int32)}
        return np.asarray(decode_step(engine.params, cache, jnp.asarray([7]), cfg)[0])

    return max(state, kv), _rel(logits(got), logits(want))


def test_a_sessions_turns_hit_at_the_previous_prompts_exact_length(engine):
    """Turn 1 is a miss, turns 2 and 3 are seeded from the turn before at its
    exact length (19 and 31: no bucket's), stored after a hit as after a miss,
    and answer as the same prompts do when nothing is stored; what the hit
    left as its own snapshot agrees with the miss's within the float32
    tolerance, state, keys and values and the logits behind them."""
    _, turns = _session(0)
    _forget(engine)
    cold, cold_entries = [], []
    for ids in turns:  # each alone: a miss
        _forget(engine)
        out = engine.generate(prompt_token_ids=ids, sampling_params=SP)
        assert out.metrics["prefix_hit_tokens"] == 0
        cold.append(out.token_ids)
        cold_entries.append(engine._prefix_cache[_key(ids)])
    _forget(engine)
    before = engine.get_stats()["counters"]
    hits = []
    for ids, want, want_entry in zip(turns, cold, cold_entries):
        out = engine.generate(prompt_token_ids=ids, sampling_params=SP)
        hits.append(out.metrics["prefix_hit_tokens"])
        assert out.token_ids == want
        entry = engine._prefix_cache[_key(ids)]
        assert entry["length"] == len(ids)
        assert entry["state"] and set(entry["state"]) <= set(STATE_LEAVES)
        assert all(x.dtype == jnp.float32 for n, x in entry["state"].items() if n.endswith("state"))
        state_err, logits_err = _entry_errors(engine, entry, want_entry, len(ids))
        assert state_err < STATE_TOL and logits_err < LOGITS_TOL
    assert hits == [0, len(turns[0]), len(turns[1])]
    now = engine.get_stats()
    c = now["counters"]
    assert c["snapshots_stored"] - before["snapshots_stored"] == 3
    assert c["snapshots_hit"] - before["snapshots_hit"] == 2
    assert (c["prompt_tokens_from_prefix"] - before["prompt_tokens_from_prefix"]
            == c["prefix_seed_tokens"] - before["prefix_seed_tokens"] == 19 + 31)
    assert now["prefix_cache_entries"] == 3
    assert now["prefix_cache_bytes"] == sum(e["nbytes"] for e in engine._prefix_cache.values())
    assert "prefix_bypassed_stateful" not in c


def test_the_same_prompt_again_is_no_hit_and_stores_nothing_new(engine):
    """A stored prompt must be strictly shorter than the one it seeds: one
    token at least remains for the last logits."""
    _forget(engine)
    ids = _bytes(5, 21)
    first = engine.generate(prompt_token_ids=ids, sampling_params=SP)
    again = engine.generate(prompt_token_ids=ids, sampling_params=SP)
    assert again.metrics["prefix_hit_tokens"] == 0 and first.token_ids == again.token_ids
    assert len(engine._prefix_cache) == 1


def test_a_state_that_went_through_bfloat16_fails_the_tolerance(engine):
    """The control of ``STATE_TOL``: turn 1's snapshot with its state rounded
    to bfloat16 and back seeds turn 2, and what turn 2 then leaves lies
    outside the tolerance a sound hit keeps."""
    _, turns = _session(20)
    _forget(engine)
    engine.generate(prompt_token_ids=turns[1], sampling_params=SP)
    want = engine._prefix_cache[_key(turns[1])]
    _forget(engine)
    engine.generate(prompt_token_ids=turns[0], sampling_params=SP)
    entry = engine._prefix_cache[_key(turns[0])]
    entry["state"] = {
        n: x.astype(jnp.bfloat16).astype(x.dtype) if n.endswith("state") else x
        for n, x in entry["state"].items()}
    out = engine.generate(prompt_token_ids=turns[1], sampling_params=SP)
    assert out.metrics["prefix_hit_tokens"] == len(turns[0])
    state_err, _ = _entry_errors(engine, engine._prefix_cache[_key(turns[1])], want, len(turns[1]))
    assert state_err > 10 * STATE_TOL


def test_a_snapshot_of_another_session_or_without_its_tail_answers_otherwise(engine):
    """The seed is what the answer rests on: seeded from another session's
    snapshot of the same length, or from its own with the convolution tails
    zeroed, turn 2's snapshot lies far from the miss's."""
    _, turns = _session(40)
    _, other = _session(60)
    _forget(engine)
    engine.generate(prompt_token_ids=turns[1], sampling_params=SP)
    want = engine._prefix_cache[_key(turns[1])]
    for spoil in ("other", "tail"):
        _forget(engine)
        engine.generate(prompt_token_ids=turns[0], sampling_params=SP)
        entry = engine._prefix_cache[_key(turns[0])]
        if spoil == "other":
            engine.generate(prompt_token_ids=other[0], sampling_params=SP)
            theirs = engine._prefix_cache[_key(other[0])]
            entry.update({n: theirs[n] for n in ("k", "v", "state")})
        else:
            entry["state"] = {n: jnp.zeros_like(x) if n.endswith("conv") else x
                              for n, x in entry["state"].items()}
        out = engine.generate(prompt_token_ids=turns[1], sampling_params=SP)
        assert out.metrics["prefix_hit_tokens"] == len(turns[0])
        state_err, _ = _entry_errors(
            engine, engine._prefix_cache[_key(turns[1])], want, len(turns[1]))
        assert state_err > 100 * STATE_TOL
