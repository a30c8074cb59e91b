"""The snapshot store's bookkeeping, each test on an engine of its own:
eviction keeps live sessions and the shared system prompt, a pool without
state stores and seeds at buckets as before, and a snapshot's keys and values
come in a few lengths. What a snapshot holds and seeds:
``tests/test_snapshot_prefix.py``."""

from tests.test_snapshot_prefix import SP, _bytes, _engine, _key, _session


def test_eviction_keeps_live_sessions_and_the_shared_system_prompt():
    """Three sessions behind one system prompt under a budget of N + 2
    entries: every turn after the system prompt's own hits at the prompt
    before it, a session's dead turn goes first, and at the end the store
    holds the system prompt and each session's last turn. Plain recency would
    have evicted the system prompt or a live session for a dead turn."""
    eng = _engine("granite-tiny", prefix_cache_entries=5)
    try:
        system, _ = _session(0)
        sessions = [_session(100 * s)[1] for s in range(3)]
        eng.generate(prompt_token_ids=system, sampling_params=SP)
        for turn in range(3):
            for s, turns in enumerate(sessions):
                out = eng.generate(prompt_token_ids=turns[turn], sampling_params=SP)
                want = len(system) if turn == 0 else len(turns[turn - 1])
                assert out.metrics["prefix_hit_tokens"] == want, (turn, s)
                assert len(eng._prefix_cache) <= 5
        held = set(eng._prefix_cache)
        assert _key(system) in held
        assert all(_key(turns[2]) in held for turns in sessions)
        stats = eng.get_stats()
        # 1 + 9 stored, 5 kept; an evicted entry was a session's dead turn
        assert stats["counters"]["snapshots_stored"] == 10
        assert stats["counters"]["snapshots_evicted"] == 5
        assert stats["prefix_cache_bytes"] == sum(e["nbytes"] for e in eng._prefix_cache.values())
    finally:
        eng.shutdown()


def test_a_pool_without_state_stores_and_seeds_at_buckets_as_before():
    """The bucket store to the letter: after a miss one entry a bucket the
    prompt covers, keys and values alone; a hit at the longest bucket stores
    nothing; no snapshot is counted."""
    eng = _engine("tiny")
    try:
        ids = _bytes(1, 40)
        eng.generate(prompt_token_ids=ids, sampling_params=SP)
        assert set(eng._prefix_cache) == {_key(ids[:b]) for b in (8, 16, 32)}
        assert all(set(e) == {"k", "v", "nbytes"} for e in eng._prefix_cache.values())
        assert [e["k"].shape[2] for e in eng._prefix_cache.values()] == [8, 16, 32]
        out = eng.generate(prompt_token_ids=ids[:36] + _bytes(2, 6), sampling_params=SP)
        assert out.metrics["prefix_hit_tokens"] == 32 and len(eng._prefix_cache) == 3
        c = eng.get_stats()["counters"]
        assert c["snapshots_stored"] == c["snapshots_hit"] == c["snapshot_store_bytes"] == 0
        assert c["prefix_seed_tokens"] == 32
    finally:
        eng.shutdown()


def test_a_snapshots_keys_and_values_come_in_a_few_lengths():
    """Stripes of 64 and buckets up to 32: a snapshot's keys and values are
    held 32 or 64 positions long, whatever the prompt's length, so the store
    and the seed are two programs each and all of them are warmed."""
    eng = _engine("granite-tiny")
    try:
        (pool,) = eng._pools
        assert eng._snapshot_lengths(pool) == [32, 64]
        assert "snapshot" in eng.get_stats()["init"]["warm_programs_by_program_s"]
        for n in (9, 31, 32, 33, 50):
            ids = _bytes(n, n)
            eng.generate(prompt_token_ids=ids, sampling_params=SP)
            assert eng._prefix_cache[_key(ids)]["k"].shape[2] == (32 if n <= 32 else 64)
    finally:
        eng.shutdown()
