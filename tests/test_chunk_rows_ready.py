"""Behind the rows of one chunk program (``tests/test_chunk_rows.py``): a replica
that is ready has nothing left to compile, and a launch that fails takes its
rows' requests with it and no others."""

import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from tests.engine_helpers import ROUTED_WINDOW, Compiles, programs_replaced
from tests.engine_helpers import together as _together, tiny_engine as _engine

pytestmark = pytest.mark.timeout(600) if hasattr(pytest.mark, "timeout") else []


@pytest.mark.parametrize("family", ["dense", ROUTED_WINDOW])
def test_a_ready_engine_compiles_nothing_for_a_burst_of_every_width(family):
    """Once the constructor returns, requests of every final width, alone
    and behind middle chunks, seeded and not, greedy and sampled, in bursts
    that pair them, and the same prompts again through the prefix cache,
    reach no program the engine has not run: no form is compiled, restored
    or refused behind the constructor (``get_stats()["init"]["programs"]``),
    and JAX compiles nothing else either (the benchmark's window counts a
    compilation, or a fetch from the compile cache, as incorrect)."""
    eng = _engine(family, enable_prefix_caching=True, prefill_buckets=(8, 16, 32, 64),
                  **(dict(max_loras=1, lora_rank=4) if family == "dense" else {}))
    try:
        rng = np.random.default_rng(7)
        lengths = (3, 8, 12, 16, 19, 27, 32, 40, 45, 64, 100, 126)
        prompts = [[int(t) for t in rng.integers(1, 250, n)] for n in lengths]
        sampling = [SamplingParams(max_tokens=3, ignore_eos=True, temperature=t, seed=s)
                    for t, s in ((0.0, None), (0.9, None), (0.7, 3))]
        ready = eng.get_stats()["init"]["programs"]
        assert ready["compiled"] + ready["restored"] == len(eng._programs) and not ready["fallback"]
        with Compiles() as compiles:
            for again in range(2):  # the second pass is served from the prefix cache
                for i in range(0, len(prompts), 4):
                    reqs = _together(eng, [
                        (ids, sampling[(i + j + again) % 3], None)
                        for j, ids in enumerate(prompts[i:i + 4])])
                    assert [req.error for req in reqs] == [None] * len(reqs)
        assert compiles.names == []
        assert eng.get_stats()["init"]["programs"] == ready
        counters = eng.get_stats()["counters"]
        assert counters["prompt_tokens_from_prefix"] > 0
        assert sum(counters["prefill_programs"].values()) < sum(counters["prefill_chunks"].values())
    finally:
        eng.shutdown()


@pytest.mark.parametrize("program", ["chunk_mid", "chunk_final"])
def test_a_launch_that_raises_fails_its_rows_requests_and_no_others(program):
    """Two prompts whose middle chunks pair and a third with a final chunk
    alone, in one pass. The pair's program raises: both of its requests fail
    with that error under ``admission``, their slots are free again, and the
    third request is served. Or the final chunk's program raises: that one
    request fails, and the pair (whose final chunks come once the program is
    whole again) is served. The loop serves the next request either way."""
    eng = _engine("dense")
    try:
        rng = np.random.default_rng(1)
        prompts = [[int(t) for t in rng.integers(1, 250, n)] for n in (40, 44, 5)]
        sp = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
        alone = [eng.generate(prompt_token_ids=ids, sampling_params=sp).token_ids
                 for ids in prompts]
        before = eng.get_stats()["counters"]["requests_failed"]
        calls = []

        def failing_once(inner):
            def boom(*a, **kw):
                calls.append(1)
                if len(calls) > 1:  # the pass's later launches, and later passes'
                    return inner(*a, **kw)
                raise RuntimeError("injected chunk failure")

            return boom

        with programs_replaced(eng, program, failing_once):
            reqs = _together(eng, [(ids, sp, None) for ids in prompts])
        fails = [0, 1] if program == "chunk_mid" else [2]
        for i, req in enumerate(reqs):
            if i in fails:
                assert isinstance(req.error, RuntimeError) and "injected" in str(req.error)
            else:
                assert req.error is None and req.out_tokens == alone[i]
        stats = eng.get_stats()
        failed = stats["counters"]["requests_failed"]
        assert {k: failed[k] - before[k] for k in failed} == {
            "submit": 0, "admission": len(fails), "decode": 0, "loop_exit": 0}
        assert stats["admitting"] == 0 and stats["active_slots"] == 0
        assert eng.generate(prompt_token_ids=prompts[0], sampling_params=sp).token_ids == alone[0]
    finally:
        eng.shutdown()
