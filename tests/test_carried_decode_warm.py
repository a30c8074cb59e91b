"""What an engine compiles before it takes requests, family by family: one
form of each chunk program, and a burst beside decoding rows compiles none.
The subject: ``tests/test_carried_decode.py``."""

import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from tests.engine_helpers import CARRYING, FAMILIES, Compiles, decoding, launched_forms
from tests.engine_helpers import tiny_engine as _engine

pytestmark = pytest.mark.timeout(900) if hasattr(pytest.mark, "timeout") else []


@pytest.mark.parametrize("family", list(FAMILIES))
def test_an_engine_warms_one_form_of_each_program_and_a_burst_compiles_none(family):
    """A pool holds one form of each chunk program, the one its launches
    run: a middle chunk a row count, a final chunk a width, one decode
    program (the engine before this one compiled the first final chunk
    twice, once for the pool's cache as it was made), each compiled or
    restored once and none refused. Requests admitted beside decoding rows
    then reach no program that was not run: the forms and their counts stay,
    and JAX compiles nothing else."""
    eng = _engine(family, prefill_buckets=(8, 16, 32, 64))
    try:
        pool = eng._pools[0]
        mid, finals = eng._chunk_widths(pool)
        held = launched_forms(eng)
        # (a pool that generates by blocks steps by ``block_step`` where the others decode)
        step = "block_step" if pool.block_length else "decode"
        assert {name: held.get(name, 0) for name in ("chunk_mid", "chunk_final", step)} == {
            "chunk_mid": pool.chunk_rows if mid else 0, "chunk_final": len(finals), step: 1}
        ready = eng.get_stats()["init"]["programs"]
        assert ready["compiled"] + ready["restored"] == len(eng._programs) and not ready["fallback"]
        rng = np.random.default_rng(3)
        sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
        with Compiles() as burst:
            first = decoding(eng, [int(t) for t in rng.integers(1, 250, 5)], sp)
            reqs = [first] + [
                eng.submit(prompt_token_ids=[int(t) for t in rng.integers(1, 250, n)],
                           sampling_params=SamplingParams(max_tokens=4, ignore_eos=True,
                                                          temperature=t, seed=s))
                for n, t, s in ((3, 0.0, None), (20, 0.9, None), (40, 0.7, 3), (70, 0.0, None))]
            for req in reqs:
                eng._await_done(req)
                assert req.error is None
        assert burst.names == []
        assert launched_forms(eng) == held and eng.get_stats()["init"]["programs"] == ready
        assert pool.carries == (family in CARRYING)
        assert (eng._n["decode_steps_in_chunk"] > 0) == pool.carries
    finally:
        eng.shutdown()
