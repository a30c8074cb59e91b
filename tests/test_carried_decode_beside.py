"""The loop that launches carrying chunks (``llm/engine.py
_advance_admissions``) against the loop that does not, family by family:
requests admitted beside decoding rows get the tokens they get alone. The
loop's other rules: ``tests/test_carried_decode_loop.py``; the subject:
``tests/test_carried_decode.py``."""

import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from tests.engine_helpers import CARRYING, decoding, tiny_engine as _engine
from tests.test_carried_decode_loop import _flat, _grew

pytestmark = pytest.mark.timeout(900) if hasattr(pytest.mark, "timeout") else []


# the stacks of several traced bodies admit two at a time: a launch of two
# rows beside carrying launches of one, and two row counts fewer to compile
ADMISSIONS = {"routed-window": 2, "state-space": 2}


@pytest.mark.parametrize("family, runahead", [(name, 1) for name in CARRYING] + [("dense", 0)])
def test_requests_admitted_beside_decoding_rows_get_the_tokens_they_get_alone(family, runahead):
    """A request decodes a long answer while three more are admitted, their
    prompts of one to four chunks: the chunk launches carry the first one's
    (then the others') decode steps, in a stack of several bodies through
    each live row's window, state and tails. Each request, greedy or seeded,
    gets at float32 the tokens it gets when the engine serves it alone, where
    no launch carries anything; also with no run-ahead, where a step that
    decoded a slot is fetched in the pass after the chunk that gave it its
    first token."""
    eng = _engine(family, decode_runahead=runahead,
                  max_concurrent_admissions=ADMISSIONS.get(family, 4))
    try:
        assert all(pool.carries for pool in eng._pools)
        rng = np.random.default_rng(23)
        prompts = [[int(t) for t in rng.integers(1, 250, n)] for n in (7, 52, 21, 40)]
        sampling = [
            SamplingParams(max_tokens=60, temperature=0.0, ignore_eos=True),
            SamplingParams(max_tokens=9, temperature=0.9, seed=4, ignore_eos=True),
            SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True),
            SamplingParams(max_tokens=7, temperature=1.1, top_k=6, seed=8, ignore_eos=True),
        ]
        before = _flat(eng)
        alone = [eng.generate(prompt_token_ids=ids, sampling_params=sp).token_ids
                 for ids, sp in zip(prompts, sampling)]
        assert "decode_steps_in_chunk" not in _grew(eng, before)
        before = _flat(eng)
        first = decoding(eng, prompts[0], sampling[0])
        rest = [eng.submit(prompt_token_ids=ids, sampling_params=sp)
                for ids, sp in zip(prompts[1:], sampling[1:])]
        for req in (first, *rest):
            eng._await_done(req)
            assert req.error is None
        assert [req.out_tokens for req in (first, *rest)] == alone
        grew = _grew(eng, before)
        assert 0 < grew["decode_steps_in_chunk"] <= grew["decode_steps"]
    finally:
        eng.shutdown()
