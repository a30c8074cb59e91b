"""Admissions that are due in the same pass run their middle chunks as rows
of one chunk program (``llm/engine.py _advance_admissions``): a request's
tokens are what it gets alone, the counters count rows and launches, a replica
that is ready has nothing left to compile, and a launch that fails takes its
rows' requests with it and no others."""

import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from tests.engine_helpers import ROUTED_WINDOW, together as _together, tiny_engine as _engine

pytestmark = pytest.mark.timeout(600) if hasattr(pytest.mark, "timeout") else []

# 3, 2, 1 and 1 middle chunks of 16, then final chunks of widths 8, 16, 8, 8:
# four middle chunks are due in the first pass, two in the second (beside the
# two final chunks of the prompts that had one), one in the third
LENGTHS = (50, 41, 23, 21)


def _requests(lora):
    rng = np.random.default_rng(34)
    prompts = [[int(t) for t in rng.integers(1, 250, n)] for n in LENGTHS]
    sampling = [
        SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True),
        SamplingParams(max_tokens=5, temperature=0.8, seed=11, ignore_eos=True),
        SamplingParams(max_tokens=7, temperature=1.2, top_k=8, seed=5, ignore_eos=True),
        SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True),
    ]
    return list(zip(prompts, sampling, (None, None, None, lora)))


def _launches(eng, log):
    """Record each launch's rows as (chunk width, final or not)."""
    for name in ("_launch_mid_chunks", "_launch_final_chunk"):
        def recording(pool, adms, carry=None, inner=getattr(eng, name)):
            rows = adms if isinstance(adms, list) else [adms]
            log.append([adm.chunks[adm.idx][0].shape[1:] + (adm.chunks[adm.idx][3],)
                        for adm in rows])
            return inner(pool, adms, carry)

        setattr(eng, name, recording)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", ["dense", ROUTED_WINDOW, "latent"])
def test_rows_of_one_program_give_each_request_the_tokens_it_gets_alone(family, dtype):
    """Four prompts of unlike lengths, with their own seeds and temperatures
    (and, where layers are alike, one of them under a LoRA adapter), admitted
    in one pass: each gets the tokens it gets when it is submitted alone, to
    the token at either dtype (on the CPU; on the chip a row's keys and
    values differ in the last bit beside a companion, PERF.md section 6, PR
    34, which is why a final chunk stays a launch of its own).
    ``prefill_chunks`` counts a prompt chunk, so it grows as it does alone;
    ``prefill_programs`` counts launches and grows by fewer; a launch of
    several rows holds middle chunks only, as many as were due. A latent
    model's chunks are admitted together and still launched one by one."""
    lora = "a" if family == "dense" else None
    eng = _engine(family, dtype=dtype, **(dict(max_loras=1, lora_rank=4) if lora else {}))
    try:
        if lora:
            rng = np.random.default_rng(0)
            eng.add_lora(lora, {k: rng.normal(scale=0.5, size=v.shape[:1] + v.shape[2:])
                                .astype(np.float32) for k, v in eng.loras.items()})
        requests = _requests(lora)

        def grown(run):
            before = eng.get_stats()["counters"]
            out = run()
            after = eng.get_stats()["counters"]
            return out, {k: {kind: after[k][kind] - before[k][kind] for kind in after[k]}
                         for k in ("prefill_chunks", "prefill_programs")}

        alone, by_one = grown(lambda: [
            eng.generate(prompt_token_ids=ids, sampling_params=sp, lora=name).token_ids
            for ids, sp, name in requests])
        log = []
        _launches(eng, log)
        reqs, by_rows = grown(lambda: _together(eng, requests))
        del eng._launch_mid_chunks, eng._launch_final_chunk
        assert [req.error for req in reqs] == [None] * 4
        assert [req.out_tokens for req in reqs] == alone
        if lora:  # the adapter's row is not the base model's
            base = eng.generate(prompt_token_ids=requests[3][0], sampling_params=requests[3][1])
            assert base.token_ids != alone[3]
        assert by_one["prefill_chunks"] == by_one["prefill_programs"] == {"mid": 7, "final": 4}
        assert by_rows["prefill_chunks"] == by_one["prefill_chunks"]
        if family == "latent":  # its chunks stay one to a launch (``JaxEngine.__init__``)
            assert by_rows["prefill_programs"] == by_one["prefill_programs"]
            assert [len(rows) for rows in log] == [1] * 11
        else:
            assert by_rows["prefill_programs"] == {"mid": 3, "final": 4}
            assert sorted(len(rows) for rows in log) == [1, 1, 1, 1, 1, 2, 4]
        for rows in log:
            assert len(rows) == 1 or not any(final for _, final in rows), rows
            assert len(set(rows)) == 1, rows  # one program, one width
    finally:
        eng.shutdown()
