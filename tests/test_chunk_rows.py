"""Admissions that are due in the same pass run their middle chunks as rows
of one chunk program (``llm/engine.py _advance_admissions``): a request's
tokens are what it gets alone, the counters count rows and launches, a replica
that is ready has nothing left to compile, and a launch that fails takes its
rows' requests with it and no others."""

import jax
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams

pytestmark = pytest.mark.timeout(600) if hasattr(pytest.mark, "timeout") else []

FAMILIES = {
    "dense": dict(model_id="tiny"),
    "patterned-moe": dict(model_id="laguna-tiny"),
    "latent": dict(model_id="kanana-tiny"),
}
# 3, 2, 1 and 1 middle chunks of 16, then final chunks of widths 8, 16, 8, 8:
# four middle chunks are due in the first pass, two in the second (beside the
# two final chunks of the prompts that had one), one in the third
LENGTHS = (50, 41, 23, 21)


def _engine(family, dtype="float32", **engine_kw):
    kw = dict(max_num_seqs=4, max_seq_len=128, prefill_chunk=16, prefill_buckets=(8, 16, 32),
              max_concurrent_admissions=4, enable_prefix_caching=False, dtype=dtype)
    kw.update(engine_kw)
    return JaxEngine(LLMConfig(model=ModelConfig(seed=3, **FAMILIES[family]),
                               engine=EngineConfig(**kw)))


def _together(eng, requests):
    """Submit while the loop takes nothing in, so that one pass admits all."""
    eng._pull_waiting = lambda: False  # the loop looks its stages up each pass
    try:
        reqs = [eng.submit(prompt_token_ids=ids, sampling_params=sp, lora=lora)
                for ids, sp, lora in requests]
    finally:
        del eng._pull_waiting
    for req in reqs:
        eng._await_done(req)
    return reqs


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
@pytest.mark.parametrize("family", list(FAMILIES))
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
    eng = _engine(family, dtype, **(dict(max_loras=1, lora_rank=4) if lora else {}))
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


class _Compiles:
    """Programs JAX compiled, or fetched from its compile cache, while open
    (as ``benchmark/trace.py CompileCounter`` counts them in a window)."""

    def __init__(self):
        self.names = []

    def _on_event(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(str(kw.get("fun_name")))

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


@pytest.mark.parametrize("family", ["dense", "patterned-moe"])
def test_a_ready_engine_compiles_nothing_for_a_burst_of_every_width(family):
    """Once the constructor returns, requests of every final width, alone
    and behind middle chunks, seeded and not, greedy and sampled, in bursts
    that pair them, and the same prompts again through the prefix cache,
    reach no program the engine has not run: the benchmark's window counts a
    compilation, or a fetch from the compile cache, as incorrect."""
    eng = _engine(family, enable_prefix_caching=True, prefill_buckets=(8, 16, 32, 64),
                  **(dict(max_loras=1, lora_rank=4) if family == "dense" else {}))
    try:
        rng = np.random.default_rng(7)
        lengths = (3, 8, 12, 16, 19, 27, 32, 40, 45, 64, 100, 126)
        prompts = [[int(t) for t in rng.integers(1, 250, n)] for n in lengths]
        sampling = [SamplingParams(max_tokens=3, ignore_eos=True, temperature=t, seed=s)
                    for t, s in ((0.0, None), (0.9, None), (0.7, 3))]
        with _Compiles() as compiles:
            for again in range(2):  # the second pass is served from the prefix cache
                for i in range(0, len(prompts), 4):
                    reqs = _together(eng, [
                        (ids, sampling[(i + j + again) % 3], None)
                        for j, ids in enumerate(prompts[i:i + 4])])
                    assert [req.error for req in reqs] == [None] * len(reqs)
        assert compiles.names == []
        counters = eng.get_stats()["counters"]
        assert counters["prompt_tokens_from_prefix"] > 0
        assert sum(counters["prefill_programs"].values()) < sum(counters["prefill_chunks"].values())
    finally:
        eng.shutdown()


@pytest.mark.parametrize("program", ["_chunk_mid_jit", "_chunk_final_jit"])
def test_a_launch_that_raises_fails_its_rows_requests_and_no_others(program, monkeypatch):
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
        inner = getattr(eng, program)
        calls = []

        def boom(*a, **kw):
            calls.append(1)
            if len(calls) > 1:  # the pass's later launches, and later passes'
                return inner(*a, **kw)
            raise RuntimeError("injected chunk failure")

        with monkeypatch.context() as m:
            m.setattr(eng, program, boom)
            reqs = _together(eng, [(ids, sp, None) for ids in prompts])
        fails = [0, 1] if program == "_chunk_mid_jit" else [2]
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
