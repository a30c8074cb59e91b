"""``dots3-tiny`` in the engine: a prefix hit answers as its miss and seeds
every stripe (the indexed layers' index keys, the sliding layers' windows),
the engine's answers are the reference's, the counters of the indexer, and the
paths that refuse the model by name."""

import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from tests.dots3_models import reference, seeded_params


@pytest.fixture(scope="module")
def engine():
    """Its chunk programs read the cache through the chunk kernel, as the
    served model's do from 128 tokens a chunk: four heads of 32 queries are
    far under the size ``chunk_walks`` gives the kernel, so the size is put
    aside for this file."""
    from ray_tpu.models import patterned

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(patterned, "_CHUNK_KERNEL_MIN_SCORE_BYTES", 0)
        eng = JaxEngine(LLMConfig(
            model=ModelConfig(model_id="dots3-tiny"),
            engine=EngineConfig(max_num_seqs=2, max_seq_len=256, dtype="float32",
                                prefill_buckets=(32, 128), prefill_chunk=32),
        ))
        eng.params = seeded_params()
        yield eng
        eng.shutdown()


def test_a_hit_answers_as_its_miss_and_as_the_reference_and_seeds_every_stripe(engine):
    """A 140-token prompt twice, greedy, 8 tokens: the second is served behind
    the 128 tokens the first left in the store, of every stripe leaf; both
    answer as the reference's whole pass, whose queries attend 8 chosen
    positions of up to 147 and windows of 5."""
    before = engine.get_stats()["counters"]
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(0, 256, 140)]
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    miss = engine.generate(prompt_token_ids=prompt, sampling_params=sp)
    hit = engine.generate(prompt_token_ids=prompt, sampling_params=sp)
    assert (miss.metrics["prefix_hit_tokens"], hit.metrics["prefix_hit_tokens"]) == (0, 128)
    assert miss.token_ids == hit.token_ids
    row = np.asarray(prompt + miss.token_ids[:-1], np.int32)
    want = reference().forward_rows(engine.params, [row], last=8)["logits"][0]
    assert miss.token_ids == np.argmax(want, -1).tolist()
    entry = max(engine._prefix_cache.values(), key=lambda e: e["k"].shape[2])
    assert entry["k"].shape[2] == 128 and {
        name: x.shape for name, x in entry["more"].items()} == {
        "k_sliding": (3, 1, 128, 128), "v_sliding": (3, 1, 128, 48), "k_index": (2, 1, 128, 128)}
    assert entry["nbytes"] == 128 * engine._pools[0].kv_bytes_per_token
    now = engine.get_stats()["counters"]
    delta = lambda name: now[name] - before[name]  # noqa: E731
    assert delta("prefix_seed_tokens") == 128
    # every decode launch scores a row's live positions whole and keeps 8; 5 lie in a window
    rows = delta("decode_slot_steps")
    assert rows >= 14 and delta("index_positions_scored") == delta("decode_kv_tokens_latent") > 140 * rows
    assert delta("index_positions_selected") == 8 * rows
    assert delta("decode_kv_tokens_window") == 5 * rows
    (pool,) = engine.get_stats()["pools"]
    assert pool["kv_bytes_per_token"] == (2 * (128 + 32 + 128) + 3 * (128 + 48)) * 4
    # both latent kinds' chunk programs (32 wide) went through the chunk kernel
    assert pool["chunk_walks"] == {"32": {"latent": "kernel", "latent_sliding": "kernel"}}


def test_requests_admitted_together_answer_as_each_alone(engine):
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(0, 256, n)] for n in (40, 75)]
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    alone = [engine.generate(prompt_token_ids=p, sampling_params=sp).token_ids for p in prompts]
    engine._prefix_cache.clear()
    reqs = [engine.submit(prompt_token_ids=p, sampling_params=sp) for p in prompts]
    for req in reqs:
        engine._await_done(req)
    assert [list(r.out_tokens) for r in reqs] == alone


@pytest.mark.parametrize("module", ["llm/spmd.py", "llm/gang.py", "tensor_parallel_degree",
                                    "llm/disagg.py"])
def test_the_paths_with_their_own_cache_programs_refuse_the_model_by_name(module):
    cfg = LLMConfig(model=ModelConfig(model_id="dots3-tiny"),
                    engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32"))
    match = module.replace(".", r"\.") + ".*latent"
    if module == "llm/spmd.py":
        from ray_tpu.llm.spmd import SPMDGenerator

        build = lambda: SPMDGenerator(cfg)  # noqa: E731
    elif module == "llm/gang.py":
        from ray_tpu.llm.gang import GangLLMServer

        build = lambda: GangLLMServer(cfg, num_workers=2)  # noqa: E731
    elif module == "llm/disagg.py":
        from ray_tpu.llm.disagg import DecodeWorker, PrefillWorker

        match = r"llm/disagg\.py.*k_sliding, v_sliding, k_index"
        with pytest.raises(NotImplementedError, match=match):
            DecodeWorker(cfg)
        build = lambda: PrefillWorker(cfg)  # noqa: E731
    else:
        cfg.engine.tensor_parallel_degree = 2
        build = lambda: JaxEngine(cfg)  # noqa: E731
        match = r"llm/engine\.py over a mesh.*latent"
    with pytest.raises(NotImplementedError, match=match):
        build()
