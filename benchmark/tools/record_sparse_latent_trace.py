"""Record the small trace ``benchmark/tests/data/sparse_latent`` keeps for the
readers of family ``sparse_latent``'s metrics. Run it in the one process that
holds the chip:

    python3 benchmark/tools/record_sparse_latent_trace.py <out_dir>

It runs the program's own engine on dots3-note-prev's attention as published
(both latent kinds' heads, head sizes and ranks, the indexer's 64 heads of
128) in a narrow, shallow model (hidden 512, layers 0-2: two indexed layers
and a sliding one, 16 of the router's 256 experts held, 2,048 ids), choosing
256 positions a query over a window of 129 so that both bind behind a
1,024-token document: the document once, then six requests of the document
and a tail of their own at once on four slots under a profiler session.
Written: ``<out_dir>/v5e-serve.xplane.pb`` (cut as
``record_scoped_trace.py cut`` cuts) and ``<out_dir>/v5e-serve.stats.json``
(the engine's ``get_stats()`` at the end)."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

MODEL_KWARGS = dict(
    n_layers=3, d_model=512, d_ff=1024, vocab_size=2048, moe_experts_held=16,
    index_topk=256, sliding_window=129,
)
DOCUMENT, TAILS, ANSWER = 1024, (40, 200, 75, 130, 33, 96), 12


def record(out_dir: str) -> tuple:
    import jax
    import numpy as np

    from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams

    from benchmark import trace

    engine = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="dots3-note-prev", tokenizer="byte", seed=0,
                          model_kwargs=MODEL_KWARGS),
        engine=EngineConfig(
            max_num_seqs=4, max_seq_len=2048, prefill_buckets=(32, 64, 128, 256, 1024),
            prefix_cache_entries=8,
        ),
    ))
    rng = np.random.default_rng(0)
    ids = lambda n: [int(t) for t in rng.integers(0, MODEL_KWARGS["vocab_size"], n)]  # noqa: E731
    document = ids(DOCUMENT)
    params = SamplingParams(max_tokens=ANSWER, temperature=0.0, ignore_eos=True)
    prompts = [document + ids(n) for n in TAILS]
    engine.generate(prompt_token_ids=document + ids(50), sampling_params=params)  # stores the document
    for p in prompts[:2]:  # every program the window uses
        engine.generate(prompt_token_ids=p, sampling_params=params)
    d = os.path.join(out_dir, "serve_trace")
    trace.start(d)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        reqs = [engine.submit(prompt_token_ids=p, sampling_params=params) for p in prompts]
        for r in reqs:
            engine._await_done(r)
        time.sleep(0.01)
    jax.profiler.stop_trace()
    stats = engine.get_stats()
    hits = [r.prefix_hit_tokens for r in reqs]
    engine.shutdown()
    return trace.find_xplane(d), stats, hits


def main(out_dir: str) -> None:
    from benchmark.tools.record_scoped_trace import cut

    os.makedirs(out_dir, exist_ok=True)
    pb, stats, hits = record(out_dir)
    cut(pb, os.path.join(out_dir, "v5e-serve.xplane.pb"))
    with open(os.path.join(out_dir, "v5e-serve.stats.json"), "w") as f:
        json.dump(stats, f, default=float)
    print("prefix_hit_tokens", hits)
    for name in ("v5e-serve.xplane.pb", "v5e-serve.stats.json"):
        print(name, os.path.getsize(os.path.join(out_dir, name)), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
