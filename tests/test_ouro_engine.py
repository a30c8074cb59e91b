"""The looped model (``ouro-tiny``: two layers run three times a token)
through ``JaxEngine``: the engine's greedy tokens are ``prefill``'s and
``decode_step``'s on the same weights, with a chunked prompt, a step carried by
a chunk launch and a prompt seeded from the prefix store over all ``passes *
layers`` cache rows; what the programs hand out of their passes and exits
reaches the counters, ``/metrics`` and the profiler's clock; the paths that do
not run the model refuse it by name."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, LLMConfig, ModelConfig, SamplingParams
from ray_tpu.llm.engine import COUNTERS, JaxEngine
from ray_tpu.models.llama import decode_step, init_kv_cache, prefill
from ray_tpu.util import metrics as app_metrics


@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="ouro-tiny", seed=2),
        engine=EngineConfig(max_num_seqs=3, max_seq_len=64, prefill_chunk=16,
                            prefill_buckets=(8, 16), max_concurrent_admissions=2,
                            dtype="float32")))
    yield eng
    eng.shutdown()


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(1000 * seed + n).integers(0, 256, n)]


def _greedy(engine, ids, n):
    """``n`` greedy tokens behind ``ids`` through ``models/llama.py`` alone."""
    cfg = engine.model_cfg
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))
    cache = init_kv_cache(cfg, 1, 64)
    logits, cache = jax.jit(lambda p, c, t: prefill(p, c, t, cfg))(
        engine.params, cache, jnp.asarray([ids]))
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n - 1):
        logits, cache = step(engine.params, cache, jnp.asarray(out[-1:]))
        out.append(int(jnp.argmax(logits[0])))
    return out


def _counters(engine):
    """The counters once nothing is in flight: a step launched ahead of a
    request's end is fetched, and counted, after the request returned."""
    deadline = time.monotonic() + 30
    while any(p.inflight or p.first_pending or p.admitting or any(p.slots)
              for p in engine._pools) and time.monotonic() < deadline:
        time.sleep(0.002)
    return engine.get_stats()["counters"]


def test_the_pool_holds_a_row_a_pass_and_layer(engine):
    cfg, (pool,) = engine.model_cfg, engine._pools
    assert (cfg.loop_passes, cfg.n_layers, cfg.branch_norm) == (3, 2, True)
    assert pool.cache["k"].shape == (6, 3, cfg.n_kv_heads, 64, cfg.head_dim)
    stats = engine.get_stats()["pools"][0]
    assert stats["kv_bytes_per_token"] == 2 * 6 * cfg.n_kv_heads * cfg.head_dim * 4
    assert stats["carries"]  # a stripe-only pool: its chunk launches take the decode rows


def test_the_engines_tokens_are_decode_steps_with_a_carried_step_and_a_prefix_hit(engine):
    """Prompts of one, two and three chunks at once on three slots, greedy: a
    later prompt's chunks carry the earlier slots' steps; then the longest
    again, seeded from the store over every pass's rows."""
    before = _counters(engine)
    params = SamplingParams(max_tokens=9, temperature=0.0, ignore_eos=True)
    prompts = [_prompt(5), _prompt(23), _prompt(37)]
    reqs = [engine.submit(prompt_token_ids=p, sampling_params=params) for p in prompts]
    again = engine.submit(prompt_token_ids=prompts[2], sampling_params=params)
    for req in (*reqs, again):
        engine._await_done(req)
        assert req.error is None
    for p, req in zip(prompts, reqs):
        assert list(req.out_tokens) == _greedy(engine, p, 9)
    assert again.prefix_hit_tokens > 0 and list(again.out_tokens) == list(reqs[2].out_tokens)
    after = _counters(engine)
    assert after["decode_steps_in_chunk"] > before["decode_steps_in_chunk"]
    assert after["prompt_tokens_from_prefix"] > before["prompt_tokens_from_prefix"]
    assert after["prefill_chunks"]["mid"] > before["prefill_chunks"]["mid"]


def test_passes_and_exits_reach_the_counters_and_the_scrape(engine):
    before = _counters(engine)
    engine.generate("looped", sampling_params=SamplingParams(max_tokens=6, ignore_eos=True))
    c = _counters(engine)
    P = engine.model_cfg.loop_passes
    assert {"loop_forwards", "loop_stack_passes", "loop_exit_rows:0"} <= set(COUNTERS)
    assert c["loop_forwards"] > 0 and c["loop_stack_passes"] == P * c["loop_forwards"]
    # at the model's threshold of 1 every counted row's head read the last pass
    exits = c["loop_exit_rows"]
    assert exits[str(P - 1)] > 0 and sum(exits.values()) == exits[str(P - 1)]
    # a row counts where its slot held a request at the launch: the one
    # request's six tokens and the steps launched ahead of its end, not the
    # two free slots' rows of every step (which would be ten more)
    made = c["tokens_generated"] - before["tokens_generated"]
    grown = exits[str(P - 1)] - before["loop_exit_rows"].get(str(P - 1), 0)
    assert made == 6 and made <= grown <= made + 2
    text = app_metrics.export_prometheus()
    assert "llm_engine_loop_stack_passes " in text
    assert f'llm_engine_loop_exit_rows{{pass="{P - 1}"}}' in text


def test_a_profiler_session_holds_the_passes_as_count_events(engine, tmp_path):
    from jax.profiler import ProfileData

    before = _counters(engine)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.generate("abcdefghijklmnopqrstuvwxyz", sampling_params=SamplingParams(
            max_tokens=5, ignore_eos=True))
        after = _counters(engine)
    finally:
        jax.profiler.stop_trace()
    (found,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    sums: dict = {}
    for plane in ProfileData.from_file(found).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "engine.counts":
                        for name, value in dict(ev.stats).items():
                            sums[name] = sums.get(name, 0) + value
    assert sums["loop_stack_passes"] == after["loop_stack_passes"] - before["loop_stack_passes"]
    assert sums["loop_forwards"] == after["loop_forwards"] - before["loop_forwards"] > 0


def test_a_model_run_once_a_token_counts_none():
    from ray_tpu.llm.engine import programs
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny()
    out = jax.eval_shape(
        programs(cfg)["decode_fn"],
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)),
        init_kv_cache(cfg, 2, 16), jnp.zeros((2,), jnp.int32), jnp.zeros((2,)),
        jnp.ones((2,), jnp.int32), jax.random.split(jax.random.PRNGKey(0), 2))
    assert out[3] is None  # no counts ride out of a dense stack run once


@pytest.mark.parametrize("module", ["llm/spmd.py", "llm/gang.py", "llm/disagg.py",
                                    "tensor_parallel_degree"])
def test_the_paths_that_do_not_run_the_looped_stack_refuse_it_by_name(module):
    cfg = LLMConfig(model=ModelConfig(model_id="ouro-tiny"),
                    engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32"))
    match = module.replace(".", r"\.") + ".*runs several times a token"
    if module == "llm/spmd.py":
        from ray_tpu.llm.spmd import SPMDGenerator

        build = lambda: SPMDGenerator(cfg)  # noqa: E731
    elif module == "llm/gang.py":
        from ray_tpu.llm.gang import GangLLMServer

        build = lambda: GangLLMServer(cfg, num_workers=2)  # noqa: E731
    elif module == "llm/disagg.py":
        from ray_tpu.llm.disagg import DecodeWorker, PrefillWorker

        with pytest.raises(NotImplementedError, match=match):
            DecodeWorker(cfg)
        build = lambda: PrefillWorker(cfg)  # noqa: E731
    else:
        cfg.engine.tensor_parallel_degree = 2
        build = lambda: JaxEngine(cfg)  # noqa: E731
        match = r"llm/engine\.py over a mesh.*runs several times a token"
    with pytest.raises(NotImplementedError, match=match):
        build()
