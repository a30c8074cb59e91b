"""Names, spans and counters inside the program (PR 26).

- every ``jax.named_scope`` of ``models/llama.py``'s vocabulary is in the
  ``op_name`` of the lowered train step and of the engine's programs;
- the engine's counters balance, failures land under the stage that dropped
  the request, an empty completion is counted, ``ignore_eos`` goes through
  the HTTP body;
- a request's spans share the caller's trace, nest under it and tile its
  time; the loop's spans land in a profiler session; ``trace_sample_n=0``
  records nothing; ``util.tracing`` imports no JAX by itself;
- PR 40: every counter grows in ``JaxEngine._count`` alone, which writes the
  growth as an ``engine.counts`` event on the profiler's clock (the events of
  a session sum to the counters' growth, name by name), and the loop keeps its
  own clock: seconds by stage, a histogram of passes and the longest pass of
  every second (``get_stats()["loop"]``), and the constructor its phases
  (``get_stats()["init"]``)."""

import glob
import inspect
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from ray_tpu.llm.engine import COUNTERS, LATENCIES, LONGEST_PASS_SECONDS
from ray_tpu.llm.server import LLMServer, sampling_from_body
from ray_tpu.models.llama import LlamaConfig, init_kv_cache
from ray_tpu.models.training import make_train_step
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.util import metrics as app_metrics
from ray_tpu.util import tracing
from tests.engine_helpers import programs_replaced

LAYER = ("embed", "norm", "attn_qkv", "attn_core", "attn_out")
PROGRAM_SCOPES = {
    "step_fn": LAYER + ("ffn", "loss", "optimizer", "grad_norm"),
    "step_fn_plain_loss": ("lm_head", "loss"),
    "step_fn_moe": ("moe_ffn",),
    "decode_fn": LAYER + ("ffn", "kv_write", "lm_head", "sampling"),
    "chunk_mid": LAYER + ("ffn", "kv_write"),
    "chunk_final": LAYER + ("ffn", "kv_write", "lm_head", "sampling"),
    "seed_prefix": ("prefix_seed",),
    "decode_fn_moe": ("moe_ffn",),
}


def _engine(**model_kwargs):
    return JaxEngine(LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0, model_kwargs=model_kwargs),
        engine=EngineConfig(max_num_seqs=4, max_seq_len=128, prefill_buckets=(16, 32, 64, 128),
                            prefill_chunk=32),
    ))


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def server(engine):
    srv = LLMServer.__new__(LLMServer)
    srv.llm_config = engine.config
    srv.engine = engine
    return srv


@pytest.fixture
def ring(monkeypatch):
    """Tracing on for every request, an empty ring; both restored after."""
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE_N", "1")
    tracing._reset_sampling()
    tracing.clear()
    yield
    tracing.clear()
    monkeypatch.delenv("RAY_TPU_TRACE_SAMPLE_N")
    tracing._reset_sampling()


# ------------------------------------------------------------------- scopes


def _shapes(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _one_device_mesh():
    return build_mesh(MeshSpec(), devices=jax.devices()[:1])


def _lower_step(**cfg_kwargs):
    cfg = LlamaConfig.tiny(remat=True, **cfg_kwargs)
    init_fn, step_fn = make_train_step(cfg, _one_device_mesh())
    state = init_fn(jax.random.PRNGKey(0))
    return step_fn.lower(state, {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)})


def _lower_engine_program(eng, program):
    while eng._loop_first_pass_t is None:  # the loop thread makes the pools' keys
        time.sleep(0.01)
    pool = eng._pools[0]
    params, cache = _shapes(eng.params), _shapes(pool.cache)
    one = jax.eval_shape(lambda: init_kv_cache(eng.model_cfg, 1, pool.stripe_len))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    keys = _shapes(pool.keys)
    if program == "decode_fn":
        return eng._decode_jit.lower(
            params, cache, i32(pool.n_slots), f32(pool.n_slots), i32(pool.n_slots), keys)
    if program == "chunk_mid":
        return eng._chunk_mid_jit.lower(params, (one,), i32(1, 32), i32(1), i32(1))
    if program == "chunk_final":
        return eng._chunk_final_jit.lower(
            params, cache, one, i32(1, 32), i32(1), i32(1), i32(), f32(), i32(),
            jax.ShapeDtypeStruct(keys.shape[1:], keys.dtype))
    if program == "seed_prefix":
        kv = jax.ShapeDtypeStruct(one["k"].shape[:1] + (one["k"].shape[2], 16, one["k"].shape[4]),
                                  one["k"].dtype)
        return eng._seed_prefix_jit.lower(one, kv, kv)
    raise KeyError(program)


@pytest.fixture(scope="module")
def lowered(engine):
    """program name -> the scope paths of its lowered text, made once."""
    cache = {}

    def paths(program):
        if program not in cache:
            if program == "step_fn":
                low = _lower_step()
            elif program == "step_fn_plain_loss":
                low = _lower_step(fused_ce=False)
            elif program == "step_fn_moe":
                low = _lower_step(moe_experts=4)
            elif program == "decode_fn_moe":
                moe = _engine(moe_experts=4)
                try:
                    low = _lower_engine_program(moe, "decode_fn")
                finally:
                    moe.shutdown()
            else:
                low = _lower_engine_program(engine, program)
            cache[program] = set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True)))
        return cache[program]

    return paths


@pytest.mark.parametrize(
    "program, scope",
    [(p, s) for p, scopes in PROGRAM_SCOPES.items() for s in scopes],
)
def test_scope_is_in_the_lowered_programs_op_names(lowered, program, scope):
    # jit(step_fn)/jvp()/while/body/closed_call/ffn/mul, or the scope inside
    # the transform that precedes it: jit(step_fn)/transpose(jvp(loss))/div
    pattern = re.compile(rf"(^|/|\(){scope}(/|\))")
    assert any(pattern.search(path) for path in lowered(program)), (
        f"no operation of {program} carries the scope {scope!r}")


def test_jitted_names_the_benchmark_reads_stay(engine):
    assert engine._decode_jit.__name__ == "decode_fn"
    assert engine._chunk_mid_jit.__name__ == "chunk_mid"
    assert engine._chunk_final_jit.__name__ == "chunk_final"
    _, step_fn = make_train_step(LlamaConfig.tiny(), _one_device_mesh())
    assert step_fn.__name__ == "step_fn"


# ----------------------------------------------------------------- counters


def _counters(eng):
    return eng.get_stats()["counters"]


def _flat(counters):
    """``get_stats()["counters"]`` back under ``COUNTERS``' own names."""
    return {
        k if not isinstance(v, dict) else f"{k}:{label}": v if not isinstance(v, dict) else v[label]
        for k, v in counters.items() for label in (v if isinstance(v, dict) else [None])
    }


def _closed(c):
    return sum(c["requests_finished"].values()) + sum(c["requests_failed"].values())


def test_counters_balance_after_mixed_requests(engine):
    before = _counters(engine)
    reqs = [
        # a letter of its own each: no prompt is served from the prefix cache
        engine.submit(chr(97 + i) * n, sampling_params=SamplingParams(
            max_tokens=m, temperature=t, ignore_eos=True, seed=n))
        for i, (n, m, t) in enumerate([
            (3, 5, 0.0), (40, 2, 0.8), (70, 9, 0.0), (10, 1, 0.0), (33, 6, 1.0), (90, 4, 0.0),
            (5, 7, 0.0)])
    ]
    with pytest.raises(KeyError):
        engine.submit("y", lora="no-such-adapter")
    for r in reqs:
        engine._await_done(r)
    outs = [engine._output(r) for r in reqs]
    s = engine.get_stats()
    c = s["counters"]
    assert set(COUNTERS) == set(_flat(c))
    assert c["requests_submitted"] - before["requests_submitted"] == len(reqs) + 1
    assert c["requests_submitted"] == _closed(c)
    assert c["requests_failed"]["submit"] - before["requests_failed"]["submit"] == 1
    assert c["requests_finished"]["length"] - before["requests_finished"]["length"] == len(reqs)
    made = sum(len(o.token_ids) for o in outs)
    assert made == 5 + 2 + 9 + 1 + 6 + 4 + 7
    assert c["tokens_generated"] - before["tokens_generated"] == made
    assert c["first_tokens"] - before["first_tokens"] == len(reqs)
    assert c["prompt_tokens"] - before["prompt_tokens"] == sum(
        len(o.prompt_token_ids) for o in outs)
    assert c["decode_slot_steps"] <= c["decode_steps"] * s["max_num_seqs"]
    assert c["prefill_chunks"]["final"] - before["prefill_chunks"]["final"] == len(reqs)
    # prompts of 41, 71, 34 and 91 tokens (with BOS) in chunks of 32
    assert c["prefill_chunks"]["mid"] - before["prefill_chunks"]["mid"] == 1 + 2 + 1 + 2
    assert c["prompt_tokens_from_prefix"] == before["prompt_tokens_from_prefix"]
    assert s["loop"]["passes"] > 0 and "loop_passes" not in c
    assert s["live_tokens"] == 0 and s["active_slots"] == 0
    for o in outs:
        m = o.metrics
        assert m["queue_wait_s"] >= 0 and m["prefill_s"] > 0 and 0 <= m["slot"] < 4
        assert m["ttft_s"] == pytest.approx(m["queue_wait_s"] + m["prefill_s"])


def test_latency_histograms_count_every_finished_request(engine):
    engine.generate("abc", sampling_params=SamplingParams(max_tokens=3, ignore_eos=True))
    s = engine.get_stats()
    finished = sum(s["counters"]["requests_finished"].values())
    lat = s["latency"]
    assert set(LATENCIES) <= set(lat)
    assert len(lat["queue_wait_s"]["counts"]) == len(lat["boundaries"]) + 1
    assert sum(lat["queue_wait_s"]["counts"]) == finished
    assert sum(lat["prefill_s"]["counts"]) == finished
    # a gap needs two tokens
    assert 0 < sum(lat["token_gap_s"]["counts"]) <= finished
    assert lat["prefill_s"]["sum"] > 0
    assert 0 < s["engine_init_s"] < 600


def test_an_engine_reads_its_own_series_of_the_process_histogram(engine):
    from ray_tpu.llm.engine import LATENCY_BOUNDS, _latency_histogram

    engine.generate("mine", sampling_params=SamplingParams(max_tokens=2, ignore_eos=True))
    hist = _latency_histogram("prefill_s")
    assert hist.read(engine._tag) == engine.get_stats()["latency"]["prefill_s"]
    assert hist.read({"engine": "nobody"}) == {
        "counts": [0] * (len(LATENCY_BOUNDS) + 1), "sum": 0.0}
    assert f'llm_engine_prefill_s_count{{engine="{engine._tag["engine"]}"}}' in (
        app_metrics.export_prometheus())


def test_counters_reach_the_metrics_scrape(engine):
    engine.generate("scrape", sampling_params=SamplingParams(max_tokens=2, ignore_eos=True))
    text = app_metrics.export_prometheus()
    assert "llm_engine_requests_submitted " in text
    assert 'llm_engine_requests_finished{reason="length"}' in text
    assert 'llm_engine_prefill_chunks{kind="final"}' in text
    assert "llm_engine_queue_wait_s_bucket" in text
    assert "llm_engine_live_tokens " in text


def test_relaid_parameter_leaves_are_counted_and_follow_a_swap():
    from ray_tpu.models.llama import init_params

    def gauges():
        return {k: float(v) for k, v in re.findall(
            r"^llm_engine_params_relaid_(leaves|bytes) (\S+)$",
            app_metrics.export_prometheus(), re.M)}

    eng = _engine()
    try:
        held = {k: eng.params[k].nbytes for k in ("wq", "wk", "wv")}
        want = {"leaves": 3, "bytes": sum(held.values())}
        assert eng.get_stats()["params_relaid"] == want == gauges()
        eng.params = None
        assert eng.get_stats()["params_relaid"] == {"leaves": 0, "bytes": 0} == gauges()
        eng.params = init_params(jax.random.PRNGKey(1), eng.model_cfg)
        assert eng.get_stats()["params_relaid"] == want == gauges()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("stage", ["admission", "decode"])
def test_injected_failure_lands_under_its_stage(engine, stage):
    before = _counters(engine)

    def boom(*a, **kw):
        raise RuntimeError(f"injected {stage} failure")

    program = "chunk_final" if stage == "admission" else "decode"
    with programs_replaced(engine, program, lambda inner: boom):
        req = engine.submit(
            "fail me", sampling_params=SamplingParams(max_tokens=4, ignore_eos=True))
        engine._await_done(req)
    assert isinstance(req.error, RuntimeError) and "injected" in str(req.error)
    c = _counters(engine)
    for s in ("submit", "admission", "decode", "loop_exit"):
        assert c["requests_failed"][s] - before["requests_failed"][s] == (s == stage)
    assert c["requests_submitted"] == _closed(c)
    # the engine still serves
    out = engine.generate("after", sampling_params=SamplingParams(max_tokens=2, ignore_eos=True))
    assert len(out.token_ids) == 2


@pytest.mark.parametrize("stage", ["submit", "admission"])
def test_an_empty_prompt_fails_its_request_and_not_the_loop(engine, stage):
    """``submit`` refuses a prompt of no tokens; one that reaches the loop
    all the same (an admission with no chunk) fails there alone."""
    from ray_tpu.llm.engine import _Request

    before = _counters(engine)
    if stage == "submit":
        with pytest.raises(ValueError, match="empty prompt"):
            engine.generate(prompt_token_ids=[])
    else:
        with engine._count_lock:
            engine._n["requests_submitted"] += 1
        req = _Request("no-chunks", [], SamplingParams(max_tokens=2))
        engine._waiting.put(req)
        engine._await_done(req)
        assert isinstance(req.error, IndexError)
    c = _counters(engine)
    for s in ("submit", "admission", "decode", "loop_exit"):
        assert c["requests_failed"][s] - before["requests_failed"][s] == (s == stage)
    assert c["requests_submitted"] == _closed(c)
    assert engine._thread.is_alive()
    out = engine.generate("after", sampling_params=SamplingParams(max_tokens=2, ignore_eos=True))
    assert len(out.token_ids) == 2
    assert engine.get_stats()["live_tokens"] == 0


def test_loop_exit_failure_is_counted_once():
    eng = _engine()
    eng.shutdown()
    req = eng.submit("nobody home", sampling_params=SamplingParams(max_tokens=2))
    eng._await_done(req)
    eng._await_done(req)
    c = _counters(eng)
    assert isinstance(req.error, RuntimeError)
    assert c["requests_failed"]["loop_exit"] == 1 and c["requests_submitted"] == _closed(c) == 1


@pytest.fixture
def eos_first(engine, monkeypatch):
    """A prompt whose first greedy token is the stop token."""
    prompt = "the first token stops"
    first = engine.generate(
        prompt, sampling_params=SamplingParams(max_tokens=1, ignore_eos=True)).token_ids[0]
    monkeypatch.setattr(engine.tokenizer, "eos_id", first)
    return prompt


def test_first_token_eos_counts_as_empty(engine, server, eos_first):
    before = _counters(engine)
    reply = server.completions({"prompt": eos_first, "max_tokens": 4})
    assert reply["usage"]["completion_tokens"] == 0
    assert reply["choices"][0]["finish_reason"] == "stop"
    c = _counters(engine)
    assert c["requests_empty"] - before["requests_empty"] == 1
    assert c["requests_finished"]["stop"] - before["requests_finished"]["stop"] == 1
    assert sum(c["requests_failed"].values()) == sum(before["requests_failed"].values())
    out = engine.generate(eos_first, sampling_params=SamplingParams(max_tokens=4))
    assert out.token_ids == [] and out.metrics["ttft_s"] is None
    assert out.metrics["prefill_s"] > 0


def test_ignore_eos_through_the_http_body(engine, server, eos_first):
    before = _counters(engine)
    reply = server.completions({"prompt": eos_first, "max_tokens": 4, "ignore_eos": True})
    assert reply["usage"]["completion_tokens"] == 4
    assert reply["choices"][0]["finish_reason"] == "length"
    assert _counters(engine)["requests_empty"] == before["requests_empty"]


@pytest.mark.parametrize("body, want", [
    ({}, SamplingParams()),
    ({"max_tokens": 7, "temperature": 0.5, "top_k": 3},
     SamplingParams(max_tokens=7, temperature=0.5, top_k=3)),
    ({"ignore_eos": True, "seed": 11}, SamplingParams(ignore_eos=True, seed=11)),
    ({"stream": True, "model": "m", "prompt": "p"}, SamplingParams()),
], ids=["defaults", "classic", "extensions", "other-keys"])
def test_sampling_from_body(body, want):
    assert sampling_from_body(body) == want


def test_seed_through_the_http_body(server):
    body = {"prompt": "seeded", "max_tokens": 6, "temperature": 1.0, "seed": 5,
            "ignore_eos": True}
    assert (server.completions(body)["choices"][0]["text"]
            == server.completions(dict(body))["choices"][0]["text"])


def test_live_tokens_gauge_equals_what_the_benchmark_reads_from_the_pools(engine, monkeypatch):
    # decodes held back: slots are bound after their first token and stay put
    with monkeypatch.context() as m:
        m.setattr(engine, "_launch_decodes", lambda: False)
        reqs = [
            engine.submit("z" * n, sampling_params=SamplingParams(max_tokens=50, ignore_eos=True))
            for n in (7, 20, 45)]
        deadline = time.time() + 60
        while time.time() < deadline and not all(r.out_tokens for r in reqs):
            time.sleep(0.01)
        assert all(len(r.out_tokens) == 1 for r in reqs)
        # benchmark/serving.py bench_window_open's own sum
        live = sum(
            len(r.prompt_token_ids) + len(r.out_tokens)
            for pool in engine._pools for r in list(pool.slots) if r is not None
        )
        assert engine.get_stats()["live_tokens"] == live == (8 + 21 + 46) + 3
    for r in reqs:
        engine._await_done(r)
    assert engine.get_stats()["live_tokens"] == 0


# -------------------------------------------------------------------- spans


def test_request_spans_share_the_callers_trace_and_tile_the_request(engine, server, ring):
    with tracing.span("caller"):
        caller_ctx = tracing.current_context()
        server.completions({"prompt": "trace me " * 6, "max_tokens": 5, "ignore_eos": True})
    spans = {s["name"]: s for s in tracing.get_spans()}
    assert set(spans) == {
        "caller", "engine.request", "engine.queue_wait", "engine.prefill", "engine.decode"}
    request = spans["engine.request"]
    assert request["trace_id"] == caller_ctx[0] and request["parent_id"] == caller_ctx[1]
    assert request["plane"] == "engine"
    assert request["attributes"]["tokens"] == 5 and request["attributes"]["chunks"] == 2
    assert request["attributes"]["finish_reason"] == "length"
    phases = [spans[n] for n in ("engine.queue_wait", "engine.prefill", "engine.decode")]
    for phase in phases:
        assert phase["trace_id"] == caller_ctx[0] and phase["parent_id"] == request["span_id"]
    assert phases[0]["start"] == request["start"] and phases[-1]["end"] == request["end"]
    assert phases[0]["end"] == phases[1]["start"] and phases[1]["end"] == phases[2]["start"]
    assert spans["caller"]["start"] <= request["start"] <= request["end"] <= spans["caller"]["end"]


def test_a_request_without_a_caller_roots_its_own_trace(engine, ring):
    engine.generate("alone", sampling_params=SamplingParams(max_tokens=2, ignore_eos=True))
    spans = tracing.get_spans()
    assert len(spans) == 4 and len({s["trace_id"] for s in spans}) == 1
    assert [s for s in spans if s["name"] == "engine.request"][0]["parent_id"] is None


def test_a_failed_request_has_the_phases_it_reached(engine, ring):
    def boom(*a, **kw):
        raise RuntimeError("injected")

    with programs_replaced(engine, "chunk_final", lambda inner: boom):
        req = engine.submit("fail", sampling_params=SamplingParams(max_tokens=2))
        engine._await_done(req)
    spans = {s["name"]: s for s in tracing.get_spans()}
    assert set(spans) == {"engine.request", "engine.queue_wait", "engine.prefill"}
    assert "injected" in spans["engine.request"]["attributes"]["error"]
    assert spans["engine.prefill"]["end"] == spans["engine.request"]["end"]


def test_trace_sample_n_zero_records_nothing(engine, server, monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE_N", "0")
    tracing._reset_sampling()
    tracing.clear()
    try:
        with tracing.span("caller"):
            server.completions({"prompt": "quiet", "max_tokens": 2})
        assert tracing.get_spans() == []
    finally:
        monkeypatch.delenv("RAY_TPU_TRACE_SAMPLE_N")
        tracing._reset_sampling()


def test_profiler_session_holds_the_engine_loops_annotations(engine, tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.annotate("test.window", cell="cpu"):
            engine.generate("profile me " * 4,
                            sampling_params=SamplingParams(max_tokens=3, ignore_eos=True))
            time.sleep(0.05)  # the loop finds nothing to do and sleeps
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    assert found
    names = set()
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events if "." in ev.name)
    assert {"test.window", "engine.pull_waiting", "engine.advance_admissions",
            "engine.prefill_chunk", "engine.launch_decodes", "engine.decode_launch",
            "engine.drain", "engine.fetch", "engine.idle_sleep"} <= names
    # hot-loop spans never reach the ring
    assert {s["name"] for s in tracing.get_spans() if s["name"].startswith("engine.")} <= {
        "engine.request", "engine.queue_wait", "engine.prefill", "engine.decode"}


def _engine_events(trace_dir):
    """[(name, start_ns, duration_ns, {stat: value})] of the ``engine.*``
    host events of a profiler session's ``.xplane.pb``."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    assert found
    return [
        (ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(found[0]).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events if ev.name.startswith("engine.")
    ]


def test_a_profiler_sessions_count_events_sum_to_the_counters_growth(engine, tmp_path, ring):
    """The keyword arguments of a ``TraceAnnotation`` are the event's own
    stats in the file (``XEvent.stats``, as ``ProfileData`` shows them: name
    and whole number), so ``_n`` and the events cannot drift."""
    params = SamplingParams(max_tokens=5, ignore_eos=True)
    engine.generate("q" * 70, sampling_params=params)  # stored: the session's copy hits it
    before = _flat(_counters(engine))
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = [engine.submit(chr(105 + i) * n, sampling_params=SamplingParams(
            max_tokens=m, ignore_eos=True)) for i, (n, m) in enumerate(
                [(3, 5), (40, 2), (70, 9), (90, 4)])]
        reqs.append(engine.submit("q" * 70 + "tail", sampling_params=params))
        with pytest.raises(ValueError):
            engine.submit(prompt_token_ids=[])
        for r in reqs:
            engine._await_done(r)
        time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    growth = {k: v - before[k] for k, v in _flat(_counters(engine)).items() if v != before[k]}
    events = [e for e in _engine_events(str(tmp_path)) if e[0] == "engine.counts"]
    sums: dict = {}
    for _, _, _, stats in events:
        for name, value in stats.items():
            assert isinstance(value, int) and value > 0 and name in COUNTERS
            sums[name] = sums.get(name, 0) + value
    assert sums == growth
    # the session saw every kind of site: callers' threads, admissions with and
    # without a prefix, launches of each program, fetches
    assert {"requests_submitted", "requests_failed:submit", "requests_finished:length",
            "prompt_tokens", "prompt_tokens_from_prefix", "prefix_seed_tokens",
            "prefill_programs:mid", "prefill_chunks:mid", "prefill_programs:final",
            "prefill_query_tokens:chunk_final", "prefill_attended_positions:chunk_mid",
            "decode_steps", "decode_slot_steps", "decode_kv_tokens_global",
            "decode_kv_positions_read", "tokens_generated", "first_tokens",
            # chunk launches that carried the pool's step, and the session's
            # first, which found no slot decoding and ran its rows dead
            "decode_steps_in_chunk", "decode_steps_dead_in_chunk:no_slot"} <= set(sums)
    # one event a launch and one a fetch, not one a token
    per_launch = [s for _, _, _, s in events if "decode_steps" in s]
    assert sum(s["decode_steps"] for s in per_launch) == len(per_launch) == growth["decode_steps"]
    assert len([s for _, _, _, s in events if "tokens_generated" in s]) < growth["tokens_generated"]
    # an instant, entered and left at once: the quickest says what one costs
    # (a thread may lose the core inside any one of them)
    assert min(d for _, _, d, _ in events) < 1e5  # ns
    # and never the ring
    assert "engine.counts" not in {s["name"] for s in tracing.get_spans()}


def test_a_finished_request_ends_at_its_last_token_and_wakes_after_the_count(engine, monkeypatch):
    """``_emit`` stamps the end where it always was; ``_drain`` closes the
    request once the fetch's block is counted, so its waiter finds the tokens
    in the counters."""
    ended, seen, emit, count = [], [], engine._emit, engine._count

    def emitting(pool, slot, token):
        r = pool.slots[slot]
        made, at = emit(pool, slot, token)
        if at is not None:
            ended.append((r, at))
        return made, at

    def counting(deltas):  # the loop's thread, as ``emitting``
        if "tokens_generated" in deltas:
            seen.extend((r, at, time.time(), r.done.is_set(), r.finished_t) for r, at in ended)
            ended.clear()
        return count(deltas)

    with monkeypatch.context() as m:
        m.setattr(engine, "_emit", emitting)
        m.setattr(engine, "_count", counting)
        req = engine.submit("ends where", sampling_params=SamplingParams(max_tokens=4, ignore_eos=True))
        engine._await_done(req)
    ((_, at, counted_t, woken, closed_t),) = [e for e in seen if e[0] is req]
    assert req.finish_reason == "length" and not woken and closed_t is None
    assert req.first_token_t <= at == req.finished_t <= counted_t
    assert _counters(engine)["tokens_generated"] >= 4


def test_the_steps_of_a_launch_are_named_inside_its_span(engine, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.generate("name the host steps " * 3,
                        sampling_params=SamplingParams(max_tokens=2, ignore_eos=True))
    finally:
        jax.profiler.stop_trace()
    events = _engine_events(str(tmp_path))
    spans = {}
    for name, start, duration, _ in events:
        spans.setdefault(name, []).append((start, start + duration))
    outer = {"engine.chunk_transfer": "engine.prefill_chunk", "engine.chunk_call": "engine.prefill_chunk",
             "engine.prefix_store": "engine.prefill_chunk",
             "engine.prefix_lookup": "engine.pull_waiting", "engine.new_stripe": "engine.pull_waiting"}
    if not engine._pools[0].carries:  # else the final chunk's program sets the slot's key and token
        outer["engine.slot_set"] = "engine.prefill_chunk"
    for inner, around in outer.items():
        assert inner in spans, inner
        for a, b in spans[inner]:
            assert any(lo <= a and b <= hi for lo, hi in spans[around]), (inner, around)
    # no span carries an attribute: the numbers ride on ``engine.counts``
    assert all(not stats for name, _, _, stats in events if name != "engine.counts")


def test_every_counter_grows_in_the_one_counting_method():
    import ray_tpu.llm.engine as mod

    grows = re.findall(r"^.*\b_n\[[^\]]*\]\s*\+=.*$|^.*\bn\[[^\]]*\]\s*\+=.*$",
                       inspect.getsource(mod), re.M)
    assert [g.strip() for g in grows] == ["n[name] += value"]
    assert "n[name] += value" in inspect.getsource(mod.JaxEngine._count)


def _after_passes(engine, n):
    """Wait for ``n`` more passes of the loop to end (an idle loop makes one
    every 2 ms): a pass in progress after this began after the call."""
    target = engine._loop.passes + n
    deadline = time.monotonic() + 60
    while engine._loop.passes < target:
        assert time.monotonic() < deadline, "the loop stands still"
        time.sleep(0.001)


def _one_slow_pass(engine, monkeypatch):
    """One pass of the idle loop whose ``_launch_decodes`` sleeps 80 ms: the
    test's own window, when the sleep began, and the loop's view once that
    pass has ended. The pass that takes the patch began after ``t_open``: two
    whole passes lie between."""
    inner, slept = engine._launch_decodes, []

    def slow():
        if not slept:
            slept.append(time.time())
            time.sleep(0.08)
        return inner()

    t_open = time.time()
    _after_passes(engine, 2)
    with monkeypatch.context() as m:
        m.setattr(engine, "_launch_decodes", slow)
        _after_passes(engine, 2)  # the one that slept, and it has been recorded
    return t_open, slept[0], time.time(), engine.get_stats()["loop"]


def test_a_slow_pass_is_the_record_of_its_second(engine, monkeypatch):
    for _ in range(5):  # again where the box stalled another pass of that second for longer
        time.sleep(1.02 - time.time() % 1)  # a second that holds no earlier pass of length
        t_open, slept_t, t_close, loop = _one_slow_pass(engine, monkeypatch)
        records = loop["longest_pass_by_second"]
        assert 0 < len(records) <= LONGEST_PASS_SECONDS
        seconds = [int(r["t"]) for r in records]
        assert seconds == sorted(set(seconds))  # one record a second, in order
        mine = [r for r in records if r["t"] <= slept_t <= r["t"] + r["s"]]
        if mine:
            break
    (r,) = mine
    assert t_open <= r["t"] <= slept_t <= t_close
    assert r["s"] >= 0.08 and r["stage_s"]["launch_decodes"] >= 0.08
    assert max(r["stage_s"], key=r["stage_s"].get) == "launch_decodes"
    assert r["s"] == pytest.approx(sum(r["stage_s"].values()))
    assert set(r["stage_s"]) == set(loop["stage_s"]) == {
        "pull_waiting", "advance_admissions", "launch_decodes", "drain", "idle_sleep"}
    # the pass is in the histogram's bucket for its duration
    bounds, counts = loop["pass_s"]["boundaries"], loop["pass_s"]["counts"]
    assert len(counts) == len(bounds) + 1 and sum(counts) >= loop["passes"]
    assert sum(c for b, c in zip(bounds, counts) if b >= 0.08) + counts[-1] >= 1


def test_a_reader_that_comes_minutes_late_finds_the_seconds_it_asks_about():
    """A profiler that stops after a busy window of many small operations
    hands back its trace minutes later (161 s on a v5e), and only then does
    the benchmark read the window's seconds: they are still recorded."""
    from ray_tpu.llm.engine import LOOP_STAGES, _LoopClock

    clock = _LoopClock()
    marks = tuple(0.001 * i for i in range(len(LOOP_STAGES) + 1))
    for second in range(300):  # a window of 40 s, then 260 s of an idle loop
        clock.end_pass(1000.0 + second, marks)
    records = clock.view()["longest_pass_by_second"]
    assert [r["t"] for r in records] == [1000.0 + s for s in range(300)]
    for second in range(LONGEST_PASS_SECONDS):
        clock.end_pass(2000.0 + second, marks)
    assert len(clock.view()["longest_pass_by_second"]) == LONGEST_PASS_SECONDS


def test_the_loops_stage_seconds_tile_its_elapsed_time(engine):
    """A pass starts where the last ended, so the stages' seconds are the
    loop's elapsed time up to the last pass's end: no more than the time
    since the first pass, and no less than it was two passes ago."""
    reqs = [engine.submit("clock " * n, sampling_params=SamplingParams(max_tokens=6, ignore_eos=True))
            for n in (2, 9, 14)]
    for r in reqs:
        engine._await_done(r)
    earlier = engine.get_stats()["loop"]["elapsed_s"]
    _after_passes(engine, 2)
    loop = engine.get_stats()["loop"]
    now = time.perf_counter() - engine._loop.started_t
    assert earlier <= sum(loop["stage_s"].values()) <= now
    assert loop["elapsed_s"] <= now
    # a fetch and a launch lie inside their stages
    assert 0 < loop["fetch_s"] <= loop["stage_s"]["drain"]
    assert 0 < loop["launch_s"] <= (
        loop["stage_s"]["pull_waiting"] + loop["stage_s"]["advance_admissions"]
        + loop["stage_s"]["launch_decodes"])
    assert 0 < loop["idle_sleeps"]
    calls = {r["call"] for r in loop["longest_pass_by_second"]}
    assert calls <= {None, "fetch:first_token", "fetch:decode", "launch:chunk_mid",
                     "launch:chunk_final", "launch:decode", "launch:seed_prefix"}


def test_the_constructors_phases_add_up_to_engine_init_s(engine):
    s = engine.get_stats()
    init = s["init"]
    phases = ("build_model_s", "build_pools_s", "compile_s", "warm_programs_s")
    assert all(init[k] >= 0 for k in phases)
    assert sum(init[k] for k in phases) == pytest.approx(s["engine_init_s"], rel=0.05)
    by_program = init["warm_programs_by_program_s"]
    assert sum(by_program.values()) == pytest.approx(init["warm_programs_s"], rel=0.01)
    # the middle chunk by rows, the final chunk by width, the seed, the decode step
    assert {"chunk_mid:rows=1", "chunk_mid:rows=4", "chunk_final:width=16",
            "chunk_final:width=32", "seed_prefix", "decode"} <= set(by_program)


def test_get_stats_stays_json_serialisable(engine):
    engine.generate("json", sampling_params=SamplingParams(max_tokens=2, ignore_eos=True))
    s = engine.get_stats()
    assert json.loads(json.dumps(s)).keys() == s.keys()
    assert {"loop", "init", "counters", "latency"} <= set(s)


def test_the_loops_stage_seconds_reach_the_metrics_scrape(engine):
    engine.generate("stages", sampling_params=SamplingParams(max_tokens=2, ignore_eos=True))
    text = app_metrics.export_prometheus()
    got = {stage: float(v) for stage, v in re.findall(
        r'^llm_engine_loop_stage_seconds\{stage="(\w+)"\} (\S+)$', text, re.M)}
    loop = engine.get_stats()["loop"]
    assert set(got) == set(loop["stage_s"])
    assert got["idle_sleep"] > 0 and got["advance_admissions"] > 0
    # and its passes by duration, folded with them when a request finishes
    # (the process's engines share both series)
    folded = int(re.search(r"^llm_engine_loop_pass_seconds_count (\d+)$", text, re.M).group(1))
    seconds = float(re.search(r"^llm_engine_loop_pass_seconds_sum (\S+)$", text, re.M).group(1))
    assert folded > 0 and seconds == pytest.approx(sum(got.values()))
    assert len(re.findall(r"^llm_engine_loop_pass_seconds_bucket", text, re.M)) == len(
        loop["pass_s"]["boundaries"]) + 1


def test_tracing_imports_no_jax_in_a_process_that_has_none():
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "with tracing.annotate('a', x=1):\n"
        "    pass\n"
        "with tracing.span('b'):\n"
        "    pass\n"
        "tracing.mark('engine.counts', decode_steps=1)\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n"
        "assert [s['name'] for s in tracing.get_spans()] == ['b']\n"
    )
    env = dict(os.environ, RAY_TPU_TRACE_SAMPLE_N="1")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    done = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


def test_what_nothing_read_is_gone():
    assert not hasattr(tracing, "set_exporter") and not hasattr(tracing, "traced")
    assert not hasattr(tracing, "_exporter")
