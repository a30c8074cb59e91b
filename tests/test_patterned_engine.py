"""A patterned model in the engine (``llm/engine.py`` over
``models/patterned.py``): its tokens against greedy decoding over the full
forward pass, the routing and window counters on a known batch, and the inner
scopes in the lowered programs' operation names. One engine serves the
module."""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from ray_tpu.models import patterned
from ray_tpu.models.llama import forward, init_kv_cache
from tests.test_patterned_counts import _routing


@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="laguna-tiny", seed=3),
        engine=EngineConfig(max_num_seqs=4, max_seq_len=128, dtype="float32",
                            prefill_chunk=16, prefill_buckets=(8, 16, 32),
                            max_concurrent_admissions=1),  # no test here speaks of rows
    ))
    yield eng
    eng.shutdown()


def test_engine_tokens_equal_greedy_over_the_full_forward(engine, monkeypatch):
    monkeypatch.setattr(patterned, "_WINDOW_ALIGN", 4)
    ids = [int(t) for t in np.random.default_rng(0).integers(32, 127, 50)]
    out = engine.generate(prompt_token_ids=ids, sampling_params=SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True))
    seq = jnp.asarray([ids + list(out.token_ids)])
    logits = forward(engine.params, seq, engine.model_cfg)
    assert [int(t) for t in jnp.argmax(logits[0, len(ids) - 1:-1], -1)] == list(out.token_ids)


def test_routing_and_window_counters_on_a_known_batch(engine):
    cfg = engine.model_cfg
    k, expert_layers, slots = cfg.moe_top_k, cfg.mlp_types.count("sparse"), 4
    before, c0 = _routing(engine)
    ids = [int(t) for t in np.random.default_rng(5).integers(32, 127, 37)]
    engine.generate(prompt_token_ids=ids, sampling_params=SamplingParams(
        max_tokens=5, temperature=0.0, ignore_eos=True))
    deadline = time.time() + 10.0
    while True:  # the run-ahead step's counts arrive with its fetch
        after, c1 = _routing(engine)
        if (after["moe_layer_steps"]["decode"] - before["moe_layer_steps"]["decode"]
                == (c1["decode_steps"] - c0["decode_steps"]) * expert_layers) or time.time() > deadline:
            break
        time.sleep(0.01)
    grew = {name: {p: after[name][p] - before[name][p] for p in after[name]} for name in after}
    # 37 tokens: two 16-token middle chunks and a final chunk of width 8 (5 real)
    assert c1["prefill_chunks"]["mid"] - c0["prefill_chunks"]["mid"] == 2
    # a prompt's middle chunks add theirs up on the device; its final chunk hands both out
    assert grew["moe_layer_steps"]["chunk_mid"] == 2 * expert_layers
    assert grew["moe_layer_steps"]["chunk_final"] == expert_layers
    # the pool carries, so a chunk program routes the pool's rows beside the
    # chunk's tokens in every expert layer, live or not (none is, here: one
    # request); a middle chunk's last feed-forward is those rows' alone,
    # since no logits are read behind it
    assert engine._pools[0].carries
    assert grew["moe_assignments"]["chunk_mid"] == 2 * k * ((16 + slots) * expert_layers - 16)
    assert grew["moe_assignments"]["chunk_final"] == k * (8 + slots) * expert_layers
    steps = c1["decode_steps"] - c0["decode_steps"]
    assert steps >= 4
    assert grew["moe_layer_steps"]["decode"] == steps * expert_layers
    assert grew["moe_assignments"]["decode"] == k * slots * steps * expert_layers
    for program in ("decode", "chunk_mid", "chunk_final"):
        runs = grew["moe_layer_steps"][program]
        assert runs <= grew["moe_experts_touched"][program] <= runs * cfg.moe_experts
        assert grew["moe_max_expert_load_sum"][program] * cfg.moe_experts >= grew["moe_assignments"][program]
    whole = c1["decode_kv_tokens_global"] - c0["decode_kv_tokens_global"]
    window = c1["decode_kv_tokens_window"] - c0["decode_kv_tokens_window"]
    assert 0 < window <= whole
    assert window == steps * cfg.sliding_window  # every step's slot is past the window
    assert whole >= steps * 37


INNER_SCOPES = {
    "decode_fn": ("attn_core/window", "attn_core/global", "moe_ffn/router", "moe_ffn/experts",
                  "moe_ffn/shared_expert", "attn_out/gate", "ffn", "kv_write", "sampling"),
    "chunk_mid": ("attn_core/window", "attn_core/global", "moe_ffn/router", "moe_ffn/experts",
                  "moe_ffn/shared_expert", "attn_out/gate"),
}


@pytest.fixture(scope="module")
def lowered_paths(engine):
    pool = engine._pools[0]
    while pool.keys is None:  # the loop thread makes them on its first pass
        time.sleep(0.01)
    shapes = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    params, cache = shapes(engine.params), shapes(pool.cache)
    one = dict(jax.eval_shape(lambda: init_kv_cache(engine.model_cfg, 1, pool.stripe_len)),
               moe_stats=i32(4))
    low = {
        "decode_fn": engine._decode_jit.lower(
            params, cache, i32(4), jax.ShapeDtypeStruct((4,), jnp.float32), i32(4), shapes(pool.keys)),
        "chunk_mid": engine._chunk_mid_jit.lower(params, (one,), i32(1, 16), i32(1), i32(1)),
    }
    return {k: set(re.findall(r'loc\("([^"]+)"', v.as_text(debug_info=True))) for k, v in low.items()}


@pytest.mark.parametrize("program, scope", [(p, s) for p, ss in INNER_SCOPES.items() for s in ss])
def test_inner_scopes_are_in_the_lowered_programs_op_names(lowered_paths, program, scope):
    pattern = re.compile(rf"(^|/){scope}(/|$)")
    assert any(pattern.search(path) for path in lowered_paths[program]), (program, scope)
