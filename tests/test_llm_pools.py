"""A pool of slots for each bucket of sequence lengths. One parametrised
test, a file of its own so that a worker has it alone."""

import pytest

from ray_tpu.llm import (
    EngineConfig,
    JaxEngine,
    LLMConfig,
    ModelConfig,
    SamplingParams,
)

pytestmark = pytest.mark.timeout(600) if hasattr(pytest.mark, "timeout") else []


@pytest.mark.parametrize("dtype, buckets", [
    ("bfloat16", (128, 256)), ("float32", (128, 256)), ("float32", (32, 128))])
def test_seq_len_bucket_pools(dtype, buckets):
    """Stripe pools: short chats run in short-stripe slots; long requests
    land in the long pool; both produce identical results to a single-pool
    engine (greedy). At the engine's default bf16 both pools' stripes are
    whole blocks of ``ops/decode_attention.py``, so every decode step on
    either side reads through the kernel, which gives a request the same
    numbers in a stripe of any length. A 32-position stripe keeps the einsum,
    whose scores are rounded to the model's dtype where the kernel's stay
    float32: the two forms agree to the token in float32, and in bf16 to
    rounding (``tests/test_decode_attention.py``)."""
    short_stripe, long_stripe = buckets
    common = dict(
        max_num_seqs=4, max_seq_len=long_stripe, dtype=dtype,
        prefill_buckets=(16, 32, 64, 128),
    )
    base = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(**common),
    )
    pooled = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(
            **common, seq_len_buckets=buckets, seqs_per_bucket=(2, 2),
            enable_prefix_caching=False,
        ),
    )
    e1 = JaxEngine(base)
    e2 = JaxEngine(pooled)
    try:
        sp_short = SamplingParams(max_tokens=6, temperature=0.0)
        sp_long = SamplingParams(max_tokens=40, temperature=0.0)
        short_prompt = "hi there"
        # too long for the short stripe with its 40 new tokens
        long_prompt = "tell me a long story " * (short_stripe // 21 + 1)
        assert len(short_prompt) + 6 < short_stripe < len(long_prompt) + 40 < long_stripe
        r1s = e1.generate(short_prompt, sampling_params=sp_short)
        r2s = e2.generate(short_prompt, sampling_params=sp_short)
        assert r1s.token_ids == r2s.token_ids
        r1l = e1.generate(long_prompt, sampling_params=sp_long)
        r2l = e2.generate(long_prompt, sampling_params=sp_long)
        assert r1l.token_ids == r2l.token_ids
        pools = e2.get_stats()["pools"]
        assert [p["stripe_len"] for p in pools] == list(buckets)
    finally:
        e1.shutdown()
        e2.shutdown()
