"""Several decode steps a launch against one, and a prompt prefilled in
chunks up to the stripe's end against the full forward pass."""

import numpy as np
import pytest

from ray_tpu.llm import (
    EngineConfig,
    JaxEngine,
    LLMConfig,
    ModelConfig,
    SamplingParams,
)

pytestmark = pytest.mark.timeout(600) if hasattr(pytest.mark, "timeout") else []


def test_multi_step_decode_equivalence():
    """decode_steps=4 (K steps per device program) produces exactly the
    single-step greedy tokens — only host round trips differ."""
    one = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(max_num_seqs=2, max_seq_len=128,
                            prefill_buckets=(16, 32, 64, 128),
                            enable_prefix_caching=False),
    )
    multi = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(max_num_seqs=2, max_seq_len=128,
                            prefill_buckets=(16, 32, 64, 128),
                            enable_prefix_caching=False, decode_steps=4),
    )
    e1, e2 = JaxEngine(one), JaxEngine(multi)
    try:
        sp = SamplingParams(max_tokens=11, temperature=0.0, ignore_eos=True)
        r1 = e1.generate("multi step decode test", sampling_params=sp)
        r2 = e2.generate("multi step decode test", sampling_params=sp)
        assert r1.token_ids == r2.token_ids
        assert len(r2.token_ids) == 11  # max_tokens honored despite K=4
    finally:
        e1.shutdown()
        e2.shutdown()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_chunked_prefill_to_the_stripes_end_matches_full_forward(dtype):
    """Prompt chunks go into the scratch stripe as contiguous blocks
    (``models/patterned.py _write_block``). Through the engine: a 300-token
    prompt in five chunks; then two prompts behind a 16-token prefix hit, so
    every chunk starts off the chunk grid and the final one's bucketed width
    passes the stripe's end (16 + 7 * 64 + 64 > 512), one of them
    ``stripe_len - 1`` long. Each returns the tokens ``forward`` gives on the
    same weights; no path but the block write is reachable from the engine's
    prefill (B = 1, width <= stripe), so there is no fallback to count.

    The first token is the prefill's, whose attention is ``forward``'s
    einsum: equal to the token at either dtype. The later ones come from
    decode steps, which read the 512-position stripe through the decode
    kernel (``ops/decode_attention.py``): its scores stay float32 where
    ``forward`` rounds them to the model's dtype. In float32 they are
    ``forward``'s greedy tokens; at the engine's default bf16 each is a token
    ``forward`` puts within bf16's rounding of its best, given the engine's
    tokens before it."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import forward, init_kv_cache, prefill

    stripe = 512
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(
            max_num_seqs=2, max_seq_len=stripe, prefill_chunk=64,
            prefill_buckets=(16, 32, 64, 128), dtype=dtype,
        ),
    ))
    try:
        rng = np.random.default_rng(27)
        first = [int(t) for t in rng.integers(1, 250, 300)]
        # share 16 tokens with `first` and differ at the 17th: a hit at the
        # 16-token bucket and at no wider one
        def behind_prefix(n):
            rest = [int(t) for t in rng.integers(1, 250, n - 16)]
            rest[0] = (first[16] + 1) % 250 + 1
            return first[:16] + rest

        plans = [
            (first, 6, 0, 4),
            (behind_prefix(stripe - 1), 1, 16, 7),
            (behind_prefix(500), 8, 16, 7),
        ]
        mids = finals = 0
        for ids, n_new, hit, n_mid in plans:
            out = eng.generate(
                prompt_token_ids=ids,
                sampling_params=SamplingParams(
                    max_tokens=n_new, temperature=0.0, ignore_eos=True
                ),
            )
            assert out.metrics["prefix_hit_tokens"] == hit
            # ``forward`` over the prompt and the engine's tokens, one pass:
            # row n - 1 + i is what it makes of the i-th new token
            logits = np.asarray(forward(
                eng.params, jnp.asarray([ids + out.token_ids[:-1]], jnp.int32),
                eng.model_cfg,
            )[0, len(ids) - 1:], np.float32)
            assert len(logits) == len(out.token_ids) == n_new
            best = logits.argmax(-1)
            assert out.token_ids[0] == best[0]
            if dtype == "float32":
                assert out.token_ids == best.tolist()
            else:  # bf16 keeps 8 bits: four steps of the largest logit's rounding
                behind = logits.max(-1) - logits[np.arange(n_new), out.token_ids]
                assert (behind <= 4 * 2.0**-8 * np.abs(logits).max()).all(), behind
            # a tiny model's argmax hardly feels a misplaced key: read the
            # slot's keys and values back, against the prompt in one piece
            n = len(ids)
            _, ref = prefill(
                eng.params, init_kv_cache(eng.model_cfg, 1, stripe),
                jnp.asarray([ids], jnp.int32), eng.model_cfg,
            )
            slot = out.metrics["slot"]
            for key in ("k", "v"):
                np.testing.assert_allclose(
                    np.asarray(eng._pools[0].cache[key][:, slot, :, :n]),
                    np.asarray(ref[key][:, 0, :, :n]), rtol=2e-2, atol=2e-2,
                )
            mids, finals = mids + n_mid, finals + 1
            chunks = eng.get_stats()["counters"]["prefill_chunks"]
            assert chunks == {"mid": mids, "final": finals}
    finally:
        eng.shutdown()
