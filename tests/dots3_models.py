"""What ``tests/test_dots3*.py`` share: the ``dots3-tiny`` preset
(``LlamaConfig.dots3_tiny``: two indexed latent layers, then a period of
sliding latent ones, 8 positions a query and a window of 5, so that both
bind on a 30-token prompt), the published keys of that size for the
benchmark's family and reference, and the benchmark's seeded weights with
every norm scale moved off its seed (a vector at one hides a path that
ignores it)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.llama import LlamaConfig, decode_step, init_kv_cache, prefill

CFG = LlamaConfig.dots3_tiny()
TOL = dict(atol=3e-4, rtol=2e-3)
# what benchmark/families/sparse_latent.py reads, for the reference
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 64, "index_head_dim": 16, "index_n_heads": 4, "index_topk": 8,
    "intermediate_size": 128, "kv_lora_rank": 32,
    "layer_types": ["full_attention", "full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"],
    "max_position_embeddings": 128, "model_type": "dots3_note", "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_routed_experts": 16, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 3, "num_hidden_layers": 5,
    "num_key_value_heads": 4, "q_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid", "sliding_window_size": 5,
    "swa_attention_gate_type": "headwise", "swa_kv_lora_rank": 48,
    "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2, "swa_q_lora_rank": 32,
    "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8, "swa_rope_theta": 50000,
    "swa_v_head_dim": 16, "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 16, "vocab_size": 256,
}


def held(published: dict, experts: int, first: int = 0) -> dict:
    """``published`` as one chip's share: ``experts`` of the router's, from ``first``."""
    return {**published, "n_routed_experts": experts,
            "published": {"n_routed_experts": published["n_routed_experts"]},
            "run": {"experts_first": first}}


def seeded_params(seed: int = 3, published: dict = PUBLISHED, dtype=jnp.float32):
    """The benchmark's weights of ``seed``, each norm scale times a factor of
    its own about 1."""
    from benchmark.families import sparse_latent as family

    params = family.make_params(seed, published, dtype)
    key = jax.random.PRNGKey(100 + seed)
    for i, name in enumerate(sorted(n for n in params if "norm" in n)):
        move = 1.0 + 0.3 * jax.random.normal(jax.random.fold_in(key, i), params[name].shape)
        params[name] = (params[name] * move).astype(dtype)
    return params


def reference(published: dict = PUBLISHED):
    from benchmark.families import sparse_latent as family

    return family.Reference(published, jax.local_devices()[:1])


@functools.lru_cache(maxsize=None)
def _programs(cfg):
    """One jitted chunk and one jitted step a configuration, so that the
    tests of a file compile each width once."""
    pre = jax.jit(lambda p, c, t, n, s: prefill(p, c, t, cfg, lengths=n, start_pos=s))
    dec = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))
    return pre, dec


def through_the_cache(params, tokens, chunks, cfg=CFG, stripe=64):
    """``tokens`` [B, T] through a cache: ``chunks`` is a list of (width,
    lengths [B]): a launch of ``width`` columns in which row ``b`` takes its
    next ``lengths[b]`` tokens (the rest of its columns padding); then every
    row a token a step to the end of the shortest remainder. Returns (for
    each row the logits it got with the position each stands at, the cache,
    each row's tokens consumed)."""
    B, T = tokens.shape
    pre, dec = _programs(cfg)
    cache = init_kv_cache(cfg, B, stripe)
    at = np.zeros(B, np.int32)
    got = [[] for _ in range(B)]
    for width, lengths in chunks:
        lengths = np.asarray(lengths, np.int32)
        fed = np.zeros((B, width), np.int32)
        for b in range(B):
            fed[b, :lengths[b]] = tokens[b, at[b]:at[b] + lengths[b]]
        logits, cache = pre(params, cache, jnp.asarray(fed), jnp.asarray(lengths), jnp.asarray(at))
        at = at + lengths
        for b in range(B):
            if lengths[b]:
                got[b].append((at[b] - 1, np.asarray(logits[b])))
    while at.max() < T:
        fed = np.asarray([tokens[b, min(at[b], T - 1)] for b in range(B)], np.int32)
        logits, cache = dec(params, cache, jnp.asarray(fed))
        for b in range(B):
            if at[b] < T:
                got[b].append((at[b], np.asarray(logits[b])))
        at = at + 1
    return got, cache, at
