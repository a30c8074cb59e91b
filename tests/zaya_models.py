"""What ``tests/test_zaya*.py`` share: the ``zaya-tiny`` preset
(``LlamaConfig.zaya_tiny``: compressed convolutional attention under top-1
experts routed by an MLP with its own stream, learned scales at the joins), the
published keys of that size for the benchmark's family and reference, and the
benchmark's seeded weights with every learned vector moved off its seed (the
scales and the temperatures off 1, ``gamma`` off its constant: a vector at its
seed hides a path that ignores it)."""

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.llama import LlamaConfig, decode_step, init_kv_cache, prefill

CFG = LlamaConfig.zaya_tiny()
# what benchmark/families/cca_moe.py reads, for the reference
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "layer_types": ["hybrid"] * 3,
    "lm_head_bias": False, "max_position_embeddings": 128, "model_type": "zaya",
    "moe_intermediate_size": 32, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_per_tok": 1, "num_hidden_layers": 3, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-5,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 10000, "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 16, "sliding_window": None, "tie_word_embeddings": True,
    "vocab_size": 256, "published": {"num_experts": 8}, "run": {"experts_first": 0},
}
MOVED = ("attn_scale", "mlp_scale", "cca_temp", "moe_router_gamma", "moe_router_norm",
         "attn_norm", "mlp_norm")


def seeded_params(seed: int = 3, published: dict = PUBLISHED, dtype=jnp.float32):
    """The benchmark's weights of ``seed``, each vector of ``MOVED`` times a
    factor of its own about 1."""
    from benchmark.families import cca_moe as family

    params = family.make_params(seed, published, dtype)
    key = jax.random.PRNGKey(100 + seed)
    for i, name in enumerate(MOVED):
        move = 1.0 + 0.3 * jax.random.normal(jax.random.fold_in(key, i), params[name].shape)
        params[name] = (params[name] * move).astype(dtype)
    return params


def reference(published: dict = PUBLISHED):
    from benchmark.families import cca_moe as family

    return family.Reference(published, jax.local_devices()[:1])


def through_the_cache(params, tokens, chunks, cfg=CFG, stripe=64):
    """``tokens`` [B, T] through a cache: ``chunks`` is a list of (width,
    lengths [B]): a launch of ``width`` columns in which row ``b`` takes its
    next ``lengths[b]`` tokens (the rest of its columns padding); then every
    row a token a step to the end of the shortest remainder. Returns (for
    each row the logits it got with the position each stands at, the cache,
    each row's tokens consumed)."""
    B, T = tokens.shape
    pre = jax.jit(lambda p, c, t, n, s: prefill(p, c, t, cfg, lengths=n, start_pos=s))
    dec = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))
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
