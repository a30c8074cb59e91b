"""What the ``test_kda_*`` files that came of ``tests/test_kda.py`` share: Solar
Open2 at test size (``LlamaConfig.solar_tiny``) with the published keys it is
mapped from, the benchmark's seeded parameters with the plain reference's
answers, the path through the cache in chunks, and a delta rule's inputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.patterned import state_cache_shapes

# the cut the cell serves, at test size: three delta-rule layers and the
# attention layer behind them, 4 of the router's 16 experts held
CFG = LlamaConfig.solar_tiny(n_layers=4, gqa_layers=(3,))
STATE = tuple(state_cache_shapes(CFG, 1))
# what benchmark/families/kda_moe.py reads, for the reference
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
                           "num_kv_heads": None},
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4, "head_dim": 16,
    "num_key_value_heads": 2, "vocab_size": 256, "intermediate_size": 128,
    "moe_intermediate_size": 32, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 128, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": [3], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 4, "published": {"n_routed_experts": 16},
}
T = 44
TOL = dict(atol=5e-5, rtol=1e-4)


@pytest.fixture(scope="module")
def model():
    """(the benchmark's seeded params, tokens [2, T], the reference's logits
    [2, T, V] and keys and values of the attention layer)."""
    from benchmark.families import kda_moe as family

    params = family.make_params(3, PUBLISHED, jnp.float32)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, CFG.vocab_size))
    ref = family.Reference(PUBLISHED, jax.local_devices()[:1])
    want = ref.forward_rows(params, list(tokens), last=T, kv_rows=range(2))
    return params, tokens, np.stack(want["logits"]), want["kv"]


def _kda_inputs(T, b=2, H=3, K=16, V=16, seed=0, rate=1.0, beta_shift=0.0):
    """Operands of the rule: unit keys, queries times K ** -0.5, log-decays
    log-uniform down to ``-rate`` a token, writing strengths 2 sigmoid(. +
    ``beta_shift``), from a state that is not zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (b, T, H, K)) for key in ks[:2])
    q, k = (t / jnp.linalg.norm(t, axis=-1, keepdims=True) for t in (q, k))
    v = jax.random.normal(ks[2], (b, T, H, V))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, T, H, K), minval=-6.0, maxval=np.log(rate)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, H)) + beta_shift)
    return jax.random.normal(ks[5], (b, H, K, V)), q * K ** -0.5, k, v, g, beta
