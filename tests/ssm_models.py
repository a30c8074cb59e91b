"""What the ``test_ssm_*`` files share: NVIDIA Nemotron-3-Super at test size
(``LlamaConfig.nemotron_tiny``) with the published keys it is mapped from, the
benchmark's seeded parameters with the plain reference's answers, and the path
through the cache in chunks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.patterned import state_cache_shapes

CFG = LlamaConfig.nemotron_tiny()
# the leaves a slot of this model holds whatever its length
STATE = tuple(state_cache_shapes(CFG, 1))
# what benchmark/families/ssm_latent_moe.py reads, for the reference: the
# configuration holds 4 of the router's 16 experts
PUBLISHED = {
    "attention_bias": False, "chunk_size": 8, "conv_kernel": 4, "expand": 2, "head_dim": 16,
    "hidden_size": 64, "hybrid_override_pattern": "MEMEMEM*EME", "intermediate_size": 48,
    "mamba_head_dim": 16, "mamba_hidden_act": "silu", "mamba_num_heads": 8,
    "mamba_proj_bias": False, "max_position_embeddings": 128, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 48,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96, "n_group": 1,
    "n_groups": 2, "n_routed_experts": 4, "n_shared_experts": 1, "norm_eps": 1e-5,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts_per_tok": 6,
    "num_hidden_layers": 11, "num_key_value_heads": 2, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 16,
    "tie_word_embeddings": False, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True, "vocab_size": 256,
    "published": {"n_routed_experts": 16},
}
T = 44
TOL = dict(atol=5e-5, rtol=1e-4)


@pytest.fixture(scope="module")
def model():
    """(the benchmark's seeded params, tokens [2, T], the reference's logits
    [2, T, V] and keys and values of the attention block)."""
    from benchmark.families import ssm_latent_moe as family

    params = family.make_params(3, PUBLISHED, jnp.float32)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, CFG.vocab_size))
    ref = family.Reference(PUBLISHED, jax.local_devices()[:1])
    want = ref.forward_rows(params, list(tokens), last=T, kv_rows=range(2))
    return params, tokens, np.stack(want["logits"]), want["kv"]


def _ssm_inputs(T, b=2, H=8, P=16, N=16, G=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, H)) - 3.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    B, C = jax.random.normal(ks[3], (b, T, G, N)), jax.random.normal(ks[4], (b, T, G, N))
    state = jax.random.normal(ks[5], (b, H, P, N))
    return state, x, dt, a, B, C, jnp.ones((H,))
