"""The models the ``test_patterned_*`` files run the one body that carries
tokens through the cache on (``models/patterned.py``): layers alike (dense,
with LoRA adapters, with routed experts) and not alike (Laguna-XS.2's pattern
at test size), each with its parameters, tokens and ``forward``'s logits."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, forward, init_lora_stack, init_params

CFG = LlamaConfig.laguna_tiny()
# what benchmark/families/moe_window_gqa.py reads, for the reference
PUBLISHED = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 5,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "tie_word_embeddings": False, "gating": True,
    "sliding_window": 8, "moe_apply_router_weight_on_input": False, "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
                           "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 8,
                           "attention_factor": 1.2, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
    },
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
}

_MOE = dict(moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0)  # ample: ``forward`` drops nothing
MODELS = {
    "dense": LlamaConfig.tiny(),
    "dense-lora": LlamaConfig.tiny(),
    "moe": LlamaConfig.tiny(**_MOE),
    "moe-shared": LlamaConfig.tiny(**_MOE, moe_d_ff=48, moe_shared_d_ff=32, moe_routed_scale=2.5),
    "laguna": CFG,
}
ADAPTERS = (1, 2)  # the adapter of each of the two rows, where there are any


@functools.lru_cache(maxsize=None)
def _model(name):
    """(cfg, params, the LoRA arguments of ``prefill`` / ``decode_step`` for
    the given rows, tokens [2, 44], ``forward``'s logits). With adapters,
    ``forward`` runs a row at a time on weights with the row's adapter
    folded into ``wq`` and ``wv``."""
    cfg = MODELS[name]
    params = init_params(jax.random.PRNGKey(7), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 44), 0, cfg.vocab_size)
    if "lora" not in name:
        return cfg, params, lambda rows=(0, 1): {}, toks, forward(params, toks, cfg)
    rng = np.random.default_rng(7)
    loras = {k: jnp.asarray(rng.normal(0, 0.1, v.shape), v.dtype)
             for k, v in init_lora_stack(cfg, 2, 4).items()}

    def folded(a):
        return dict(
            params,
            wq=params["wq"] + jnp.einsum("ler,lrhd->lehd", loras["wq_a"][:, a], loras["wq_b"][:, a]),
            wv=params["wv"] + jnp.einsum("ler,lrhd->lehd", loras["wv_a"][:, a], loras["wv_b"][:, a]),
        )

    whole = jnp.concatenate([forward(folded(a), toks[b:b + 1], cfg) for b, a in enumerate(ADAPTERS)])
    return cfg, params, lambda rows=(0, 1): dict(
        loras=loras, adapter_ids=jnp.asarray([ADAPTERS[b] for b in rows], jnp.int32)), toks, whole


def _tol(cfg):  # the capacity form of ``forward``'s expert layers sums in another order
    return dict(atol=5e-5, rtol=1e-4) if cfg.layer_types else dict(atol=2e-4, rtol=2e-4)


def _count_kernel_calls(monkeypatch):
    """The kernel's calls, as a layer loop's body is traced."""
    traced = []
    kernel = patterned.decode_attention
    monkeypatch.setattr(patterned, "decode_attention",
                        lambda *a: traced.append(a[3]) or kernel(*a))
    return traced
