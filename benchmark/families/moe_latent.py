"""Family ``moe_latent``: pre-norm decoders with latent attention (one normed
key-value latent and one rotated key a token for all heads, ``model_type:
deepseek_v3`` without a query latent), a dense feed-forward in the leading
layers and sigmoid-routed experts with a selection bias beside shared ones
after them (kakaocorp Kanana-2-30B-A3B), which the program expresses through
``models/llama.py``'s entry points and ``models/patterned.py`` behind them
(attention kind ``latent``)."""

from benchmark import common
from benchmark.families.moe_window_gqa import int8_roundtrip as _int8_roundtrip
from benchmark.reference_moe_latent import Reference  # noqa: F401 - part of the family

# Standard deviation of the seeded embedding table: one, not ``hidden ** -0.5``
# as the other families draw theirs. A fan-in-scaled row has norm 1 beside a
# first layer whose attention and feed-forward add some 5 and 18 that are
# nearly the same for every row of a decode batch (attention over 12k-20k
# seeded bytes is close to their mean), so all rows chose nearly the same
# experts: 38-49 of 128 touched a layer where 22 rows x 6 independent choices
# touch 83, by the seed's weights, and ``serve_tok_s`` followed that count from
# seed to seed (correlation -0.93 over 12 seeds; PERF.md section 6, PR 33).
# With unit rows the token's own part leads the stream, as it does in a
# trained model, and rows route by their tokens.
EMBED_STD = 1.0
# standard deviation of the seeded selection bias (a buffer the published
# model's training moves, not a weight): small against the scores' spread and
# not zero, so that the choice of experts and their weights differ
BIAS_STD = 0.05


def layer_rows(config: dict) -> dict:
    """Rows of each stack of per-layer leaves: all layers (every one a latent
    attention layer), the dense and the expert feed-forward layers."""
    n = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], n)
    return {"all": n, "dense": dense, "sparse": n - dense}


def model_kwargs(config: dict) -> dict:
    """The published (Hugging Face) keys of a configuration file as the
    program's ``LlamaConfig`` fields. Widths are read, never set here."""
    c = config
    common.require(c["q_lora_rank"] is None and c["rope_scaling"] is None and not c["attention_bias"],
                   "models/patterned.py: no query latent, no rope scaling, no attention bias")
    common.require(c["qk_head_dim"] == c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
                   "qk_head_dim is the two parts of a query")
    common.require(c["n_group"] == 1 and c["topk_group"] == 1 and c["norm_topk_prob"]
                   and c["moe_layer_freq"] == 1 and c["topk_method"] == "noaux_tc"
                   and c["hidden_act"] == "silu",
                   "parallel/moe.py topk_gates: no group step, top k renormalised, every layer "
                   "after the dense ones an expert layer, SwiGLU")
    n = c["num_hidden_layers"]
    rows = layer_rows(c)
    return dict(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        n_layers=n,
        n_heads=c["num_attention_heads"],
        d_ff=c["intermediate_size"],
        rms_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        kv_latent_rank=c["kv_lora_rank"],
        qk_nope_dim=c["qk_nope_head_dim"],
        qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"],
        rope_interleave=bool(c["rope_interleave"]),
        layer_types=("latent",) * n,
        heads_per_layer=(c["num_attention_heads"],) * n,
        mlp_types=("dense",) * rows["dense"] + ("sparse",) * rows["sparse"],
        moe_experts=c["n_routed_experts"],
        moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        moe_shared_d_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
        moe_routed_scale=float(c["routed_scaling_factor"]),
        moe_scoring=c["scoring_func"],
    )


def served_model(config: dict, seed: int):
    """The program's ``ModelConfig`` for a serving cell: every size comes from
    the configuration file; the preset only names the family's code path."""
    from ray_tpu.llm import EngineConfig, ModelConfig
    from ray_tpu.llm.config import resolve_llama_config

    run = config["run"]
    model = ModelConfig(
        model_id=run["preset"], tokenizer=run["tokenizer"], seed=seed,
        model_kwargs=model_kwargs(config),
    )
    # resolved here, in the driver, before any replica is started: a program
    # that lacks the preset or a field (a commit before PR 33) fails at once,
    # not in every replica's constructor until the health wait runs out
    try:
        resolve_llama_config(model, EngineConfig(**run["engine"]))
    except (TypeError, ValueError) as e:
        raise common.BenchFailure(f"the program cannot build this family's model: {e}") from e
    return model


# ------------------------------------------------------------------ weights


def param_shapes(config: dict) -> dict:
    """name -> (shape, fan_in or None for a norm scale): drawn normal with
    standard deviation ``fan_in ** -0.5`` (the size contracted away; the
    entries of the embedding table and of the selection bias are those that
    give ``EMBED_STD`` and ``BIAS_STD``). The tree ``models/patterned.py`` takes: ``wkv_b`` of
    the published model is its two halves by head, ``wuk_latent`` [h, nope,
    rank] and ``wuv_latent`` [h, rank, v]."""
    c = config
    e, v, h = c["hidden_size"], c["vocab_size"], c["num_attention_heads"]
    r, nope, rope, vd = (c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                         c["v_head_dim"])
    f, fm = c["intermediate_size"], c["moe_intermediate_size"]
    fs, n_exp = c["n_shared_experts"] * fm, c["n_routed_experts"]
    n = layer_rows(c)
    L = n["all"]
    shapes = {
        "embed": ((v, e), EMBED_STD ** -2),
        "final_norm": ((e,), None),
        "attn_norm": ((L, e), None),
        "mlp_norm": ((L, e), None),
        "wq_latent": ((L, e, h, nope + rope), e),
        "wkv_a_latent": ((L, e, r + rope), e),
        "kv_norm_latent": ((L, r), None),
        "wuk_latent": ((L, h, nope, r), r),
        "wuv_latent": ((L, h, r, vd), r),
        "wo_latent": ((L, h, vd, e), h * vd),
    }
    if n["dense"]:
        d = n["dense"]
        shapes.update({"w_gate": ((d, e, f), e), "w_up": ((d, e, f), e), "w_down": ((d, f, e), f)})
    if n["sparse"]:
        m = n["sparse"]
        shapes.update({
            "moe_router": ((m, e, n_exp), e),
            "moe_router_bias": ((m, n_exp), BIAS_STD ** -2),
            "moe_w_gate": ((m, n_exp, e, fm), e), "moe_w_up": ((m, n_exp, e, fm), e),
            "moe_w_down": ((m, n_exp, fm, e), fm),
            "moe_shared_gate": ((m, e, fs), e), "moe_shared_up": ((m, e, fs), e),
            "moe_shared_down": ((m, fs, e), fs),
        })
    if not c["tie_word_embeddings"]:
        shapes["unembed"] = ((e, v), e)
    return shapes


def make_params(seed: int, config: dict, dtype, shardings=None):
    """All leaves in one jitted call, normal with standard deviation
    ``fan_in ** -0.5`` (the bias: ``BIAS_STD``), norm scales at one. Stacked
    leaves are drawn a layer at a time and expert banks an expert at a time
    (``lax.map``), so the float32 draw of a whole leaf never exists beside
    the weights."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)
    names = sorted(shapes)

    def make(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shape, fan_in = shapes[name]
            if fan_in is None:
                out[name] = jnp.ones(shape, dtype)
                continue
            lead = 2 if name.startswith("moe_w_") else 1
            rows = 1
            for d in shape[:lead]:
                rows *= d

            def draw(k, shape=shape[lead:], std=fan_in ** -0.5):
                return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

            out[name] = jax.lax.map(draw, jax.random.split(k, rows)).reshape(shape)
        return out

    if shardings is not None:
        shardings = {name: shardings[name] for name in names}
    return jax.jit(make, out_shardings=shardings)(jax.random.PRNGKey(seed))


def int8_roundtrip(params):
    """Every weight matrix through symmetric int8 and back (the other expert
    family's, leaf for leaf). Norm scales and the selection bias, a buffer and
    no matrix, are left alone. Used only by the control of ``correct``."""
    bias = {k: v for k, v in params.items() if k == "moe_router_bias"}
    return {**_int8_roundtrip({k: v for k, v in params.items() if k not in bias}), **bias}


# ------------------------------------------- what a step needs: bytes and operations


def attention_params(config: dict) -> int:
    """Matmul parameters of one attention layer (Wq, Wkv_a, Wkv_b, Wo)."""
    c = config
    e, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return e * h * (nope + rope) + e * (r + rope) + r * h * (nope + vd) + h * vd * e


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def moe_fixed_params(config: dict) -> int:
    """What every token of an expert layer passes through: router, its bias
    and the shared experts."""
    e, n_exp = config["hidden_size"], config["n_routed_experts"]
    return e * n_exp + n_exp + 3 * e * config["n_shared_experts"] * config["moe_intermediate_size"]


def param_count(config: dict) -> int:
    total = 0
    for shape, _ in param_shapes(config).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def moe_needed_bytes(config: dict, layers: int, experts_touched: float, dtype_bytes: int = 2) -> float:
    """Bytes ``layers`` expert-layer runs must read: router and shared
    experts each run, and the weights of the experts that got a token
    (``experts_touched``: summed over those runs)."""
    return dtype_bytes * (layers * moe_fixed_params(config) + experts_touched * expert_params(config))


def latent_bytes_per_token_layer(config: dict, dtype_bytes: int = 2) -> int:
    """What attention needs of a cached token in one layer: the latent and
    the shared rotated key (the chip holds the key in a 128-lane row; the
    need is the numbers)."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * dtype_bytes


def decode_weight_bytes(config: dict, experts_touched_per_layer: float, dtype_bytes: int = 2) -> float:
    """Weights one decode step must read: attention and norms of every layer,
    the dense layers' feed-forward, router, shared experts and the touched
    experts of every expert layer, the final norm and the head (of the
    embedding table a step reads a row a slot)."""
    e, v, f = config["hidden_size"], config["vocab_size"], config["intermediate_size"]
    n = layer_rows(config)
    params = (
        n["all"] * (attention_params(config) + 2 * e + config["kv_lora_rank"]) + e + v * e
        + n["dense"] * 3 * e * f
    )
    return dtype_bytes * params + moe_needed_bytes(
        config, n["sparse"], n["sparse"] * experts_touched_per_layer, dtype_bytes)


def attention_flops(config: dict, query_tokens: float, attended: float, seen: float) -> float:
    """Operations one layer's attention over a latent cache needs, in the
    cheaper of its two forms (``models/patterned.py _chunk_expands`` chooses
    so, by the chunk's padded width; this counts its real tokens).
    ``attended``: (query, cached position) pairs, summed over the queries;
    ``seen``: cached positions the last query sees. Absorbed: each query
    through the key up-projection (2 * heads * nope * rank a token), then a
    score (rank + rope) and a context (rank) a head and pair. Expanded: each
    seen position's keys and values from its latent (2 * rank * heads * (nope
    + v)), then a score (nope + rope) and a context (v) a head and pair."""
    c = config
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    absorbed = 2.0 * h * (query_tokens * nope * r + attended * (2 * r + rope))
    expanded = 2.0 * h * (seen * r * (nope + v) + attended * (nope + rope + v))
    return min(absorbed, expanded)
