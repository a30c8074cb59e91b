"""Family ``moe_window_gqa``: pre-norm decoders whose layers alternate full
and sliding-window grouped-query attention (each kind with its own query-head
count and rotary settings, a per-head gate on the attention output), with a
dense feed-forward in the leading layers and routed experts plus a shared one
after them (poolside Laguna-XS.2), which the program expresses through
``models/llama.py``'s entry points and ``models/patterned.py`` behind them."""

from benchmark import common
from benchmark.reference_moe_window import Reference  # noqa: F401 - part of the family

KINDS = {"full_attention": "full", "sliding_attention": "sliding"}


def layer_rows(config: dict) -> dict:
    """Rows of each stack of per-layer leaves: all layers, each attention
    kind, the dense and the expert feed-forward layers."""
    types = [KINDS[t] for t in config["layer_types"]]
    return {
        "all": config["num_hidden_layers"],
        "full": types.count("full"), "sliding": types.count("sliding"),
        "dense": config["mlp_layer_types"].count("dense"),
        "sparse": config["mlp_layer_types"].count("sparse"),
    }


def heads_of(config: dict, kind: str) -> int:
    heads = {h for t, h in zip(config["layer_types"], config["num_attention_heads_per_layer"])
             if KINDS[t] == kind}
    common.require(len(heads) == 1, f"{kind} layers have query heads {sorted(heads)}")
    return heads.pop()


def model_kwargs(config: dict) -> dict:
    """The published (Hugging Face) keys of a configuration file as the
    program's ``LlamaConfig`` fields. Widths are read, never set here."""
    n = config["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        common.require(len(config[key]) == n, f"{key} has {len(config[key])} entries for {n} layers")
    full = config["rope_parameters"]["full_attention"]
    sliding = config["rope_parameters"]["sliding_attention"]
    common.require(full["rope_type"] == "yarn" and sliding["rope_type"] == "default"
                   and sliding["partial_rotary_factor"] == 1,
                   "models/patterned.py: YaRN on full layers, plain whole-head RoPE on sliding ones")
    common.require(not config["attention_bias"] and not config["moe_apply_router_weight_on_input"],
                   "no attention bias, router weight on the output")
    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=n,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_width=config["head_dim"],
        d_ff=config["intermediate_size"],
        rms_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        layer_types=tuple(KINDS[t] for t in config["layer_types"]),
        heads_per_layer=tuple(config["num_attention_heads_per_layer"]),
        mlp_types=tuple(config["mlp_layer_types"]),
        sliding_window=config["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        rope_partial=float(full["partial_rotary_factor"]),
        yarn_factor=float(full["factor"]),
        yarn_original_len=int(full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        rope_theta_sliding=float(sliding["rope_theta"]),
        attn_gate=bool(config["gating"]),
        moe_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        moe_shared_d_ff=config["shared_expert_intermediate_size"],
        moe_routed_scale=float(config["moe_routed_scaling_factor"]),
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
    # that lacks the preset or a field (a commit before PR 28) would fail in
    # every replica's constructor, and Serve would replace replicas until the
    # harness's 900 s health wait ran out
    try:
        resolve_llama_config(model, EngineConfig(**run["engine"]))
    except (TypeError, ValueError) as e:
        raise common.BenchFailure(f"the program cannot build this family's model: {e}") from e
    return model


# ------------------------------------------------------------------ weights


def param_shapes(config: dict) -> dict:
    """name -> (shape, fan_in or None for a norm scale). Leaves every layer
    shares are stacked over all layers, the others over the layers of their
    kind, in layer order: the tree ``models/patterned.py`` takes."""
    e, v = config["hidden_size"], config["vocab_size"]
    kv, hd = config["num_key_value_heads"], config["head_dim"]
    f, fm, fs = (config["intermediate_size"], config["moe_intermediate_size"],
                 config["shared_expert_intermediate_size"])
    n_exp = config["num_experts"]
    n = layer_rows(config)
    shapes = {
        "embed": ((v, e), e),
        "final_norm": ((e,), None),
        "wk": ((n["all"], e, kv, hd), e),
        "wv": ((n["all"], e, kv, hd), e),
        "attn_norm": ((n["all"], e), None),
        "mlp_norm": ((n["all"], e), None),
    }
    for kind in ("full", "sliding"):
        if n[kind]:
            h = heads_of(config, kind)
            shapes["wq_" + kind] = ((n[kind], e, h, hd), e)
            shapes["wo_" + kind] = ((n[kind], h, hd, e), h * hd)
            if config["gating"]:
                shapes["wg_" + kind] = ((n[kind], e, h), e)
    if n["dense"]:
        shapes.update({"w_gate": ((n["dense"], e, f), e), "w_up": ((n["dense"], e, f), e),
                       "w_down": ((n["dense"], f, e), f)})
    if n["sparse"]:
        m = n["sparse"]
        shapes.update({
            "moe_router": ((m, e, n_exp), e),
            "moe_w_gate": ((m, n_exp, e, fm), e), "moe_w_up": ((m, n_exp, e, fm), e),
            "moe_w_down": ((m, n_exp, fm, e), fm),
            "moe_shared_gate": ((m, e, fs), e), "moe_shared_up": ((m, e, fs), e),
            "moe_shared_down": ((m, fs, e), fs),
        })
    if not config["tie_word_embeddings"]:
        shapes["unembed"] = ((e, v), e)
    return shapes


def _is_expert_bank(name: str) -> bool:
    return name.startswith("moe_w_")


def make_params(seed: int, config: dict, dtype, shardings=None):
    """All leaves in one jitted call, normal with standard deviation
    ``fan_in ** -0.5``, norm scales at one. Stacked leaves are drawn a layer
    at a time and expert banks an expert at a time (``lax.map``), so the
    float32 draw of a whole leaf never exists beside the weights."""
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
            lead = 2 if _is_expert_bank(name) else 1
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
    """Every weight matrix through symmetric int8 and back, one scale per
    index of the last axis, per layer, and per expert in an expert bank: the
    lower precision a later PR would be tempted by. Norm scales are left
    alone. Used only by the control of ``correct``. A leaf at a time, in
    place."""
    import jax
    import jax.numpy as jnp

    def matrix(w):
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=tuple(range(w.ndim - 1)), keepdims=True) / 127.0
        q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
        return (q.astype(jnp.float32) * scale).astype(w.dtype)

    def leaf(w, depth):
        if depth == 0:
            return matrix(w)
        return jax.lax.map(lambda x: leaf(x, depth - 1), w)

    def depth_of(name, w):
        if _is_expert_bank(name):
            return 2
        return 1 if w.ndim >= 3 else 0

    return {
        name: w if "norm" in name
        else jax.jit(lambda x, d=depth_of(name, w): leaf(x, d),
                     out_shardings=w.sharding, donate_argnums=(0,))(w)
        for name, w in params.items()
    }


# ------------------------------------------- what a step needs: bytes and operations


def attention_params(config: dict, kind: str) -> int:
    """Matmul parameters of one attention layer of that kind (q, k, v, the
    gate, o)."""
    e, kv, hd = config["hidden_size"], config["num_key_value_heads"], config["head_dim"]
    h = heads_of(config, kind)
    gate = e * h if config["gating"] else 0
    return e * h * hd + 2 * e * kv * hd + gate + h * hd * e


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def moe_fixed_params(config: dict) -> int:
    """What every token of an expert layer passes through: router and shared
    expert."""
    e = config["hidden_size"]
    return e * config["num_experts"] + 3 * e * config["shared_expert_intermediate_size"]


def chunk_mid_expert_layers(config: dict) -> int:
    """Expert layers a middle prompt chunk has to run. It hands out keys and
    values and no logits, so nothing reads the last layer's feed-forward and
    the compiler drops it (on a v5e ``jit_chunk_mid`` of the 5-layer cut has
    9 grouped-matmul calls where ``jit_chunk_final`` has 12; PERF.md section
    6, PR 28). Its router still runs, for the routing counts."""
    kinds = config["mlp_layer_types"]
    return kinds.count("sparse") - (kinds[-1] == "sparse")


def moe_needed_bytes(config: dict, layers: int, experts_touched: float, dtype_bytes: int = 2) -> float:
    """Bytes ``layers`` expert-layer runs must read: router and shared expert
    each run, and the weights of the experts that got a token
    (``experts_touched``: summed over those runs)."""
    return dtype_bytes * (layers * moe_fixed_params(config) + experts_touched * expert_params(config))


def moe_needed_flops(config: dict, layers: int, tokens: float) -> float:
    """Operations ``layers`` expert-layer runs over ``tokens`` tokens each
    need: router, shared expert, and ``num_experts_per_tok`` experts a token."""
    per_token = moe_fixed_params(config) + config["num_experts_per_tok"] * expert_params(config)
    return 2.0 * layers * tokens * per_token


def kv_bytes_per_token_layer(config: dict, dtype_bytes: int = 2) -> int:
    return 2 * config["num_key_value_heads"] * config["head_dim"] * dtype_bytes


def decode_weight_bytes(config: dict, experts_touched_per_layer: float, dtype_bytes: int = 2) -> float:
    """Weights one decode step must read: attention and norms of every layer,
    the dense layers' feed-forward, router, shared expert and the touched
    experts of every expert layer, the final norm and the head (of the
    embedding table a step reads a row a slot)."""
    e, v, f = config["hidden_size"], config["vocab_size"], config["intermediate_size"]
    n = layer_rows(config)
    params = (
        n["full"] * attention_params(config, "full")
        + n["sliding"] * attention_params(config, "sliding")
        + n["all"] * 2 * e + e + v * e
        + n["dense"] * 3 * e * f
    )
    return dtype_bytes * params + moe_needed_bytes(
        config, n["sparse"], n["sparse"] * experts_touched_per_layer, dtype_bytes)
