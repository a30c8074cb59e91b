"""Family ``sparse_latent``: pre-norm decoders whose layers are latent
attention of two kinds side by side, each with a query latent and a sigmoid
gate a head: *full* layers that attend the positions a learned indexer picks
(DeepSeek-V3.2's: ``index_topk`` of all before the query) and *sliding* ones
over a window, with a latent, heads and a rotary base of their own; a dense
feed-forward in the leading layer and sigmoid-routed experts with a selection
bias beside a shared one after it (``model_type: dots3_note``; dots-studio
dots3-note-prev), which the program expresses through ``models/llama.py``'s
entry points and ``models/patterned.py`` behind them (attention kinds
``latent`` and ``latent_sliding``, ``q_latent_rank``, ``index_topk``,
``moe_experts_held``). A configuration holds one chip's share of a stated
deployment: ``n_routed_experts`` and ``vocab_size`` are what the chip holds of
``published``'s."""

from benchmark import common
from benchmark.families.moe_latent import EMBED_STD
from benchmark.families.moe_window_gqa import int8_roundtrip as _int8_roundtrip
from benchmark.reference_sparse_latent import LAYER_KINDS, Reference  # noqa: F401 - part of the family

# Seeded standard deviation of the selection bias (a buffer the published
# model's training moves until the experts' load is even): 0.01, not Kanana's
# 0.05. This chip holds 16 of the router's 256 experts, and its share of a
# step's work is a sixteenth only where the seeded router is balanced: a bias
# of 0.05 beside sigmoid scores that spread 0.21 over the experts moves an
# expert's chance of being among a token's 8 by half, so over 12 seeds the
# held sixteen got 5.25-7.87% of the assignments where 6.25% is even, and
# ``serve_tok_s`` followed that share (925-968 tokens/s, correlation -0.94,
# spread 2.6% and 3.1% in two sets of six: PERF.md section 6, PR 51). At 0.01
# the share stays within a twentieth of even; not zero, so that the choice
# (score + bias) and the weights (the scores alone) still differ.
BIAS_STD = 0.01
# seeded standard deviation of the index key's LayerNorm bias: small, not zero
INDEX_BIAS_STD = 0.02
# leaves that are no weight matrix (norm scales apart): the int8 control
# leaves them alone
VECTORS = ("moe_router_bias", "index_k_bias")
LANES = 128  # a cached key's row on the chip: whole lane tiles


def router_experts(config: dict) -> int:
    """Experts the router scores: the published count, of which
    ``n_routed_experts`` are held here."""
    return config.get("published", config)["n_routed_experts"]


def layer_rows(config: dict) -> dict:
    """Rows of each stack of per-layer leaves: all layers, the full (indexed)
    and the sliding attention layers, the dense and the expert feed-forwards."""
    n = config["num_hidden_layers"]
    kinds = [LAYER_KINDS[t] for t in config["layer_types"][:n]]
    dense = min(config["first_k_dense_replace"], n)
    return {"all": n, "full": kinds.count("latent"), "sliding": kinds.count("latent_sliding"),
            "dense": dense, "sparse": n - dense}


def kind_sizes(config: dict, kind: str) -> dict:
    """The sizes of one attention kind (``full`` or ``sliding``): heads, the
    two latents' ranks, a head's query in its two parts and its value."""
    pre = "swa_" if kind == "sliding" else ""
    c = config
    return {"heads": c[pre + "num_attention_heads"], "q_rank": c[pre + "q_lora_rank"],
            "rank": c[pre + "kv_lora_rank"], "nope": c[pre + "qk_nope_head_dim"],
            "rope": c[pre + "qk_rope_head_dim"], "v": c[pre + "v_head_dim"]}


def model_kwargs(config: dict) -> dict:
    """The published (Hugging Face) keys of a configuration file as the
    program's ``LlamaConfig`` fields. Widths are read, never set here."""
    c = config
    common.require(c["rope_scaling"] is None and not c["attention_bias"],
                   "models/patterned.py: no rope scaling, no attention bias")
    common.require(c["attention_gate_type"] == c["swa_attention_gate_type"] == "headwise",
                   "models/patterned.py attn_gate: a gate a head on both kinds")
    common.require(c["norm_topk_prob"] and c["moe_layer_freq"] == 1
                   and c["topk_method"] == "noaux_tc" and c["hidden_act"] == "silu",
                   "parallel/moe.py topk_gates: top k renormalised, every layer after the "
                   "dense ones an expert layer, SwiGLU")
    n = c["num_hidden_layers"]
    rows = layer_rows(c)
    full, sliding = kind_sizes(c, "full"), kind_sizes(c, "sliding")
    kinds = tuple(LAYER_KINDS[t] for t in c["layer_types"][:n])
    held, router = c["n_routed_experts"], router_experts(c)
    return dict(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        n_layers=n,
        n_heads=full["heads"],
        d_ff=c["intermediate_size"],
        rms_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        q_latent_rank=full["q_rank"],
        kv_latent_rank=full["rank"],
        qk_nope_dim=full["nope"],
        qk_rope_dim=full["rope"],
        v_head_dim=full["v"],
        q_latent_rank_sliding=sliding["q_rank"],
        kv_latent_rank_sliding=sliding["rank"],
        qk_nope_dim_sliding=sliding["nope"],
        qk_rope_dim_sliding=sliding["rope"],
        v_head_dim_sliding=sliding["v"],
        rope_theta_sliding=float(c["swa_rope_theta"]),
        sliding_window=c["sliding_window_size"],
        latent_rescale=bool(c["apply_mla_qkv_lora_rescale"]),
        attn_gate=True,
        index_heads=c["index_n_heads"],
        index_head_dim=c["index_head_dim"],
        index_topk=c["index_topk"],
        layer_types=kinds,
        heads_per_layer=tuple(full["heads"] if k == "latent" else sliding["heads"] for k in kinds),
        mlp_types=("dense",) * rows["dense"] + ("sparse",) * rows["sparse"],
        moe_experts=router,
        moe_experts_held=held if held != router else 0,
        moe_experts_first=int(c.get("run", {}).get("experts_first", 0)),
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
    # that lacks the preset or a field (a commit before PR 51) fails at once,
    # not in every replica's constructor until the health wait runs out
    try:
        resolve_llama_config(model, EngineConfig(**run["engine"]))
    except (TypeError, ValueError) as e:
        raise common.BenchFailure(f"the program cannot build this family's model: {e}") from e
    return model


# ------------------------------------------------------------------ weights


def attention_shapes(config: dict, kind: str, n: int) -> dict:
    """The leaves of ``n`` stacked attention layers of ``kind`` (``full`` or
    ``sliding``), each (shape, fan_in or None for a norm scale), named as
    ``models/patterned.py`` names them."""
    e, s = config["hidden_size"], kind_sizes(config, kind)
    tag = "latent" if kind == "full" else "latent_sliding"
    h, r, q = s["heads"], s["rank"], s["q_rank"]
    shapes = {
        f"wqa_{tag}": ((n, e, q), e),
        f"q_norm_{tag}": ((n, q), None),
        f"wq_{tag}": ((n, q, h, s["nope"] + s["rope"]), q),
        f"wkv_a_{tag}": ((n, e, r + s["rope"]), e),
        f"kv_norm_{tag}": ((n, r), None),
        f"wuk_{tag}": ((n, h, s["nope"], r), r),
        f"wuv_{tag}": ((n, h, r, s["v"]), r),
        f"wo_{tag}": ((n, h, s["v"], e), h * s["v"]),
        f"wg_{tag}": ((n, e, h), e),
    }
    if kind == "full":
        hi, di = config["index_n_heads"], config["index_head_dim"]
        shapes.update({
            "index_wq": ((n, q, hi * di), q), "index_wk": ((n, e, di), e),
            "index_k_norm": ((n, di), None), "index_k_bias": ((n, di), INDEX_BIAS_STD ** -2),
            "index_ww": ((n, e, hi), e),
        })
    return shapes


def param_shapes(config: dict) -> dict:
    """name -> (shape, fan_in or None for a norm scale): drawn normal with
    standard deviation ``fan_in ** -0.5`` (the size contracted away; the
    embedding table's, the selection bias's and the index key's bias's entries
    are those that give ``EMBED_STD``, ``BIAS_STD`` and ``INDEX_BIAS_STD``).
    The tree ``models/patterned.py`` takes: the router scores every published
    expert, the banks hold this chip's."""
    c = config
    e, v = c["hidden_size"], c["vocab_size"]
    f, fm = c["intermediate_size"], c["moe_intermediate_size"]
    fs, held, router = c["n_shared_experts"] * fm, c["n_routed_experts"], router_experts(c)
    n = layer_rows(c)
    L = n["all"]
    shapes = {
        "embed": ((v, e), EMBED_STD ** -2),
        "final_norm": ((e,), None),
        "attn_norm": ((L, e), None),
        "mlp_norm": ((L, e), None),
        **attention_shapes(c, "full", n["full"]),
    }
    if n["sliding"]:
        shapes.update(attention_shapes(c, "sliding", n["sliding"]))
    if n["dense"]:
        d = n["dense"]
        shapes.update({"w_gate": ((d, e, f), e), "w_up": ((d, e, f), e), "w_down": ((d, f, e), f)})
    if n["sparse"]:
        m = n["sparse"]
        shapes.update({
            "moe_router": ((m, e, router), e),
            "moe_router_bias": ((m, router), BIAS_STD ** -2),
            "moe_w_gate": ((m, held, e, fm), e), "moe_w_up": ((m, held, e, fm), e),
            "moe_w_down": ((m, held, fm, e), fm),
            "moe_shared_gate": ((m, e, fs), e), "moe_shared_up": ((m, e, fs), e),
            "moe_shared_down": ((m, fs, e), fs),
        })
    if not c["tie_word_embeddings"]:
        shapes["unembed"] = ((e, v), e)
    return shapes


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
    family's, leaf for leaf). Norm scales and ``VECTORS`` are left alone. Used
    only by the control of ``correct``."""
    kept = {k: v for k, v in params.items() if k in VECTORS}
    return {**_int8_roundtrip({k: v for k, v in params.items() if k not in kept}), **kept}


# ------------------------------------------- what a step needs: bytes and operations


def attention_params(config: dict, kind: str) -> int:
    """Matmul parameters of one attention layer of ``kind``: the query
    latent's two projections, Wkv_a, the two halves of the up-projection, Wo,
    the gate, and a full layer's indexer (query, key and weight projections)."""
    total = 0
    for name, (shape, fan_in) in attention_shapes(config, kind, 1).items():
        if fan_in is not None and name != "index_k_bias":
            n = 1
            for d in shape:
                n *= d
            total += n
    return total


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def moe_fixed_params(config: dict) -> int:
    """What every token of an expert layer passes through: the router over
    every published expert, its bias, and the shared expert."""
    e, router = config["hidden_size"], router_experts(config)
    return e * router + router + config["n_shared_experts"] * expert_params(config)


def param_count(config: dict) -> int:
    total = 0
    for shape, _ in param_shapes(config).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def moe_needed_bytes(config: dict, layers: int, experts_touched: float, dtype_bytes: int = 2) -> float:
    """Bytes ``layers`` expert-layer runs must read: router and shared expert
    each run, and the weights of the held experts that got a token
    (``experts_touched``: summed over those runs)."""
    return dtype_bytes * (layers * moe_fixed_params(config) + experts_touched * expert_params(config))


def cached_bytes(config: dict, kind: str, dtype_bytes: int = 2) -> int:
    """What attention needs of a cached token in one layer of ``kind``: the
    latent and the shared rotated key (the chip holds the key in a 128-lane
    row; the need is the numbers)."""
    s = kind_sizes(config, kind)
    return (s["rank"] + s["rope"]) * dtype_bytes


def index_key_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """The index key of a cached token in one full layer."""
    return config["index_head_dim"] * dtype_bytes


def held_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """What the program's cache holds of a token over all layers: each key in
    a row of whole lane tiles, the latents, a full layer's index key."""
    n = layer_rows(config)

    def lanes(x):
        return -(-x // LANES) * LANES

    full, sliding = kind_sizes(config, "full"), kind_sizes(config, "sliding")
    return dtype_bytes * (
        n["full"] * (lanes(full["rope"]) + full["rank"] + lanes(config["index_head_dim"]))
        + n["sliding"] * (lanes(sliding["rope"]) + sliding["rank"]))


def selected_positions(config: dict, live: float) -> float:
    """Positions a query with ``live`` positions up to its own attends in a
    full layer."""
    return min(live, config["index_topk"])


def window_positions(config: dict, live: float) -> float:
    return min(live, config["sliding_window_size"])


def decode_attention_bytes(config: dict, live_tokens: float, selected: float,
                           windowed: float) -> dict:
    """Bytes one decode step's attention must read of the cache, all layers of
    a kind, by part: the live index keys (``live_tokens``: summed over the
    step's rows), the selected positions' keys and latents (``selected``:
    summed over rows), the windows' (``windowed``)."""
    n = layer_rows(config)
    return {
        "index": n["full"] * live_tokens * index_key_bytes(config),
        "sparse": n["full"] * selected * cached_bytes(config, "full"),
        "window": n["sliding"] * windowed * cached_bytes(config, "sliding"),
    }


def decode_weight_bytes(config: dict, experts_touched_per_layer: float, dtype_bytes: int = 2) -> float:
    """Weights one decode step must read: attention (with the indexer) and
    norms of every layer, the dense layers' feed-forward, router, shared
    expert and the touched held experts of every expert layer, the final norm
    and the head (of the embedding table a step reads a row a slot)."""
    e, v, f = config["hidden_size"], config["vocab_size"], config["intermediate_size"]
    n = layer_rows(config)
    params = (
        n["full"] * attention_params(config, "full")
        + n["sliding"] * attention_params(config, "sliding")
        + n["all"] * 2 * e + e + v * e + n["dense"] * 3 * e * f
    )
    return dtype_bytes * params + moe_needed_bytes(
        config, n["sparse"], n["sparse"] * experts_touched_per_layer, dtype_bytes)


def decode_step_bytes(config: dict, experts_touched_per_layer: float, live_tokens: float,
                      selected: float, windowed: float) -> float:
    """Everything one decode step must read: the weights once, and of the
    cache what ``decode_attention_bytes`` counts."""
    return decode_weight_bytes(config, experts_touched_per_layer) + sum(
        decode_attention_bytes(config, live_tokens, selected, windowed).values())


def sparse_attention_flops(config: dict, query_tokens: float, attended: float, seen: float) -> float:
    """Operations a full layer's attention of a prompt chunk needs over the
    positions its queries selected, in the cheaper of the two forms, the
    indexer's scores not counted. ``attended``: (query, selected position)
    pairs; ``seen``: cached positions whose keys and values are expanded.
    Absorbed: each query through the key up-projection, then a score (rank +
    rope) and a context (rank) a head and pair. Expanded: each seen position's
    keys and values from its latent, then a score (nope + rope) and a context
    (v) a head and pair."""
    s = kind_sizes(config, "full")
    h, r, nope, rope, v = s["heads"], s["rank"], s["nope"], s["rope"], s["v"]
    absorbed = 2.0 * h * (query_tokens * nope * r + attended * (2 * r + rope))
    expanded = 2.0 * h * (seen * r * (nope + v) + attended * (nope + rope + v))
    return min(absorbed, expanded)
