"""Family ``kda_moe``: pre-norm decoders whose every layer is a mixer and a
sigmoid-routed SwiGLU expert layer with a shared expert, the mixer Kimi Delta
Attention (a gated delta rule with a decay a channel) or, in the layers
``gqa_layers``, grouped-query attention without rotation and with an output
gate a channel (``model_type: solar_open2``; upstage Solar-Open2-250B), which
the program expresses through ``models/llama.py``'s entry points and
``models/patterned.py`` behind them (layer kind ``kda``, ``attn_gate``
``channel``, ``moe_experts_held``). A configuration holds one chip's share of
a stated deployment: ``n_routed_experts`` is what the chip holds of the
router's ``published.n_routed_experts``, ``vocab_size`` its slice of the
vocabulary, ``gqa_layers`` the attention layers among the layers it keeps."""

import math

from benchmark import common
from benchmark.families.moe_latent import BIAS_STD, EMBED_STD
from benchmark.reference_kda_moe import Reference  # noqa: F401 - part of the family

# the seeded vectors of a delta-rule mixer's decay (the configuration's
# ``assumed``): the bias a channel so that softplus(dt_bias) is log-uniform
# over TIME_STEP, the rate A = exp(A_log) a head uniform over A_RANGE, as PR 35
# seeded Mamba-2's
A_RANGE = (1.0, 16.0)
TIME_STEP = (0.001, 0.1)
BANKS = ("moe_w_gate", "moe_w_up", "moe_w_down")
# leaves that are no weight matrix (norm scales apart): the int8 control
# leaves them alone
VECTORS = ("moe_router_bias", "kda_dt_bias", "kda_a_log")


def layer_rows(config: dict) -> dict:
    """Layers of each mixer kind (``kda``, ``full``), with experts
    (``sparse``: all of them) and in all."""
    n, full = config["num_hidden_layers"], len(config["gqa_layers"])
    return {"kda": n - full, "full": full, "sparse": n, "all": n}


def router_experts(config: dict) -> int:
    """Experts the router scores: the published count, of which
    ``n_routed_experts`` are held here."""
    return config.get("published", config)["n_routed_experts"]


def kda_dims(config: dict) -> dict:
    """Widths of a delta-rule mixer: ``inner`` (heads x head width: each of
    query, key and value), ``rank`` (the low rank of the decay and of the
    output gate: the configuration's ``assumed.kda_rank``, the head's width),
    ``conv`` (what the three convolutions run over), ``proj`` (q k v, the two
    low ranks, a writing strength a head)."""
    lin = config["linear_attn_config"]
    heads, rank = lin["num_heads"], lin["head_dim"]
    inner = heads * lin["head_dim"]
    return {"inner": inner, "rank": rank, "conv": 3 * inner, "proj": 3 * inner + 2 * rank + heads}


def model_kwargs(config: dict) -> dict:
    """The published (Hugging Face) keys of a configuration file as the
    program's ``LlamaConfig`` fields. Widths are read, never set here."""
    c, lin = config, config["linear_attn_config"]
    common.require(
        c["norm_topk_prob"] and c["n_shared_experts"] == 1 and c["first_k_dense_replace"] == 0,
        "parallel/moe.py topk_gates and models/patterned.py _moe_decode_ffn: top k renormalised, "
        "one shared expert, experts in every layer")
    common.require(
        not c["use_rope"] and c["use_gqa_gate"] and not c["kda_use_full_proj"]
        and c["kda_allow_neg_eigval"] and lin["num_kv_heads"] is None,
        "models/patterned.py: attention without rotation and with its output gate, the decay "
        "through a low rank, a writing strength in (0, 2), keys and values of the queries' heads")
    common.require(
        set(c["gqa_layers"]) <= set(range(c["num_hidden_layers"])),
        "gqa_layers name the attention layers among num_hidden_layers")
    held, router = c["n_routed_experts"], router_experts(c)
    return dict(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        gqa_layers=tuple(c["gqa_layers"]),
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_width=c["head_dim"],
        d_ff=c["intermediate_size"],
        rms_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        attn_rope=False,
        attn_gate="channel",
        kda_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        moe_experts=router,
        moe_experts_held=held if held != router else 0,
        moe_experts_first=int(c.get("run", {}).get("experts_first", 0)),
        moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        moe_shared_d_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
        moe_routed_scale=float(c["routed_scaling_factor"]),
        moe_scoring="sigmoid",
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
    # that lacks the preset or a field (a commit before PR 42) fails at once,
    # not in every replica's constructor until the health wait runs out
    try:
        resolve_llama_config(model, EngineConfig(**run["engine"]))
    except (TypeError, ValueError) as e:
        raise common.BenchFailure(f"the program cannot build this family's model: {e}") from e
    return model


# ------------------------------------------------------------------ weights


def param_shapes(config: dict) -> dict:
    """name -> (shape, how it is drawn): a number is a fan-in (normal with
    standard deviation ``fan_in ** -0.5``: the size contracted away; the
    embedding table's and the selection bias's entries are those that give
    ``EMBED_STD`` and ``BIAS_STD``), None a norm scale (ones), a word one of
    the decay's vectors (``make_params``). The tree ``models/patterned.py``
    takes: every layer's two norms, stacks of the delta-rule mixers' leaves,
    of the attention layers' and of the expert layers'."""
    c = config
    e, v = c["hidden_size"], c["vocab_size"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    n, d = layer_rows(c), kda_dims(c)
    lin = c["linear_attn_config"]
    held, router, f = c["n_routed_experts"], router_experts(c), c["moe_intermediate_size"]
    fs = c["n_shared_experts"] * f
    L = n["all"]
    shapes = {
        "embed": ((v, e), EMBED_STD ** -2),
        "final_norm": ((e,), None),
        "attn_norm": ((L, e), None),
        "mlp_norm": ((L, e), None),
        "moe_router": ((L, e, router), e),
        "moe_router_bias": ((L, router), BIAS_STD ** -2),
        "moe_w_gate": ((L, held, e, f), e), "moe_w_up": ((L, held, e, f), e),
        "moe_w_down": ((L, held, f, e), f),
        "moe_shared_gate": ((L, e, fs), e), "moe_shared_up": ((L, e, fs), e),
        "moe_shared_down": ((L, fs, e), fs),
    }
    if n["full"]:
        a = n["full"]
        shapes.update({
            "wq_full": ((a, e, h, hd), e), "wk": ((a, e, kv, hd), e), "wv": ((a, e, kv, hd), e),
            "wo_full": ((a, h, hd, e), h * hd), "wg_full": ((a, e, h * hd), e),
        })
    if n["kda"]:
        m, taps = n["kda"], lin["short_conv_kernel_size"]
        shapes.update({
            "kda_w_in": ((m, e, d["proj"]), e),
            "kda_conv_w": ((m, taps, d["conv"]), taps),
            "kda_w_decay": ((m, d["rank"], d["inner"]), d["rank"]),
            "kda_dt_bias": ((m, d["inner"]), "dt_bias"),
            "kda_a_log": ((m, lin["num_heads"]), "a_log"),
            "kda_w_gate": ((m, d["rank"], d["inner"]), d["rank"]),
            "kda_norm": ((m, lin["head_dim"]), None),
            "kda_w_out": ((m, d["inner"], e), d["inner"]),
        })
    if not c["tie_word_embeddings"]:
        shapes["unembed"] = ((e, v), e)
    return shapes


def make_params(seed: int, config: dict, dtype, shardings=None):
    """All leaves in one jitted call. Matrices normal with standard deviation
    ``fan_in ** -0.5``, norm scales at one, the decay's vectors as the
    configuration's ``assumed`` says. Stacked leaves are drawn a layer at a
    time and expert banks an expert at a time (``lax.map``), so the float32
    draw of a whole leaf (3.4 GB for four layers of 40 experts) never exists
    beside the weights."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)
    names = sorted(shapes)
    lo, hi = (math.log(t) for t in TIME_STEP)

    def vector(how, k, shape):
        if how == "a_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, *A_RANGE))
        step = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
        return step + jnp.log(-jnp.expm1(-step))  # softplus's inverse

    def make(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shape, how = shapes[name]
            if how is None:
                out[name] = jnp.ones(shape, dtype)
                continue
            if isinstance(how, str):
                out[name] = vector(how, k, shape).astype(dtype)
                continue
            lead = 2 if name in BANKS else 1
            rows = math.prod(shape[:lead])

            def draw(k, shape=shape[lead:], std=how ** -0.5):
                return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

            out[name] = jax.lax.map(draw, jax.random.split(k, rows)).reshape(shape)
        return out

    if shardings is not None:
        shardings = {name: shardings[name] for name in names}
    return jax.jit(make, out_shardings=shardings)(jax.random.PRNGKey(seed))


def int8_roundtrip(params):
    """Every weight matrix through symmetric int8 and back, one scale per
    index of the last axis, per layer, and per expert in an expert bank (the
    convolutions' taps are a matrix a layer too): the lower precision a later
    PR would be tempted by. Norm scales, the selection bias (a buffer) and the
    decay's vectors are left alone. Used only by the control of ``correct``.
    A leaf at a time, in place."""
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
        if name in BANKS:
            return 2
        return 1 if w.ndim >= 3 else 0

    return {
        name: w if "norm" in name or name in VECTORS
        else jax.jit(lambda x, d=depth_of(name, w): leaf(x, d),
                     out_shardings=w.sharding, donate_argnums=(0,))(w)
        for name, w in params.items()
    }


# ------------------------------------------- what a step needs: bytes and operations


def param_count(config: dict) -> int:
    return sum(math.prod(shape) for shape, _ in param_shapes(config).values())


def kda_params(config: dict) -> int:
    """Parameters of one delta-rule mixer (its layer norm apart): the input
    and output projections, the two low ranks' second halves, the three
    convolutions, the decay's bias a channel and rate a head, the norm a
    head."""
    d, e, lin = kda_dims(config), config["hidden_size"], config["linear_attn_config"]
    return (e * d["proj"] + d["inner"] * e + 2 * d["rank"] * d["inner"]
            + lin["short_conv_kernel_size"] * d["conv"] + d["inner"] + lin["num_heads"]
            + lin["head_dim"])


def attention_params(config: dict) -> int:
    """One attention layer: queries, keys, values, the gate a channel, the
    output projection."""
    e, hd = config["hidden_size"], config["head_dim"]
    return e * hd * (3 * config["num_attention_heads"] + 2 * config["num_key_value_heads"])


def expert_params(config: dict) -> int:
    """One routed expert: three matrices of the hidden size by its width."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def moe_fixed_params(config: dict) -> int:
    """What every token of an expert layer passes through: router and its
    bias, the shared expert."""
    e, router = config["hidden_size"], router_experts(config)
    return e * router + router + config["n_shared_experts"] * expert_params(config)


def whole_model_params(config: dict) -> dict:
    """Parameters of the uncut model (``published``): ``total``, and
    ``active`` a token (every mixer, the router, the shared and the chosen
    experts, table and head)."""
    p = config.get("published", config)
    n = layer_rows(p)
    e, v = p["hidden_size"], p["vocab_size"]
    mixers = n["kda"] * kda_params(p) + n["full"] * attention_params(p) + 2 * n["all"] * e
    fixed = n["all"] * moe_fixed_params(p) + (1 if p["tie_word_embeddings"] else 2) * v * e + e
    return {
        "total": mixers + fixed + n["all"] * p["n_routed_experts"] * expert_params(p),
        "active": mixers + fixed + n["all"] * p["num_experts_per_tok"] * expert_params(p),
    }


def state_bytes_per_slot(config: dict, dtype_bytes: int = 2) -> int:
    """What a slot holds whatever its length: a float32 state [heads, head
    width, head width] and the last ``short_conv_kernel_size - 1`` inputs of
    the three convolutions, in the served type, for each delta-rule layer."""
    lin = config["linear_attn_config"]
    state = lin["num_heads"] * lin["head_dim"] ** 2 * 4
    tail = (lin["short_conv_kernel_size"] - 1) * kda_dims(config)["conv"] * dtype_bytes
    return layer_rows(config)["kda"] * (state + tail)


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """Keys and values of a token: the attention layers alone have them."""
    return (layer_rows(config)["full"] * 2 * config["num_key_value_heads"] * config["head_dim"]
            * dtype_bytes)


def kda_decode_bytes(config: dict, rows: float, dtype_bytes: int = 2) -> float:
    """Bytes the delta-rule layers of one decode step must move: each layer's
    weights once, and the state and convolution tails of the ``rows`` slots
    that hold a request, read and written (the step's kernel walks every slot
    of the pool: what it moves of the free ones is no need)."""
    n = layer_rows(config)["kda"]
    return dtype_bytes * n * kda_params(config) + 2.0 * rows * state_bytes_per_slot(config, dtype_bytes)


def attention_decode_bytes(config: dict, live_tokens: float, dtype_bytes: int = 2) -> float:
    """Bytes the attention layers of one decode step must move: projections,
    gate and output projection once, and the keys and values of the live
    tokens."""
    return (dtype_bytes * layer_rows(config)["full"] * attention_params(config)
            + live_tokens * kv_bytes_per_token(config, dtype_bytes))


def moe_needed_bytes(config: dict, layers: int, experts_touched: float, dtype_bytes: int = 2) -> float:
    """Bytes ``layers`` expert-layer runs must read: router and shared expert
    each run, and the weights of the held experts that got a token
    (``experts_touched``: summed over those runs)."""
    return dtype_bytes * (layers * moe_fixed_params(config) + experts_touched * expert_params(config))


def moe_needed_flops(config: dict, layers: int, tokens: float, held_assignments: float) -> float:
    """Operations ``layers`` expert-layer runs over ``tokens`` real tokens
    need: each token through the router (all the experts it scores) and the
    shared expert, and each of the ``held_assignments`` (a run's: a token's
    choices that fell on the experts held here) through its expert."""
    e = config["hidden_size"]
    a_token = e * router_experts(config) + config["n_shared_experts"] * expert_params(config)
    return 2.0 * layers * (tokens * a_token + held_assignments * expert_params(config))


def decode_step_bytes(config: dict, rows: float, experts_touched_per_layer: float,
                      live_tokens: float, dtype_bytes: int = 2) -> float:
    """Bytes one decode step must move: the mixers' weights, the live rows'
    state read and written and the live tokens' keys and values; router,
    shared expert and the touched held experts of every layer; the norms and
    the head (of the embedding table a step reads a row a slot)."""
    e, v, n = config["hidden_size"], config["vocab_size"], layer_rows(config)
    return (kda_decode_bytes(config, rows, dtype_bytes)
            + attention_decode_bytes(config, live_tokens, dtype_bytes)
            + moe_needed_bytes(config, n["sparse"], n["sparse"] * experts_touched_per_layer,
                               dtype_bytes)
            + dtype_bytes * (2 * n["all"] * e + e + v * e))


def kda_scan_flops(config: dict, tokens: float, chunk: int = 64) -> float:
    """Operations the delta rule of one layer needs over ``tokens`` real
    tokens in its chunked form (chunk ``C``): a head and token, a pair weight
    of K products with each of the ``(C - 1) / 2`` earlier tokens of its chunk
    for the solve's matrix and ``(C + 1) / 2`` for the reads', the solve
    applied to the chunk's values and keys (``(C - 1) / 2`` rows of V + K),
    what it writes from the state it met (K V), its output from that state
    (K V) and from the chunk's writes (``(C + 1) / 2`` of V), and its part of
    the state handed on (K V); a multiply and an add each. The solve's own
    inverse is not counted: its size is the algorithm's, not the need's."""
    lin = config["linear_attn_config"]
    H, K = lin["num_heads"], lin["head_dim"]
    V = K
    before, upto = (chunk - 1) / 2.0, (chunk + 1) / 2.0
    a_token = before * K + upto * K + before * (V + K) + 3 * K * V + upto * V
    return 2.0 * tokens * H * a_token


def kda_scan_bytes(config: dict, tokens: float, rows: float, dtype_bytes: int = 2) -> float:
    """Bytes the delta rule of one layer must move over ``tokens`` real
    tokens in ``rows`` rows: a token's q, k, v in (the served type), its decay
    a channel and writing strength in and its output out (float32), and a
    row's state read and written once."""
    lin = config["linear_attn_config"]
    H, K = lin["num_heads"], lin["head_dim"]
    return tokens * (3 * H * K * dtype_bytes + (H * K + H) * 4 + H * K * 4) + 2.0 * rows * H * K * K * 4
