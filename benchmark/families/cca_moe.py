"""Family ``cca_moe``: pre-norm decoders whose every layer is compressed
convolutional attention (grouped-query attention in a latent narrower than
the stream, whose queries and keys pass two short causal convolutions and
half of whose value heads are the token before's) under a top-1 SwiGLU expert
layer routed by a small MLP with a stream of its own through the depth, both
branches joined under learned scales, a tied head (``model_type: zaya``;
Zyphra ZAYA1-8B), which the program expresses through ``models/llama.py``'s
entry points and ``models/patterned.py`` behind them (layer kind ``cca``,
``moe_router_hidden``, ``residual_scales``, ``moe_experts_held``). A
configuration holds one chip's share of a stated deployment: ``num_experts``
is what the chip holds of the router's ``published.num_experts``."""

import math

from benchmark import common
from benchmark.families.moe_latent import EMBED_STD
from benchmark.reference_cca_moe import Reference  # noqa: F401 - part of the family

# seeded standard deviation of the convolutions' and the router's MLP's biases
SMALL_BIAS_STD = 0.02
# The router's last matrix is seeded ROUTER_GAIN times wider than fan_in **
# -0.5 (the configuration's ``assumed.router_margin``): at 1 the 16 logits
# have a standard deviation of about 0.6 and the chosen probability is about
# 0.12, so the expert layer adds a tenth of an expert's output and the cell
# would hardly see its banks' values; at 2 the chosen probability is about 0.2
# and stands some 0.05 over the second. Wider is no surer: bfloat16's error in
# the stream swaps the choice of 1.2-1.4% of tokens a layer at any gain (the
# gap between the two largest logits and the noise in them scale together),
# and what a swap moves is the probability at the tie times a whole expert's
# output (k = 1: no other choice dilutes it). Read on the chip at 4 (PR 48,
# call 1): sound 0.014-0.034 from seed to seed against an int8 control at
# 0.042-0.054, too near for a limit between them to hold on fresh seeds.
ROUTER_GAIN = 2.0
# A trained router is balanced (the published model's balancing moves ``beta``
# until it is), and this chip's share of the work is half only then. A seeded
# one is not: behind two GELUs most of the MLP's output is one vector common
# to every token, so the 16 logits differ more by expert than by token, and a
# selection bias as large as the probabilities themselves (Kanana's 0.05,
# where 1/16 is 0.06) picks the same few experts for most tokens: over 12
# seeds the held half got 38-62% of the choices and ``serve_tok_s`` followed
# it (2,302 at 38%, 2,176 at 52%: PERF.md section 6, PR 48). So ``W_3`` is
# seeded orthogonal to the MLP's mean output over BALANCE_PROBES seeded
# Gaussian inputs (what ``rmsnorm(r)`` looks like), which leaves the logits
# their part that differs by token, and ``beta`` is seeded ROUTER_BIAS_STD,
# small against 1/16 and not zero (it takes part in the choice and not in the
# weight): the held half then gets 49-51%.
ROUTER_BIAS_STD = 0.01
BALANCE_PROBES = 1024
# the multiple of the router's vector of the layer before
GAMMA = 0.5
BANKS = ("moe_w_gate", "moe_w_up", "moe_w_down")
# leaves that are no weight matrix (norm scales apart): the int8 control
# leaves them alone
VECTORS = ("moe_router_bias", "moe_router_gamma", "moe_router_b1", "moe_router_b2",
           "moe_router_b3", "cca_conv0_b", "cca_conv1_b", "cca_temp", "attn_scale", "mlp_scale")


def router_experts(config: dict) -> int:
    """Experts the router scores: the published count, of which
    ``num_experts`` are held here."""
    return config.get("published", config)["num_experts"]


def cca_dims(config: dict) -> dict:
    """Widths of an attention layer: ``heads`` (query and key heads side by
    side: what the convolutions run over), ``qk`` (their channels), ``vprev``
    (the value heads that are the token before's), ``tail`` (what a slot
    carries from token to token: the last ``taps - 1`` inputs of each
    convolution and the shifted values)."""
    c = config
    heads, hd = c["num_attention_heads"] + c["num_key_value_heads"], c["head_dim"]
    qk, vprev = heads * hd, c["num_key_value_heads"] // 2 * hd
    return {"heads": heads, "qk": qk, "vprev": vprev,
            "tail": (c["cca_time0"] - 1) * qk + (c["cca_time1"] - 1) * qk + vprev}


def model_kwargs(config: dict) -> dict:
    """The published (Hugging Face) keys of a configuration file as the
    program's ``LlamaConfig`` fields. Widths are read, never set here."""
    c = config
    n = c["num_hidden_layers"]
    common.require(
        c["num_experts_per_tok"] == 1 and c["hidden_act"] == "silu" and not c["attention_bias"]
        and not c["lm_head_bias"] and c["tie_word_embeddings"],
        "models/patterned.py: one SwiGLU expert a token, no bias on a projection or the head, "
        "a tied head")
    common.require(
        set(c["layer_types"][:n]) == {"hybrid"} and c["sliding_window"] is None,
        "every layer kept is a 'hybrid' one (full attention in the compressed latent); no "
        "window")
    rope = c["rope_parameters"]["hybrid"]
    common.require(
        rope["rope_type"] == "default"
        and rope["partial_rotary_factor"] == c["partial_rotary_factor"],
        "plain rotation of the first partial_rotary_factor of each head")
    held, router = c["num_experts"], router_experts(c)
    return dict(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        n_layers=n,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_width=c["head_dim"],
        d_ff=c["moe_intermediate_size"],
        rms_eps=float(c["rms_norm_eps"]),
        rope_theta=float(rope["rope_theta"]),
        rope_partial=float(c["partial_rotary_factor"]),
        tie_embeddings=True,
        cca_taps=(c["cca_time0"], c["cca_time1"]),
        moe_experts=router,
        moe_experts_held=held if held != router else 0,
        moe_experts_first=int(c.get("run", {}).get("experts_first", 0)),
        moe_top_k=1,
        moe_d_ff=c["moe_intermediate_size"],
        moe_router_hidden=c["router_hidden_size"],
        residual_scales=True,
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
    # that lacks the preset or a field (a commit before PR 48) fails at once,
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
    embedding table's, the biases' and the router's last matrix's entries are
    those that give ``EMBED_STD``, ``ROUTER_BIAS_STD``, ``SMALL_BIAS_STD`` and
    ``ROUTER_GAIN``), None ones (norm scales, the scales at a join, a key
    head's temperature), ``"gamma"`` the constant ``GAMMA``. The tree
    ``models/patterned.py`` takes, every leaf stacked over all layers."""
    c = config
    e, v, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    d = cca_dims(c)
    t0, t1 = c["cca_time0"], c["cca_time1"]
    held, router, f, R = (c["num_experts"], router_experts(c), c["moe_intermediate_size"],
                          c["router_hidden_size"])
    small = SMALL_BIAS_STD ** -2
    return {
        "embed": ((v, e), EMBED_STD ** -2),
        "final_norm": ((e,), None),
        "attn_norm": ((L, e), None), "mlp_norm": ((L, e), None),
        "attn_scale": ((L, 2, e), None), "mlp_scale": ((L, 2, e), None),
        "wq_cca": ((L, e, h, hd), e), "wk": ((L, e, kv, hd), e), "wv": ((L, e, kv, hd), e),
        "wo_cca": ((L, h, hd, e), h * hd),
        "cca_conv0_w": ((L, t0, d["qk"]), t0), "cca_conv0_b": ((L, d["qk"]), small),
        "cca_conv1_w": ((L, d["heads"], t1 * hd, hd), t1 * hd),
        "cca_conv1_b": ((L, d["qk"]), small),
        "cca_temp": ((L, kv), None),
        "moe_router_down": ((L, e, R), e), "moe_router_gamma": ((L, R), "gamma"),
        "moe_router_norm": ((L, R), None),
        "moe_router_w1": ((L, R, R), R), "moe_router_b1": ((L, R), small),
        "moe_router_w2": ((L, R, R), R), "moe_router_b2": ((L, R), small),
        "moe_router_w3": ((L, R, router), R / ROUTER_GAIN ** 2),
        "moe_router_b3": ((L, router), small),
        "moe_router_bias": ((L, router), ROUTER_BIAS_STD ** -2),
        "moe_w_gate": ((L, held, e, f), e), "moe_w_up": ((L, held, e, f), e),
        "moe_w_down": ((L, held, f, e), f),
    }


def make_params(seed: int, config: dict, dtype, shardings=None):
    """All leaves in one jitted call. Matrices normal with standard deviation
    ``fan_in ** -0.5``, scales at one, the router's last matrix balanced, as
    the configuration's ``assumed`` says. Stacked leaves are drawn a layer at a time and expert banks an
    expert at a time (``lax.map``), so the float32 draw of a whole leaf (2.7
    GB for 20 layers of 8 experts) never exists beside the weights."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)
    names = sorted(shapes)

    def make(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shape, how = shapes[name]
            if how is None:
                out[name] = jnp.ones(shape, dtype)
                continue
            if how == "gamma":
                out[name] = jnp.full(shape, GAMMA, dtype)
                continue
            if name == "embed" and shape[0] % 128 == 0:
                # 128 rows of the table a draw: a row a draw is 262,272 turns of a loop
                out[name] = jax.lax.map(
                    lambda k, std=how ** -0.5: (
                        jax.random.normal(k, (128, shape[1]), jnp.float32) * std).astype(dtype),
                    jax.random.split(k, shape[0] // 128)).reshape(shape)
                continue
            lead = 2 if name in BANKS else 1
            rows = math.prod(shape[:lead])

            def draw(k, shape=shape[lead:], std=how ** -0.5):
                return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

            out[name] = jax.lax.map(draw, jax.random.split(k, rows)).reshape(shape)
        out["moe_router_w3"] = jax.vmap(balanced)(
            jax.random.split(jax.random.fold_in(key, 1), shapes["moe_router_w3"][0][0]),
            *(out["moe_router_" + n].astype(jnp.float32) for n in ("w1", "b1", "w2", "b2", "w3")),
        ).astype(dtype)
        return out

    def balanced(k, w1, b1, w2, b2, w3):
        """``w3`` without its part along the MLP's mean output: see
        ``ROUTER_BIAS_STD``."""
        z = jax.random.normal(k, (BALANCE_PROBES, w1.shape[0]), jnp.float32)
        mean = jax.nn.gelu(jax.nn.gelu(z @ w1 + b1) @ w2 + b2).mean(axis=0)
        return w3 - jnp.outer(mean, (mean @ w3) / (mean @ mean))

    if shardings is not None:
        shardings = {name: shardings[name] for name in names}
    return jax.jit(make, out_shardings=shardings)(jax.random.PRNGKey(seed))


def int8_roundtrip(params):
    """Every weight matrix through symmetric int8 and back, one scale per
    index of the last axis, per layer, and per expert in an expert bank (the
    convolutions' taps are a matrix a layer too): the lower precision a later
    PR would be tempted by. Norm scales, the scales at a join, the
    temperatures, the biases and ``gamma`` are left alone. Used only by the
    control of ``correct``. A leaf at a time, in place."""
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


def cca_params(config: dict) -> int:
    """One attention layer (its norm and scales apart): the four projections,
    the two convolutions with their biases, a temperature a key head."""
    c, d = config, cca_dims(config)
    e, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return (e * hd * (2 * h + 2 * kv) + (c["cca_time0"] + 1) * d["qk"]
            + d["heads"] * c["cca_time1"] * hd * hd + d["qk"] + kv)


def router_params(config: dict) -> int:
    """One router: the projection, ``gamma``, the norm, three matrices with
    their biases, the selection bias."""
    e, R, E = config["hidden_size"], config["router_hidden_size"], router_experts(config)
    return e * R + 2 * R + 2 * (R * R + R) + R * E + 2 * E


def expert_params(config: dict) -> int:
    """One routed expert: three matrices of the hidden size by its width."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_fixed_params(config: dict) -> int:
    """What every token of a layer passes through: attention, router, the two
    norms and the four scale vectors."""
    return cca_params(config) + router_params(config) + 6 * config["hidden_size"]


def whole_model_params(config: dict) -> dict:
    """Parameters of the uncut model (``published``): ``total``, ``active`` a
    token (attention, router and the one chosen expert of every layer, the
    table once: it is the head too), and ``layer`` / ``layer_active``."""
    p = config.get("published", config)
    n, table = p["num_hidden_layers"], p["vocab_size"] * p["hidden_size"]
    fixed, expert = layer_fixed_params(p), expert_params(p)
    return {
        "layer": fixed + p["num_experts"] * expert, "layer_active": fixed + expert,
        "total": n * (fixed + p["num_experts"] * expert) + table + p["hidden_size"],
        "active": n * (fixed + expert) + table + p["hidden_size"],
    }


def cca_tail_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """What a slot holds whatever its length: the tail of every layer."""
    return config["num_hidden_layers"] * cca_dims(config)["tail"] * dtype_bytes


state_bytes_per_slot = cca_tail_bytes


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """Keys and values of a token, all layers: the compressed heads."""
    return (config["num_hidden_layers"] * 2 * config["num_key_value_heads"] * config["head_dim"]
            * dtype_bytes)


def bank_bytes(config: dict, experts_touched: float, dtype_bytes: int = 2) -> float:
    """Bytes of the held experts that got a token (``experts_touched``:
    summed over the layer runs asked about)."""
    return dtype_bytes * experts_touched * expert_params(config)


def attention_decode_bytes(config: dict, rows: float, live_tokens: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes the attention layers of one decode step must move: their weights
    once, the live tokens' keys and values, the live rows' tails in and out."""
    return (dtype_bytes * config["num_hidden_layers"] * cca_params(config)
            + live_tokens * kv_bytes_per_token(config, dtype_bytes)
            + 2.0 * rows * cca_tail_bytes(config, dtype_bytes))


def decode_step_bytes(config: dict, rows: float, experts_touched_per_layer: float,
                      live_tokens: float, dtype_bytes: int = 2) -> float:
    """Bytes one decode step must move: attention (weights, live keys and
    values, tails), the routers, the touched held banks of every layer, the
    norms and scales, and the table as the head (as an embedding a step reads
    a row a slot)."""
    c = config
    e, L = c["hidden_size"], c["num_hidden_layers"]
    return (attention_decode_bytes(c, rows, live_tokens, dtype_bytes)
            + bank_bytes(c, L * experts_touched_per_layer, dtype_bytes)
            + dtype_bytes * (L * (router_params(c) + 6 * e) + e + c["vocab_size"] * e))


def moe_needed_bytes(config: dict, layers: int, experts_touched: float,
                     dtype_bytes: int = 2) -> float:
    """Bytes ``layers`` expert-layer runs must read: the router each run and
    the weights of the held experts that got a token."""
    return dtype_bytes * layers * router_params(config) + bank_bytes(
        config, experts_touched, dtype_bytes)


def moe_needed_flops(config: dict, layers: int, tokens: float, held_assignments: float) -> float:
    """Operations ``layers`` expert-layer runs over ``tokens`` real tokens
    need: each token through the router, and each of the ``held_assignments``
    (a run's: the tokens whose one choice fell on the experts held here)
    through its expert."""
    return 2.0 * layers * (tokens * router_params(config)
                           + held_assignments * expert_params(config))


def chunk_flops(config: dict, tokens: float, seen: float, held_share: float) -> float:
    """Operations a prompt chunk of ``tokens`` real tokens needs, whose
    queries see ``seen`` key positions on average: every layer's projections
    and convolutions, scores and context, router, and the chosen expert of the
    ``held_share`` of tokens whose choice is held here. No head: a middle
    chunk has none."""
    c = config
    hd, h = c["head_dim"], c["num_attention_heads"]
    a_token = cca_params(c) + router_params(c) + held_share * expert_params(c) + 2 * h * hd * seen
    return 2.0 * c["num_hidden_layers"] * tokens * a_token
