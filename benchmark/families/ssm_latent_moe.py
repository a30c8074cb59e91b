"""Family ``ssm_latent_moe``: pre-norm decoders whose blocks are a mixer or a
feed-forward alone, by a pattern of ``M`` (Mamba-2 state-space mixer), ``E``
(sigmoid-routed squared-ReLU experts in a latent, beside a shared expert) and
``*`` (grouped-query attention without rotation) blocks (``model_type:
nemotron_h``; NVIDIA Nemotron-3-Super-120B-A12B), which the program expresses
through ``models/llama.py``'s entry points and ``models/patterned.py`` behind
them (layer kinds ``ssm`` and ``none``, ``moe_latent_dim``,
``moe_experts_held``). A configuration holds one chip's share of a stated
deployment: ``n_routed_experts`` is what the chip holds of the router's
``published.n_routed_experts``, ``vocab_size`` its slice of the vocabulary."""

import math

from benchmark import common
from benchmark.families.moe_latent import BIAS_STD, EMBED_STD
from benchmark.reference_ssm_latent_moe import Reference  # noqa: F401 - part of the family

# Mamba-2's seeded vectors (the configuration's ``assumed``): the step's bias
# so that softplus(dt_bias) is log-uniform over time_step_min .. time_step_max,
# the decay rate A = -exp(A_log) with A uniform over 1 .. 16, the skip D one,
# the convolution's bias small
A_RANGE = (1.0, 16.0)
CONV_BIAS_STD = 0.02
BANKS = ("moe_w_up", "moe_w_down")
# leaves that are no weight matrix (norm scales apart): the int8 control
# leaves them alone
VECTORS = ("moe_router_bias", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log", "ssm_d")


def layer_rows(config: dict) -> dict:
    """Blocks of each kind: ``ssm`` (M), ``sparse`` (E), ``full`` (*), and
    ``mixer`` (M and *: the rows of ``attn_norm``)."""
    pattern = config["hybrid_override_pattern"]
    n = {"ssm": pattern.count("M"), "sparse": pattern.count("E"), "full": pattern.count("*")}
    return dict(n, mixer=n["ssm"] + n["full"], all=len(pattern))


def router_experts(config: dict) -> int:
    """Experts the router scores: the published count, of which
    ``n_routed_experts`` are held here."""
    return config.get("published", config)["n_routed_experts"]


def ssm_dims(config: dict) -> dict:
    """Widths of a state-space mixer: ``inner`` (heads x head width: the gate
    and the input), ``bc`` (one of B and C: groups x state), ``conv`` (what
    the convolution runs over: x, B, C), ``proj`` (z, x B C, a step a head)."""
    heads = config["mamba_num_heads"]
    inner, bc = heads * config["mamba_head_dim"], config["n_groups"] * config["ssm_state_size"]
    return {"inner": inner, "bc": bc, "conv": inner + 2 * bc, "proj": 2 * inner + 2 * bc + heads}


def model_kwargs(config: dict) -> dict:
    """The published (Hugging Face) keys of a configuration file as the
    program's ``LlamaConfig`` fields. Widths are read, never set here."""
    c = config
    common.require(
        c["n_group"] == 1 and c["topk_group"] == 1 and c["norm_topk_prob"]
        and c["mlp_hidden_act"] == "relu2" and c["n_shared_experts"] == 1,
        "parallel/moe.py topk_gates and models/patterned.py _moe_decode_ffn: no group step, "
        "top k renormalised, squared-ReLU experts, one shared expert")
    common.require(
        c["mamba_hidden_act"] == "silu" and c["use_conv_bias"] and not c["mamba_proj_bias"]
        and c["mamba_num_heads"] * c["mamba_head_dim"] == c["expand"] * c["hidden_size"]
        and c["mamba_num_heads"] % c["n_groups"] == 0,
        "models/patterned.py _ssm_mixer: SiLU, a convolution bias, no projection bias, an inner "
        "width of expand x hidden, whole groups of heads")
    common.require(
        not (c["attention_bias"] or c["mlp_bias"] or c["use_bias"]) and c["sliding_window"] is None
        and len(c["hybrid_override_pattern"]) == c["num_hidden_layers"]
        and not set(c["hybrid_override_pattern"]) - set("ME*"),
        "no bias, no window, a pattern of M, E and * blocks, one a layer")
    held, router = c["n_routed_experts"], router_experts(c)
    return dict(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        pattern=c["hybrid_override_pattern"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_width=c["head_dim"],
        d_ff=c["intermediate_size"],
        rms_eps=float(c["norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        attn_rope=False,
        ssm_heads=c["mamba_num_heads"],
        ssm_head_dim=c["mamba_head_dim"],
        ssm_state=c["ssm_state_size"],
        ssm_groups=c["n_groups"],
        ssm_conv=c["conv_kernel"],
        ssm_chunk=c["chunk_size"],
        moe_experts=router,
        moe_experts_held=held if held != router else 0,
        moe_experts_first=int(c.get("run", {}).get("experts_first", 0)),
        moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        moe_shared_d_ff=c["n_shared_experts"] * c["moe_shared_expert_intermediate_size"],
        moe_routed_scale=float(c["routed_scaling_factor"]),
        moe_scoring="sigmoid",
        moe_activation="relu2",
        moe_latent_dim=c["moe_latent_size"],
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
    # that lacks the preset or a field (a commit before PR 35) fails at once,
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
    ``EMBED_STD`` and ``BIAS_STD``, the convolution bias's ``CONV_BIAS_STD``),
    None a norm scale (ones), a word one of Mamba-2's vectors (``make_params``).
    The tree ``models/patterned.py`` takes: stacks of the mixers' norms
    (``attn_norm``) and the feed-forwards' (``mlp_norm``), of the state-space
    mixers' leaves, of the attention blocks' and of the expert blocks'."""
    c = config
    e, v = c["hidden_size"], c["vocab_size"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    n, d = layer_rows(c), ssm_dims(c)
    H, taps = c["mamba_num_heads"], c["conv_kernel"]
    held, router, w = c["n_routed_experts"], router_experts(c), c["moe_latent_size"]
    f, fs = c["moe_intermediate_size"], c["n_shared_experts"] * c["moe_shared_expert_intermediate_size"]
    shapes = {
        "embed": ((v, e), EMBED_STD ** -2),
        "final_norm": ((e,), None),
        "attn_norm": ((n["mixer"], e), None),
        "mlp_norm": ((n["sparse"], e), None),
    }
    if n["full"]:
        a = n["full"]
        shapes.update({
            "wq_full": ((a, e, h, hd), e), "wk": ((a, e, kv, hd), e), "wv": ((a, e, kv, hd), e),
            "wo_full": ((a, h, hd, e), h * hd),
        })
    if n["ssm"]:
        m = n["ssm"]
        shapes.update({
            "ssm_w_in": ((m, e, d["proj"]), e),
            "ssm_conv_w": ((m, taps, d["conv"]), taps),
            "ssm_conv_b": ((m, d["conv"]), CONV_BIAS_STD ** -2),
            "ssm_dt_bias": ((m, H), "dt_bias"),
            "ssm_a_log": ((m, H), "a_log"),
            "ssm_d": ((m, H), "ones"),
            "ssm_norm": ((m, d["inner"]), None),
            "ssm_w_out": ((m, d["inner"], e), d["inner"]),
        })
    if n["sparse"]:
        m = n["sparse"]
        shapes.update({
            "moe_router": ((m, e, router), e),
            "moe_router_bias": ((m, router), BIAS_STD ** -2),
            "moe_latent_down": ((m, e, w), e), "moe_latent_up": ((m, w, e), w),
            "moe_w_up": ((m, held, w, f), w), "moe_w_down": ((m, held, f, w), f),
            "moe_shared_up": ((m, e, fs), e), "moe_shared_down": ((m, fs, e), fs),
        })
    if not c["tie_word_embeddings"]:
        shapes["unembed"] = ((e, v), e)
    return shapes


def make_params(seed: int, config: dict, dtype, shardings=None):
    """All leaves in one jitted call. Matrices normal with standard deviation
    ``fan_in ** -0.5``, norm scales at one, Mamba-2's vectors as the
    configuration's ``assumed`` says. Stacked leaves are drawn a layer at a
    time and expert banks an expert at a time (``lax.map``), so the float32
    draw of a whole leaf (7 GB for five layers of 128 experts) never exists
    beside the weights."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)
    names = sorted(shapes)
    lo, hi = math.log(config["time_step_min"]), math.log(config["time_step_max"])

    def vector(how, k, shape):
        if how == "ones":
            return jnp.ones(shape, jnp.float32)
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
    convolution's taps are a matrix a layer too): the lower precision a later
    PR would be tempted by. Norm scales, the selection bias (a buffer) and
    Mamba-2's vectors a head are left alone. Used only by the control of
    ``correct``. A leaf at a time, in place."""
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


def ssm_params(config: dict) -> int:
    """Parameters of one state-space mixer (its norm apart): the two
    projections, the convolution and its bias, three values a head, the
    grouped norm's scale."""
    d, e = ssm_dims(config), config["hidden_size"]
    return (e * d["proj"] + d["inner"] * e + (config["conv_kernel"] + 1) * d["conv"]
            + 3 * config["mamba_num_heads"] + d["inner"])


def attention_params(config: dict) -> int:
    e, hd = config["hidden_size"], config["head_dim"]
    return e * hd * (2 * config["num_attention_heads"] + 2 * config["num_key_value_heads"])


def expert_params(config: dict) -> int:
    """One routed expert: two matrices between the latent and its width."""
    return 2 * config["moe_latent_size"] * config["moe_intermediate_size"]


def moe_fixed_params(config: dict) -> int:
    """What every token of an expert block passes through: router and its
    bias, the two projections of the latent, the shared expert."""
    e, router = config["hidden_size"], router_experts(config)
    fs = config["n_shared_experts"] * config["moe_shared_expert_intermediate_size"]
    return e * router + router + 2 * e * config["moe_latent_size"] + 2 * e * fs


def state_bytes_per_slot(config: dict, dtype_bytes: int = 2) -> int:
    """What a slot holds whatever its length: a float32 state [heads, head
    width, state] and the last ``conv_kernel - 1`` inputs of the convolution,
    in the served type, for each state-space block."""
    c = config
    state = c["mamba_num_heads"] * c["mamba_head_dim"] * c["ssm_state_size"] * 4
    tail = (c["conv_kernel"] - 1) * ssm_dims(c)["conv"] * dtype_bytes
    return layer_rows(c)["ssm"] * (state + tail)


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """Keys and values of a token: the attention blocks alone have them."""
    return (layer_rows(config)["full"] * 2 * config["num_key_value_heads"] * config["head_dim"]
            * dtype_bytes)


def ssm_decode_bytes(config: dict, slots: int, dtype_bytes: int = 2) -> float:
    """Bytes the state-space blocks of one decode step must move: each
    block's weights once, and every slot's state and convolution tail read
    and written (all slots: the step updates each row of the pool)."""
    n = layer_rows(config)["ssm"]
    return dtype_bytes * n * ssm_params(config) + 2.0 * slots * state_bytes_per_slot(config, dtype_bytes)


def moe_needed_bytes(config: dict, layers: int, experts_touched: float, dtype_bytes: int = 2) -> float:
    """Bytes ``layers`` expert-block runs must read: router, latent
    projections and shared expert each run, and the weights of the held
    experts that got a token (``experts_touched``: summed over those runs)."""
    return dtype_bytes * (layers * moe_fixed_params(config) + experts_touched * expert_params(config))


def moe_needed_flops(config: dict, layers: int, tokens: float, held_assignments: float) -> float:
    """Operations ``layers`` expert-block runs over ``tokens`` real tokens
    need: each token through the router (all the experts it scores), the
    latent's two projections and the shared expert's two matrices, and each
    of the ``held_assignments`` (a run's: a token's choices that fell on the
    experts held here) through its expert's two matrices."""
    c = config
    e, fs = c["hidden_size"], c["n_shared_experts"] * c["moe_shared_expert_intermediate_size"]
    a_token = e * router_experts(c) + 2 * e * c["moe_latent_size"] + 2 * e * fs
    return 2.0 * layers * (tokens * a_token + held_assignments * expert_params(c))


def decode_weight_bytes(config: dict, experts_touched_per_layer: float, dtype_bytes: int = 2) -> float:
    """Weights one decode step must read: the state-space and attention
    blocks and the norms, router, latent projections, shared expert and the
    touched held experts of every expert block, the final norm and the head
    (of the embedding table a step reads a row a slot)."""
    e, v, n = config["hidden_size"], config["vocab_size"], layer_rows(config)
    params = (n["ssm"] * ssm_params(config) + n["full"] * attention_params(config)
              + n["all"] * e + e + v * e)
    return dtype_bytes * params + moe_needed_bytes(
        config, n["sparse"], n["sparse"] * experts_touched_per_layer, dtype_bytes)


def decode_step_bytes(config: dict, slots: int, experts_touched_per_layer: float,
                      live_tokens: float, dtype_bytes: int = 2) -> float:
    """Bytes one decode step must move: the weights, every slot's state read
    and written, and the keys and values of the live tokens."""
    return (decode_weight_bytes(config, experts_touched_per_layer, dtype_bytes)
            + 2.0 * slots * state_bytes_per_slot(config, dtype_bytes)
            + live_tokens * kv_bytes_per_token(config, dtype_bytes))


def ssm_scan_flops(config: dict, tokens: float) -> float:
    """Operations the recurrence of one state-space block needs over
    ``tokens`` real tokens in its chunked form (chunk ``Q``): inside a chunk
    a score ``C_t . B_s`` a group and causal pair (a token sees ``(Q + 1) / 2``
    on average) and its weight on ``x_s`` a head, and a token's part of the
    state a head once in (``x (x) B``) and once out (``S C``)."""
    c = config
    H, P, N, G, Q = (c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"],
                     c["n_groups"], c["chunk_size"])
    pairs = (Q + 1) / 2.0
    return tokens * 2.0 * (pairs * (G * N + H * P) + 2 * H * P * N)


def ssm_scan_bytes(config: dict, tokens: float, rows: float, dtype_bytes: int = 2) -> float:
    """Bytes the recurrence of one state-space block must move over
    ``tokens`` real tokens in ``rows`` rows: a token's x, B, C and step in,
    its y out (float32, as the gate takes it), and a row's state read and
    written once."""
    c = config
    d = ssm_dims(c)
    state = c["mamba_num_heads"] * c["mamba_head_dim"] * c["ssm_state_size"] * 4
    return tokens * (d["conv"] * dtype_bytes + c["mamba_num_heads"] * 4 + d["inner"] * 4) + 2.0 * rows * state
