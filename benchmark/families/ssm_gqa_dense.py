"""Family ``ssm_gqa_dense``: pre-norm decoders whose every layer is a mixer
under a dense SwiGLU feed-forward, the mixer a Mamba-2 state-space mixer or
grouped-query attention without rotation by ``layer_types``, under the four
Granite scalars and a tied head (``model_type: granitemoehybrid`` with
``num_local_experts`` 0; IBM Granite-4.0-H-Micro), which the program expresses
through ``models/llama.py``'s entry points and ``models/patterned.py`` behind
them (layer kinds ``ssm`` and ``full`` with ``attn_rope=False``,
``embedding_multiplier``, ``residual_multiplier``, ``attention_multiplier``,
``logits_scaling``). The configuration is the model whole: nothing of it is a
share of a larger deployment."""

import math

from benchmark import common
from benchmark.reference_ssm_gqa_dense import Reference  # noqa: F401 - part of the family

# Mamba-2's seeded vectors (the configuration's ``assumed``): the step's bias
# so that softplus(dt_bias) is log-uniform over TIME_STEP, the decay rate
# A = -exp(A_log) with A uniform over A_RANGE, the skip D one, the
# convolution's bias small
TIME_STEP = (0.001, 0.1)
A_RANGE = (1.0, 16.0)
CONV_BIAS_STD = 0.02
# leaves that are no weight matrix (norm scales apart): the int8 control
# leaves them alone
VECTORS = ("ssm_conv_b", "ssm_dt_bias", "ssm_a_log", "ssm_d")
KINDS = {"mamba": "ssm", "attention": "full"}


def layer_rows(config: dict) -> dict:
    """Layers of each kind: ``ssm`` (mamba), ``full`` (attention), ``all``."""
    types = config["layer_types"]
    return {"ssm": types.count("mamba"), "full": types.count("attention"), "all": len(types)}


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def embed_std(config: dict) -> float:
    """The tied table's entries: ``1 / embedding_multiplier``, so that the
    looked-up rows enter the stream at unit scale, as the other families' do,
    and the 80 branches (0.22 of a unit each) outweigh them. At unit entries
    under the multiplier of 12 the stream was the last token's embedding
    beside branches a sixth its size, and nothing downstream of a layer (the
    keys and values, the state of the next layer) witnessed it in proportion
    (PERF.md section 2, PR 44)."""
    return 1.0 / config["embedding_multiplier"]


def ssm_dims(config: dict) -> dict:
    """Widths of a state-space mixer: ``inner`` (heads x head width: the gate
    and the input), ``bc`` (one of B and C: groups x state), ``conv`` (what
    the convolution runs over: x, B, C), ``proj`` (z, x B C, a step a head)."""
    heads = config["mamba_n_heads"]
    inner, bc = heads * config["mamba_d_head"], config["mamba_n_groups"] * config["mamba_d_state"]
    return {"inner": inner, "bc": bc, "conv": inner + 2 * bc, "proj": 2 * inner + 2 * bc + heads}


def model_kwargs(config: dict) -> dict:
    """The published (Hugging Face) keys of a configuration file as the
    program's ``LlamaConfig`` fields. Widths are read, never set here."""
    c = config
    common.require(
        c["num_local_experts"] == 0 and c["num_experts_per_tok"] == 0
        and c["shared_intermediate_size"] == c["intermediate_size"] and c["hidden_act"] == "silu",
        "models/patterned.py _dense_ffn: no routed experts, the shared width is the one SwiGLU's")
    common.require(
        c["mamba_conv_bias"] and not c["mamba_proj_bias"]
        and c["mamba_n_heads"] * c["mamba_d_head"] == c["mamba_expand"] * c["hidden_size"]
        and c["mamba_n_heads"] % c["mamba_n_groups"] == 0,
        "models/patterned.py _ssm_mix: a convolution bias, no projection bias, an inner width "
        "of expand x hidden, whole groups of heads")
    common.require(
        not c["attention_bias"] and c["position_embedding_type"] == "nope"
        and c["normalization_function"] == "rmsnorm"
        and len(c["layer_types"]) == c["num_hidden_layers"]
        and not set(c["layer_types"]) - set(KINDS),
        "no bias, no position signal, rmsnorm, mamba or attention a layer")
    types = tuple(KINDS[t] for t in c["layer_types"])
    return dict(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        layer_types=types,
        heads_per_layer=tuple(c["num_attention_heads"] if t == "full" else 0 for t in types),
        mlp_types=("dense",) * len(types),
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_width=head_dim(c),
        d_ff=c["shared_intermediate_size"],
        rms_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        attn_rope=False,
        ssm_heads=c["mamba_n_heads"],
        ssm_head_dim=c["mamba_d_head"],
        ssm_state=c["mamba_d_state"],
        ssm_groups=c["mamba_n_groups"],
        ssm_conv=c["mamba_d_conv"],
        ssm_chunk=c["mamba_chunk_size"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
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
    # that lacks the preset or a field (a commit before PR 44) fails at once,
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
    embedding table's entries are those that give ``embed_std``, the
    convolution bias's ``CONV_BIAS_STD``), None a norm scale (ones), a word
    one of Mamba-2's vectors (``make_params``). The tree ``models/patterned.py``
    takes: a mixer's and a feed-forward's norm a layer, stacks of the
    state-space mixers' leaves, of the attention layers' and of the
    feed-forwards'."""
    c = config
    e, v, f = c["hidden_size"], c["vocab_size"], c["shared_intermediate_size"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    n, d = layer_rows(c), ssm_dims(c)
    H, taps = c["mamba_n_heads"], c["mamba_d_conv"]
    L = n["all"]
    shapes = {
        "embed": ((v, e), embed_std(c) ** -2),
        "final_norm": ((e,), None),
        "attn_norm": ((L, e), None),
        "mlp_norm": ((L, e), None),
        "w_gate": ((L, e, f), e), "w_up": ((L, e, f), e), "w_down": ((L, f, e), f),
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
    if not c["tie_word_embeddings"]:
        shapes["unembed"] = ((e, v), e)
    return shapes


def make_params(seed: int, config: dict, dtype, shardings=None):
    """All leaves in one jitted call. Matrices normal with standard deviation
    ``fan_in ** -0.5``, norm scales at one, Mamba-2's vectors as the
    configuration's ``assumed`` says. Stacked leaves are drawn a layer at a
    time (``lax.map``), so the float32 draw of a whole leaf (2.7 GB for the 40
    feed-forwards' ``w_gate``) never exists beside the weights."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)
    names = sorted(shapes)
    lo, hi = math.log(TIME_STEP[0]), math.log(TIME_STEP[1])

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

            def draw(k, shape=shape[1:], std=how ** -0.5):
                return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

            out[name] = jax.lax.map(draw, jax.random.split(k, shape[0])).reshape(shape)
        return out

    if shardings is not None:
        shardings = {name: shardings[name] for name in names}
    return jax.jit(make, out_shardings=shardings)(jax.random.PRNGKey(seed))


def int8_roundtrip(params):
    """Every weight matrix through symmetric int8 and back, one scale per
    index of the last axis and per layer (the convolution's taps are a matrix
    a layer too; the tied table is the head's matrix and the looked-up rows
    at once): the lower precision a later PR would be tempted by. Norm scales
    and Mamba-2's vectors a head are left alone. Used only by the control of
    ``correct``. A leaf at a time, in place."""
    import jax
    import jax.numpy as jnp

    def matrix(w):
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=tuple(range(w.ndim - 1)), keepdims=True) / 127.0
        q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
        return (q.astype(jnp.float32) * scale).astype(w.dtype)

    def leaf(w, stacked):
        return jax.lax.map(matrix, w) if stacked else matrix(w)

    return {
        name: w if "norm" in name or name in VECTORS
        else jax.jit(lambda x, s=w.ndim >= 3: leaf(x, s),
                     out_shardings=w.sharding, donate_argnums=(0,))(w)
        for name, w in params.items()
    }


# ------------------------------------------- what a step needs: bytes and operations


def param_count(config: dict) -> int:
    return sum(math.prod(shape) for shape, _ in param_shapes(config).values())


def ssm_params(config: dict) -> int:
    """Parameters of one state-space mixer (its norm apart): the two
    projections, the convolution and its bias, three values a head, the
    gated norm's scale."""
    d, e = ssm_dims(config), config["hidden_size"]
    return (e * d["proj"] + d["inner"] * e + (config["mamba_d_conv"] + 1) * d["conv"]
            + 3 * config["mamba_n_heads"] + d["inner"])


def attention_params(config: dict) -> int:
    e, hd = config["hidden_size"], head_dim(config)
    return e * hd * (2 * config["num_attention_heads"] + 2 * config["num_key_value_heads"])


def ffn_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["shared_intermediate_size"]


def state_bytes_per_slot(config: dict, dtype_bytes: int = 2) -> int:
    """What a slot holds whatever its length: a float32 state [heads, head
    width, state] and the last ``mamba_d_conv - 1`` inputs of the convolution,
    in the served type, for each mamba layer."""
    c = config
    state = c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] * 4
    tail = (c["mamba_d_conv"] - 1) * ssm_dims(c)["conv"] * dtype_bytes
    return layer_rows(c)["ssm"] * (state + tail)


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """Keys and values of a token: the attention layers alone have them."""
    return (layer_rows(config)["full"] * 2 * config["num_key_value_heads"] * head_dim(config)
            * dtype_bytes)


def ssm_state_bytes(config: dict, rows: float) -> float:
    """Bytes the recurrence of a decode step must move in all mamba layers:
    the float32 state of ``rows`` live rows once in and once out."""
    c = config
    state = c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] * 4
    return 2.0 * rows * layer_rows(c)["ssm"] * state


def decode_weight_bytes(config: dict, dtype_bytes: int = 2) -> float:
    """Weights one decode step must read: every layer's mixer, feed-forward
    and two norms, the final norm and the tied table once (as the head; of
    its rows as an embedding a step reads one a slot)."""
    e, v, n = config["hidden_size"], config["vocab_size"], layer_rows(config)
    params = (n["ssm"] * ssm_params(config) + n["full"] * attention_params(config)
              + n["all"] * (ffn_params(config) + 2 * e) + e + v * e)
    return dtype_bytes * params


def decode_step_bytes(config: dict, rows: float, live_tokens: float, dtype_bytes: int = 2) -> float:
    """Bytes one decode step must move: the weights, the live rows' state
    and convolution tails read and written, and the keys and values of the
    live tokens."""
    return (decode_weight_bytes(config, dtype_bytes)
            + 2.0 * rows * state_bytes_per_slot(config, dtype_bytes)
            + live_tokens * kv_bytes_per_token(config, dtype_bytes))


def ssm_scan_flops(config: dict, tokens: float) -> float:
    """Operations the recurrence of one mamba layer needs over ``tokens`` real
    tokens in its chunked form (chunk ``Q``): inside a chunk a score
    ``C_t . B_s`` a *group* and causal pair (a token sees ``(Q + 1) / 2`` on
    average; one group's scores serve all its heads and are counted once) and
    its weight on ``x_s`` a head, and a token's part of the state a head once
    in (``x (x) B``) and once out (``S C``)."""
    c = config
    H, P, N, G, Q = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                     c["mamba_n_groups"], c["mamba_chunk_size"])
    pairs = (min(Q, tokens) + 1) / 2.0  # a chunk shorter than Q has fewer
    return tokens * 2.0 * (pairs * (G * N + H * P) + 2 * H * P * N)


def ssm_scan_bytes(config: dict, tokens: float, rows: float, dtype_bytes: int = 2) -> float:
    """Bytes the recurrence of one mamba layer must move over ``tokens`` real
    tokens in ``rows`` rows: a token's x, B, C and step in, its y out
    (float32, as the gate takes it), and a row's state read and written once."""
    c = config
    d = ssm_dims(c)
    state = c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] * 4
    return tokens * (d["conv"] * dtype_bytes + c["mamba_n_heads"] * 4 + d["inner"] * 4) + 2.0 * rows * state


def snapshot_bytes(config: dict, kv_positions: int, dtype_bytes: int = 2) -> int:
    """Bytes of one stored prefix: a slot's state and convolution tails, and
    the attention layers' keys and values over ``kv_positions`` (the length
    the store rounds a prompt up to)."""
    return state_bytes_per_slot(config, dtype_bytes) + kv_positions * kv_bytes_per_token(
        config, dtype_bytes)
