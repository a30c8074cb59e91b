"""Family ``looped_dense``: pre-norm decoders whose one stack of layers (full
attention over a dense SwiGLU, a norm on each branch's way out as well as in)
runs ``total_ut_steps`` times over a token with the same weights, each pass
keeping keys and values of its own, and whose head reads the pass an exit gate
picks (ByteDance Ouro, ``model_type: ouro``; arXiv 2510.25741), which the
program expresses through ``LlamaConfig.loop_passes`` in
``models/patterned.py``."""

from benchmark import common
from benchmark.families import moe_window_gqa
from benchmark.reference_looped_dense import Reference  # noqa: F401 - part of the family

# Standard deviation of the seeded embedding table: one, as
# ``benchmark/families/moe_latent.py EMBED_STD`` has it. The reason given there
# (rows of a launch that route alike) has no routed expert to bite here; what
# is left of it is that a token's own part should lead the stream in front of
# layer 0 as it does in a trained model: every branch joins the stream under a
# norm of scale one (a row of norm 45 at 2,048), so a fan-in-scaled row of
# norm 1 would be drowned by the first branch and every position would enter
# pass 1 as nearly the same vector.
EMBED_STD = 1.0


def passes(config: dict) -> int:
    return int(config["total_ut_steps"])


def model_kwargs(config: dict) -> dict:
    """The published (Hugging Face) keys of a configuration file as the
    program's ``LlamaConfig`` fields. Widths are read, never set here."""
    c = config
    n = c["num_hidden_layers"]
    common.require(
        c["rope_scaling"] is None and not c["use_sliding_window"] and c["sliding_window"] is None
        and c["hidden_act"] == "silu" and set(c["layer_types"]) == {"full_attention"}
        and len(c["layer_types"]) == n,
        "models/patterned.py loop_passes: no rope scaling, no window, SwiGLU, every layer full "
        "attention")
    return dict(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        n_layers=n,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_width=c["head_dim"],
        d_ff=c["intermediate_size"],
        rms_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        layer_types=("full",) * n,
        heads_per_layer=(c["num_attention_heads"],) * n,
        mlp_types=("dense",) * n,
        loop_passes=passes(c),
        exit_threshold=float(c["early_exit_threshold"]),
        branch_norm=True,
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
    # that lacks the preset or a field (a commit before PR 57) fails at once
    # ("unknown model_id"), not in every replica's constructor until the
    # health wait runs out
    try:
        resolve_llama_config(model, EngineConfig(**run["engine"]))
    except (TypeError, ValueError) as e:
        raise common.BenchFailure(f"the program cannot build this family's model: {e}") from e
    return model


# ------------------------------------------------------------------ weights


def param_shapes(config: dict) -> dict:
    """name -> (shape, fan_in, or None for a leaf that is not drawn: a norm
    scale at one, the gate's bias at zero): drawn normal with standard
    deviation ``fan_in ** -0.5`` (the size contracted away; the embedding
    table's entry is the one that gives ``EMBED_STD``). The tree
    ``models/patterned.py`` takes for layers given by kind: the query and
    output projections under the kind's name, four norms a layer, the exit
    gate a ``Linear(hidden, 1)``."""
    c = config
    e, v, n, f = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"], c["intermediate_size"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    shapes = {
        "embed": ((v, e), EMBED_STD ** -2),
        "final_norm": ((e,), None),
        **{name: ((n, e), None) for name in (
            "attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm")},
        "wq_full": ((n, e, h, hd), e),
        "wk": ((n, e, kv, hd), e),
        "wv": ((n, e, kv, hd), e),
        "wo_full": ((n, h, hd, e), h * hd),
        "w_gate": ((n, e, f), e),
        "w_up": ((n, e, f), e),
        "w_down": ((n, f, e), f),
        "exit_w": ((e,), e),
        "exit_b": ((1,), None),
    }
    if not c["tie_word_embeddings"]:
        shapes["unembed"] = ((e, v), e)
    return shapes


def make_params(seed: int, config: dict, dtype, shardings=None):
    """All leaves in one jitted call, normal with standard deviation
    ``fan_in ** -0.5``, norm scales at one, the gate's bias at zero. Stacked
    leaves are drawn a layer at a time (``lax.map``), so the float32 draw of a
    whole leaf never exists beside the weights."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)
    names = sorted(shapes)

    def make(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shape, fan_in = shapes[name]
            if fan_in is None:
                out[name] = jnp.zeros(shape, dtype) if name == "exit_b" else jnp.ones(shape, dtype)
                continue

            def draw(k, shape=shape[1:], std=fan_in ** -0.5):
                return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

            out[name] = jax.lax.map(draw, jax.random.split(k, shape[0]))
        return out

    if shardings is not None:
        shardings = {name: shardings[name] for name in names}
    return jax.jit(make, out_shardings=shardings)(jax.random.PRNGKey(seed))


def int8_roundtrip(params):
    """Every weight matrix through symmetric int8 and back (``families/
    moe_window_gqa.py int8_roundtrip``: one scale per index of the last axis
    and layer), the gate's weight as the one-column matrix it is (one scale);
    norm scales and the gate's bias (zero: no scale) are left alone. Used only
    by the control of ``correct``."""
    import jax.numpy as jnp

    gate = params["exit_w"].astype(jnp.float32)
    scale = jnp.max(jnp.abs(gate)) / 127.0
    cut = moe_window_gqa.int8_roundtrip(
        {name: w for name, w in params.items() if not name.startswith("exit_")})
    return {**cut, "exit_b": params["exit_b"],
            "exit_w": (jnp.clip(jnp.round(gate / scale), -127, 127) * scale).astype(
                params["exit_w"].dtype)}


def param_count(config: dict) -> int:
    total = 0
    for shape, _ in param_shapes(config).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


# ------------------------------------------- what a step needs: bytes and operations


def layer_matmul_params(config: dict) -> int:
    """Matmul parameters of one layer: q, k, v, o and the three of the SwiGLU."""
    e, hd, f = config["hidden_size"], config["head_dim"], config["intermediate_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return e * h * hd + 2 * e * kv * hd + h * hd * e + 3 * e * f


def layer_params(config: dict) -> int:
    """... and its four norms."""
    return layer_matmul_params(config) + 4 * config["hidden_size"]


def head_params(config: dict) -> int:
    return config["hidden_size"] * config["vocab_size"]


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """Keys and values a token holds in the cache: a row a pass and layer."""
    return (2 * config["num_key_value_heads"] * config["head_dim"] * dtype_bytes
            * config["num_hidden_layers"] * passes(config))


def step_matmul_bytes(config: dict, dtype_bytes: int = 2) -> float:
    """Weights a decode step's matmuls read: every layer's projections and
    feed-forward once a pass, the head once."""
    return dtype_bytes * (
        passes(config) * config["num_hidden_layers"] * layer_matmul_params(config)
        + head_params(config))


def step_weight_bytes(config: dict, dtype_bytes: int = 2) -> float:
    """... with the norms of every pass, the final norm and the gate a pass."""
    e = config["hidden_size"]
    return step_matmul_bytes(config, dtype_bytes) + dtype_bytes * passes(config) * (
        config["num_hidden_layers"] * 4 * e + 2 * e + 1)


def step_needed_bytes(config: dict, live_positions: float, dtype_bytes: int = 2) -> float:
    """Bytes one decode step needs: the layers' weights once a pass and the
    head once (``step_weight_bytes``), and the keys and values of the live
    slots' positions (``live_positions``: summed over the slots) in every row
    of the cache, a row a pass and layer."""
    return step_weight_bytes(config, dtype_bytes) + live_positions * kv_bytes_per_token(
        config, dtype_bytes)
