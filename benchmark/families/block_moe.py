"""Family ``block_moe``: pre-norm decoders whose layers are all alike,
grouped-query attention under per-head query and key norms over a feed-forward
of softmax-routed experts alone, that generate by diffusion over blocks: a
block of ``block_length`` positions is forwarded whole, unmasked by confidence
over a few denoise forwards and kept by one more (JetLM SDAR-30B-A3B-Chat,
``model_type: sdar_moe``), which the program expresses through ``models/llama.py
block_step`` beside ``prefill``, and ``models/patterned.py`` behind them."""

from benchmark import common
from benchmark.families.moe_window_gqa import int8_roundtrip  # noqa: F401 - part of the family
from benchmark.reference_block_moe import Reference  # noqa: F401 - part of the family

# Standard deviation of the seeded embedding table: one, as
# ``benchmark/families/moe_latent.py EMBED_STD`` has it and says why. A
# fan-in-scaled row has norm 1 beside a first layer whose attention and expert
# sum add several times that and are nearly the same for every row of a launch
# (attention over a thousand seeded bytes is close to their mean), so the rows
# of a step choose nearly the same experts, the count of touched experts
# follows the seed and ``serve_tok_s`` follows that count. Here it bites
# harder: three quarters of a first denoise forward's rows are the *same*
# token, the mask. With unit rows the token's own part leads the stream, as it
# does in a trained model, and what tells two masked rows apart (their
# positions, through attention) is at least not drowned.
EMBED_STD = 1.0
# Standard deviation of the mask token's own row of that table: the fan-in
# scale, ``hidden ** -0.5`` (a row of norm 1 where a token's is 45). A masked
# position holds no token: in a trained model what its stream carries is what
# attention brings from the context, not the mask's embedding. With a unit
# row every masked row of a launch (half of the 256, over a block's forwards)
# was one and the same vector in front of layer 0 and stayed nearly so through
# the depth: the masked rows of a step chose the same eight experts a layer,
# the tokens they unmasked to were the same few ids (an answer read "t t t
# ..."), so the clean rows collapsed as well, a block step touched 81 to 106
# of the 128 experts by the seed's weights, and ``serve_tok_s`` followed that
# count from seed to seed: 2,520 at 106 touched to 2,788 at 82, a spread of
# 5.4% over six seeds where the cell is admitted under 2% (my chip runs, PR
# 55, calls 1-2; PERF.md section 6). With a small row (``mask_row_std``) a masked position's
# stream is its attention output (norm about 2.3 by the shapes, a direction of
# its own a row: the rotary scores differ by position and the context by
# slot), so masked rows route apart, as rows of a trained model do, every
# bank is read every step and the count no longer follows the seed.


def mask_row_std(config: dict) -> float:
    """Standard deviation of the mask token's row of the embedding table."""
    return config["hidden_size"] ** -0.5


def generation(config: dict) -> dict:
    """The sizes of the generation loop (the file's ``generation`` group: none
    of them is in the published config; ``assumed`` says where each is from)."""
    return config["generation"]


def model_kwargs(config: dict) -> dict:
    """The published (Hugging Face) keys of a configuration file as the
    program's ``LlamaConfig`` fields. Widths are read, never set here."""
    c, g = config, generation(config)
    n = c["num_hidden_layers"]
    common.require(
        not c["attention_bias"] and c["rope_scaling"] is None and not c["use_sliding_window"]
        and c["decoder_sparse_step"] == 1 and not c["mlp_only_layers"] and c["norm_topk_prob"]
        and c["hidden_act"] == "silu",
        "models/patterned.py: no attention bias, rope scaling or window, every layer an expert "
        "layer of SwiGLU experts, the top k renormalised")
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
        qk_norm=True,
        layer_types=("full",) * n,
        heads_per_layer=(c["num_attention_heads"],) * n,
        mlp_types=("sparse",) * n,
        moe_experts=c["num_experts"],
        moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        block_length=g["block_length"],
        mask_token_id=g["mask_token_id"],
        denoise_steps=g["denoise_steps"],
        confidence_threshold=float(g["confidence_threshold"]),
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
    # that lacks the preset or a field (a commit before PR 55) fails at once,
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
    embedding table's entry is the one that gives ``EMBED_STD``). The tree
    ``models/patterned.py`` takes for layers given by kind: the query and
    output projections under the kind's name, the two head norms a layer."""
    c = config
    e, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    fm, n_exp = c["moe_intermediate_size"], c["num_experts"]
    shapes = {
        "embed": ((v, e), EMBED_STD ** -2),
        "final_norm": ((e,), None),
        "attn_norm": ((n, e), None),
        "mlp_norm": ((n, e), None),
        "q_head_norm": ((n, hd), None),
        "k_head_norm": ((n, hd), None),
        "wq_full": ((n, e, h, hd), e),
        "wk": ((n, e, kv, hd), e),
        "wv": ((n, e, kv, hd), e),
        "wo_full": ((n, h, hd, e), h * hd),
        "moe_router": ((n, e, n_exp), e),
        "moe_w_gate": ((n, n_exp, e, fm), e),
        "moe_w_up": ((n, n_exp, e, fm), e),
        "moe_w_down": ((n, n_exp, fm, e), fm),
    }
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
        out["embed"] = out["embed"].at[generation(config)["mask_token_id"]].multiply(
            jnp.asarray(mask_row_std(config) / EMBED_STD, dtype))
        return out

    if shardings is not None:
        shardings = {name: shardings[name] for name in names}
    return jax.jit(make, out_shardings=shardings)(jax.random.PRNGKey(seed))


def param_count(config: dict) -> int:
    total = 0
    for shape, _ in param_shapes(config).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


# ------------------------------------------- what a step needs: bytes and operations


def attention_params(config: dict) -> int:
    """Matmul parameters of one attention layer (q, k, v, o)."""
    e, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return e * h * hd + 2 * e * kv * hd + h * hd * e


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def moe_fixed_params(config: dict) -> int:
    """What every token of an expert layer passes through: the router."""
    return config["hidden_size"] * config["num_experts"]


def moe_needed_bytes(config: dict, layers: int, experts_touched: float, dtype_bytes: int = 2) -> float:
    """Bytes ``layers`` expert-layer runs must read: the router each run, and
    the weights of the experts that got a token (``experts_touched``: summed
    over those runs)."""
    return dtype_bytes * (layers * moe_fixed_params(config) + experts_touched * expert_params(config))


def moe_needed_flops(config: dict, layers: int, tokens: float) -> float:
    """Operations ``layers`` expert-layer runs over ``tokens`` tokens each
    need: the router and ``num_experts_per_tok`` experts a token."""
    per_token = moe_fixed_params(config) + config["num_experts_per_tok"] * expert_params(config)
    return 2.0 * layers * tokens * per_token


def kv_bytes_per_token_layer(config: dict, dtype_bytes: int = 2) -> int:
    return 2 * config["num_key_value_heads"] * config["head_dim"] * dtype_bytes


def step_weight_bytes(config: dict, experts_touched_per_layer: float, dtype_bytes: int = 2) -> float:
    """Weights one block step (a denoise forward or a commit: the same read)
    must fetch: attention and norms of every layer, the router and the
    touched experts of every layer, the final norm and the head (of the
    embedding table a step reads a row a position)."""
    e, v, n, hd = (config["hidden_size"], config["vocab_size"], config["num_hidden_layers"],
                   config["head_dim"])
    params = n * (attention_params(config) + 2 * e + 2 * hd) + e + v * e
    return dtype_bytes * params + moe_needed_bytes(
        config, n, n * experts_touched_per_layer, dtype_bytes)


def step_needed_bytes(config: dict, experts_touched_per_layer: float, live_positions: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one block step needs: its weights (``step_weight_bytes``) and the
    keys and values of the live slots' positions (``live_positions``: summed
    over the slots, each slot's cache and its block), once a slot and layer
    whatever the block's length."""
    return step_weight_bytes(config, experts_touched_per_layer, dtype_bytes) + (
        live_positions * config["num_hidden_layers"] * kv_bytes_per_token_layer(config, dtype_bytes))


def chunk_needed_flops(config: dict, tokens: float, attended: float, with_head: bool) -> float:
    """Operations one prompt chunk of ``tokens`` real tokens needs:
    projections and experts a token and layer, a score and a context a head
    and (query, position) pair (``attended``: the pairs, summed over the
    queries), and the head on one row where the chunk has one (a final chunk
    of this family has none: nothing is sampled from a prompt)."""
    c = config
    n, h, hd = c["num_hidden_layers"], c["num_attention_heads"], c["head_dim"]
    per_token = attention_params(c) + moe_fixed_params(c) + c["num_experts_per_tok"] * expert_params(c)
    head = 2.0 * c["hidden_size"] * c["vocab_size"] if with_head else 0.0
    return n * (2.0 * tokens * per_token + 4.0 * h * hd * attended) + head
