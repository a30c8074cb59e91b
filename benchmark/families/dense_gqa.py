"""Family ``dense_gqa``: pre-norm decoders with grouped-query attention,
rotary positions and a SwiGLU feed-forward (Mistral, Llama), which the
program expresses through ``models/llama.py``."""

from benchmark import common
from benchmark.reference import Reference  # noqa: F401 - part of the family
from benchmark.weights import int8_roundtrip, make_params  # noqa: F401


def model_kwargs(config: dict) -> dict:
    """The published (Hugging Face) keys of a configuration file as the
    program's ``LlamaConfig`` fields. Widths are read, never set here."""
    common.require(
        config["hidden_size"] == config["num_attention_heads"] * config["head_dim"],
        "models/llama.py derives the head width from hidden_size / heads",
    )
    common.require(config.get("sliding_window") is None, "no sliding window in models/llama.py")
    common.require(config.get("hidden_act", "silu") == "silu", "models/llama.py is SwiGLU")
    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
    )


def train_config(config: dict, traffic: dict):
    """The program's model config for a train cell."""
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    run = config["run"]
    return LlamaConfig(
        **model_kwargs(config), max_seq_len=traffic["seq_len"],
        attention=run["attention"], remat=run["remat"], fused_ce=run["fused_ce"],
        dtype=jnp.dtype(run["dtype"]),
    )


def param_shardings(cfg, mesh):
    from ray_tpu.models.llama import param_shardings as shardings

    return shardings(cfg, mesh)


def served_model(config: dict, seed: int):
    """The program's ``ModelConfig`` for a serving cell: every size comes from
    the configuration file; the preset only names the family's code path."""
    from ray_tpu.llm import ModelConfig

    run = config["run"]
    return ModelConfig(
        model_id=run["preset"], tokenizer=run["tokenizer"], seed=seed,
        model_kwargs=model_kwargs(config),
    )
