"""Published peaks of one chip, and the counts that utilisation divides by.

Source for TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
A device that is not in the table is an error, never a default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmark/peaks.py (known: {sorted(PEAKS)})"
        )
    return PEAKS[device_kind]


def layer_matmul_params(config: dict) -> int:
    """Parameters of one decoder layer that take part in a matmul."""
    e, f = config["hidden_size"], config["intermediate_size"]
    h, kv, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    return e * h * hd + 2 * e * kv * hd + h * hd * e + 3 * e * f


def param_count(config: dict) -> int:
    e, v, layers = config["hidden_size"], config["vocab_size"], config["num_hidden_layers"]
    head = 0 if config["tie_word_embeddings"] else v * e
    return v * e + head + layers * (layer_matmul_params(config) + 2 * e) + e


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Operations the forward and backward passes need for one token.

    Matmuls: 2 per parameter forward, twice that backward, over the layers
    and the output head (the embedding lookup is a gather and counts none).
    Attention: scores and the weighted sum are 2 * 2 * head_width * heads
    operations per query-key pair; causal attention needs on average
    (seq_len + 1) / 2 keys per query and is counted once, so no masked-out
    pair is counted. Backward is twice forward. Recomputation is not
    counted."""
    e, v, layers = config["hidden_size"], config["vocab_size"], config["num_hidden_layers"]
    matmul = 2.0 * (layers * layer_matmul_params(config) + v * e)
    attn = layers * 4.0 * config["num_attention_heads"] * config["head_dim"] * (seq_len + 1) / 2.0
    return 3.0 * (matmul + attn)


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * config["head_dim"] * dtype_bytes)


def decode_step_bytes(config: dict, live_tokens: float, dtype_bytes: int = 2) -> float:
    """Bytes one decode step must read: every weight a token passes through
    once (all but the embedding table, of which a step reads a row per slot),
    and the keys and values of the tokens that are live in the active slots.
    Not the whole stripe: dead stripe is what the program reads today, not
    what the step needs."""
    e, v, layers = config["hidden_size"], config["vocab_size"], config["num_hidden_layers"]
    weights = (layers * (layer_matmul_params(config) + 2 * e) + e + v * e) * dtype_bytes
    return weights + live_tokens * kv_bytes_per_token(config, dtype_bytes)
