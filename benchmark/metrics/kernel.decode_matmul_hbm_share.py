"""Bytes of the weights a decode step multiplies by (every layer's
projections and feed-forward, and the output head, once each) over the chip's
peak HBM bandwidth, over the device time of a decode step under the scopes
``attn_qkv``, ``attn_out``, ``ffn`` / ``moe_ffn`` and ``lm_head``. Those
matmuls have 32 rows: they are bound by reading the weights."""

from benchmark import peaks, scopes


def read(ctx):
    ms = scopes.per_step_ms(ctx, "jit_decode_fn", scopes.DECODE_MATMULS)
    if not ms:
        return None
    c = ctx["config"]
    weights = 2 * (c["num_hidden_layers"] * peaks.layer_matmul_params(c)
                   + c["vocab_size"] * c["hidden_size"])
    return 100.0 * weights / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / (1e-3 * ms)
