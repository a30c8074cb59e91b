"""What the readers of family ``cca_moe``'s metrics share. The program names
the convolutions' work ``cca_conv`` *inside* ``attn_qkv`` (both convolutions,
the mean, the norm a head, the rotation, the shifted values and the tail's read
and write), the router's ``moe_ffn/router`` (projection, the stream through the
depth, the MLP, the choice and the counts) and the grouped matmuls'
``moe_ffn/experts``; its attention over the stripes is ``attn_core/global``,
the decode kernel's. The routing counters, the live rows and tokens a step and
the chunk programs' means are read with the other expert and state-keeping
families' helpers (``benchmark/moe_window.py``, ``benchmark/ssm_latent_moe.py``,
``benchmark/kda_moe.py``). The decode shares are computed on the traced
window's own counts (``kda_moe.on_window``): device time and counts are then of
the same launches. Against a program without these scopes or counters every
function returns None."""

from __future__ import annotations

from benchmark import moe_window, peaks, scopes, ssm_latent_moe, trace
from benchmark.families import cca_moe as family
from benchmark.kda_moe import _share, chunk_means, on_window  # noqa: F401 - the readers' own

DECODE = "jit_decode_fn"


def decode_step_share(ctx: dict) -> "float | None":
    step_s = trace.module_mean_s(ctx["trace"], DECODE)
    touched = moe_window.touched_per_layer(ctx, "decode")
    rows = ssm_latent_moe.active_slots_per_step(ctx)
    tokens = ssm_latent_moe.live_tokens_per_step(ctx)
    if step_s is None or touched is None or rows is None or tokens is None:
        return None
    return _share(family.decode_step_bytes(ctx["config"], rows, touched, tokens), ctx, 1e3 * step_s)


def moe_decode_share(ctx: dict) -> "float | None":
    """The touched held banks of one decode step over the chip's bandwidth,
    over the step's device time under ``moe_ffn/experts``, percent."""
    ms = moe_window.inner_ms(ctx, DECODE, "moe_ffn", "experts")
    touched = moe_window.touched_per_layer(ctx, "decode")
    if not ms or touched is None:
        return None
    c = ctx["config"]
    return _share(family.bank_bytes(c, c["num_hidden_layers"] * touched), ctx, ms)


def attention_decode_share(ctx: dict) -> "float | None":
    """The live tokens' keys and values of all layers over the chip's
    bandwidth, over the step's device time under ``attn_core``, percent."""
    ms = moe_window.inner_ms(ctx, DECODE, "attn_core")
    tokens = ssm_latent_moe.live_tokens_per_step(ctx)
    if not ms or tokens is None:
        return None
    return _share(tokens * family.kv_bytes_per_token(ctx["config"]), ctx, ms)


def conv_ms(ctx: dict) -> "float | None":
    return ssm_latent_moe.under_ms(ctx, DECODE, "cca_conv")


def router_ms(ctx: dict) -> "float | None":
    if ssm_latent_moe.under_ms(ctx, DECODE, "cca_conv") is None:
        return None  # another family's router: its metric is not this one
    return moe_window.inner_ms(ctx, DECODE, "moe_ffn", "router")


def moe_prefill_share(ctx: dict) -> "float | None":
    """Over the middle chunk's executions in the traced window: the least
    time the held experts' grouped matmuls of a mean launch could take (the
    larger of their operations over the peak bf16 rate and of the touched
    banks' bytes over the peak bandwidth) over its device time under
    ``moe_ffn/experts``, percent."""
    module = "jit_chunk_mid"
    ms = moe_window.inner_ms(ctx, module, "moe_ffn", "experts")
    touched = moe_window.touched_per_layer(ctx, "chunk_mid")
    means = chunk_means(ctx, module)
    held, made = (scopes.counter(ctx, name) for name in ("moe_assignments_held", "moe_assignments"))
    if (not ms or touched is None or means is None or not isinstance(held, dict)
            or not isinstance(made, dict) or not made.get("chunk_mid")):
        return None
    c, chip = ctx["config"], peaks.peaks(ctx["device_kind"])
    layers = c["num_hidden_layers"]
    on_held = means[0] * held.get("chunk_mid", 0) / made["chunk_mid"]
    least_s = max(
        2.0 * layers * on_held * family.expert_params(c) / chip["bf16_flops_per_s"],
        family.bank_bytes(c, layers * touched) / chip["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (1e-3 * ms)
