"""What the readers of family ``kda_moe``'s metrics share. The program names a
delta-rule mixer's work ``kda_mixer`` *inside* the three scopes the other
readers and coverage know (``attn_qkv/kda_mixer`` the input projection, decay
and writing strength, ``attn_core/kda_mixer`` the convolutions and the rule
itself with ``kda_conv`` and ``kda_scan`` or ``kda_step`` inside it,
``attn_out/kda_mixer`` norm, gate and output projection), so the attention
layer's own time is what lies under the same three scopes and not under
``kda_mixer``. The routing counters, the slots' state and the live rows and
tokens a step are read with the other expert and state-keeping families'
helpers (``benchmark/moe_window.py``, ``benchmark/ssm_latent_moe.py``). The
decode shares are computed on the traced window's own counts
(``benchmark/window_counts.py``: ``on_window``): device time and counts are
then of the same launches. Against a program without these scopes or counters
every function returns None."""

from __future__ import annotations

from benchmark import moe_window, peaks, scopes, ssm_latent_moe, trace, window_counts
from benchmark.families import kda_moe as family

MIXER = "kda_mixer"
ATTENTION_SCOPES = ("attn_qkv", "attn_core", "attn_out")
CHUNK_PROGRAMS = {"jit_chunk_mid": ("chunk_mid", "mid"), "jit_chunk_final": ("chunk_final", "final")}


def on_window(formula):
    """The ``read`` of a metric whose counts are the traced window's own:
    ``formula`` on ``window_counts.windowed(ctx)``, None without the events."""
    def read(ctx):
        own = window_counts.windowed(ctx)
        return None if own is None else formula(own)
    return read


def _share(needed_bytes: float, ctx: dict, ms: float) -> float:
    return 100.0 * needed_bytes / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / (1e-3 * ms)


def attention_ms(ctx: dict, module: str) -> "float | None":
    """Mean device milliseconds of one execution of ``module`` under the
    attention layer's three scopes: what is booked to ``attn_qkv``,
    ``attn_core`` or ``attn_out`` and has no ``kda_mixer`` on its path."""
    found = scopes.scoped_module_ops(ctx, module)
    if found is None:
        return None
    n, ops = found
    total = sum(
        end - start for start, end, _, op_name in ops
        if scopes.scope_of(op_name) in ATTENTION_SCOPES and MIXER not in op_name.split("/")
    )
    return 1e3 * total / n if total else None


def kda_decode_share(ctx: dict) -> "float | None":
    ms = ssm_latent_moe.under_ms(ctx, "jit_decode_fn", MIXER)
    rows = ssm_latent_moe.active_slots_per_step(ctx)
    if not ms or rows is None:
        return None
    return _share(family.kda_decode_bytes(ctx["config"], rows), ctx, ms)


def moe_decode_share(ctx: dict) -> "float | None":
    ms = moe_window.inner_ms(ctx, "jit_decode_fn", "moe_ffn")
    touched = moe_window.touched_per_layer(ctx, "decode")
    if not ms or touched is None:
        return None
    c = ctx["config"]
    layers = family.layer_rows(c)["sparse"]
    return _share(family.moe_needed_bytes(c, layers, layers * touched), ctx, ms)


def attention_decode_share(ctx: dict) -> "float | None":
    ms = attention_ms(ctx, "jit_decode_fn")
    tokens = ssm_latent_moe.live_tokens_per_step(ctx)
    if not ms or tokens is None:
        return None
    return _share(family.attention_decode_bytes(ctx["config"], tokens), ctx, ms)


def decode_step_share(ctx: dict) -> "float | None":
    step_s = trace.module_mean_s(ctx["trace"], "jit_decode_fn")
    touched = moe_window.touched_per_layer(ctx, "decode")
    rows = ssm_latent_moe.active_slots_per_step(ctx)
    tokens = ssm_latent_moe.live_tokens_per_step(ctx)
    if step_s is None or touched is None or rows is None or tokens is None:
        return None
    return _share(family.decode_step_bytes(ctx["config"], rows, touched, tokens), ctx, 1e3 * step_s)


def chunk_means(ctx: dict, module: str) -> "tuple | None":
    """(real tokens, rows) of a mean launch of one of the chunk programs, from
    the engine's ``prefill_query_tokens``, ``prefill_chunks`` (rows) and
    ``prefill_programs`` (launches); None where it has not counted them."""
    by_program, by_kind = CHUNK_PROGRAMS[module]
    tokens, rows, launches = (scopes.counter(ctx, name) for name in (
        "prefill_query_tokens", "prefill_chunks", "prefill_programs"))
    if not all(isinstance(x, dict) for x in (tokens, rows, launches)) or not launches.get(by_kind):
        return None
    return tokens[by_program] / launches[by_kind], rows[by_kind] / launches[by_kind]


def kda_prefill_share(ctx: dict) -> "float | None":
    """Over the chunk programs' executions in the traced window: the least
    time the chunked delta rule of a mean launch could take over its device
    time under ``kda_scan``, percent."""
    c, chip = ctx["config"], peaks.peaks(ctx["device_kind"])
    layers = family.layer_rows(c)["kda"]
    least_s = measured_s = 0.0
    for module in CHUNK_PROGRAMS:
        found = scopes.scoped_module_ops(ctx, module)
        means = chunk_means(ctx, module)
        if found is None or means is None:
            continue
        n = found[0]
        ms = ssm_latent_moe.under_ms(ctx, module, "kda_scan")
        if not ms:
            continue
        tokens, rows = means
        least_s += n * layers * max(
            family.kda_scan_flops(c, tokens) / chip["bf16_flops_per_s"],
            family.kda_scan_bytes(c, tokens, rows) / chip["hbm_bytes_per_s"],
        )
        measured_s += n * 1e-3 * ms
    return 100.0 * least_s / measured_s if measured_s else None


def moe_prefill_share(ctx: dict) -> "float | None":
    ms = moe_window.inner_ms(ctx, "jit_chunk_final", "moe_ffn")
    touched = moe_window.touched_per_layer(ctx, "chunk_final")
    tokens = ssm_latent_moe.mean_final_chunk_tokens(ctx)
    share = ssm_latent_moe.final_chunk_held_share(ctx)
    if not ms or touched is None or tokens is None or share is None:
        return None
    c, chip = ctx["config"], peaks.peaks(ctx["device_kind"])
    layers = family.layer_rows(c)["sparse"]
    held = tokens * c["num_experts_per_tok"] * share
    least_s = max(
        family.moe_needed_flops(c, layers, tokens, held) / chip["bf16_flops_per_s"],
        family.moe_needed_bytes(c, layers, layers * touched) / chip["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (1e-3 * ms)
