"""What the readers of family ``sparse_latent``'s metrics share. The program
names an indexed latent layer's work inside scopes the other readers and
coverage know: the indexer's projections ``attn_qkv/attn_index``, its scores
``attn_core/attn_index``, the choice of positions ``attn_core/attn_select``,
the attention over the chosen positions ``attn_core/latent_sparse`` (the
gather of their keys and latents with it), a sliding latent layer's
``attn_core/latent_window`` (the decode kernel between the window's bounds),
the grouped matmuls ``moe_ffn/experts``. Times are read under a name at any
depth (``ssm_latent_moe.under_ms``); the counts are the traced window's own
(``kda_moe.on_window``): ``index_positions_scored`` and
``index_positions_selected`` (a decode launch's sums over its rows, a full
layer), ``decode_kv_tokens_window`` (the rows' positions inside the window),
the routing counters. Against a program without these scopes or counters
every function returns None."""

from __future__ import annotations

from benchmark import moe_window, peaks, scopes, ssm_latent_moe, trace
from benchmark.families import sparse_latent as family
from benchmark.kda_moe import _share, on_window  # noqa: F401 - the readers' own

DECODE = "jit_decode_fn"
FINAL = "jit_chunk_final"


def per_step(ctx: dict, name: str) -> "float | None":
    """Mean over decode steps of counter ``name``."""
    total, steps = scopes.counter(ctx, name), scopes.counter(ctx, "decode_steps")
    return total / steps if total is not None and steps else None


def step_counts(ctx: dict) -> "dict | None":
    """A mean decode step's live positions (what a full layer's indexer
    scores), selected positions and positions inside the windows, each summed
    over the step's rows."""
    out = {name: per_step(ctx, counter) for name, counter in (
        ("live", "index_positions_scored"), ("selected", "index_positions_selected"),
        ("windowed", "decode_kv_tokens_window"))}
    return None if any(v is None for v in out.values()) else out


def decode_step_share(ctx: dict) -> "float | None":
    step_s = trace.module_mean_s(ctx["trace"], DECODE)
    touched = moe_window.touched_per_layer(ctx, "decode")
    n = step_counts(ctx)
    if step_s is None or touched is None or n is None:
        return None
    needed = family.decode_step_bytes(ctx["config"], touched, n["live"], n["selected"], n["windowed"])
    return _share(needed, ctx, 1e3 * step_s)


def attention_part_share(part: str, scope: str):
    """The reader of one part of a decode step's attention (``index``,
    ``sparse`` or ``window``: ``family.decode_attention_bytes``): its bytes
    over the chip's bandwidth, over the step's device time under ``scope``."""
    def read(ctx):
        ms = ssm_latent_moe.under_ms(ctx, DECODE, scope)
        n = step_counts(ctx)
        if not ms or n is None:
            return None
        needed = family.decode_attention_bytes(
            ctx["config"], n["live"], n["selected"], n["windowed"])[part]
        return _share(needed, ctx, ms)
    return read


def select_ms(ctx: dict) -> "float | None":
    return ssm_latent_moe.under_ms(ctx, DECODE, "attn_select")


def moe_decode_share(ctx: dict) -> "float | None":
    """The touched held experts' banks, router and shared expert of one decode
    step over the chip's bandwidth, over the step's device time under
    ``moe_ffn``, percent."""
    ms = moe_window.inner_ms(ctx, DECODE, "moe_ffn")
    touched = moe_window.touched_per_layer(ctx, "decode")
    if not ms or touched is None:
        return None
    c = ctx["config"]
    layers = family.layer_rows(c)["sparse"]
    return _share(family.moe_needed_bytes(c, layers, layers * touched), ctx, ms)


def selected_share(ctx: dict) -> "float | None":
    scored, selected = (scopes.counter(ctx, name) for name in (
        "index_positions_scored", "index_positions_selected"))
    return 100.0 * selected / scored if scored and selected is not None else None


def final_chunk_sparse_share(ctx: dict) -> "float | None":
    """A mean final chunk's attention over the positions its queries selected,
    the full layers: the larger of its operations (the cheaper form; the
    indexer's scores counted apart, under ``attn_index``) over the peak bf16
    rate and of the selected positions' bytes over the peak bandwidth, over
    the chunk's device time under ``latent_sparse``, percent. A query at
    position p selects ``min(p + 1, index_topk)`` positions; the mean chunk's
    ``n`` queries that attend ``a`` pairs in all before selection see ``a / n +
    (n - 1) / 2`` positions at the last."""
    from benchmark import moe_latent

    ms = ssm_latent_moe.under_ms(ctx, FINAL, "latent_sparse")
    chunk = moe_latent.final_chunk(ctx)
    if not ms or chunk is None:
        return None
    c, chip = ctx["config"], peaks.peaks(ctx["device_kind"])
    layers = family.layer_rows(c)["full"]
    n, a = chunk["query_tokens"], chunk["attended"]
    seen = a / n + (n - 1) / 2
    attended = n * family.selected_positions(c, a / n)
    picked = min(seen, attended)  # positions whose keys and values any query needs
    least_s = layers * max(
        family.sparse_attention_flops(c, n, attended, picked) / chip["bf16_flops_per_s"],
        picked * family.cached_bytes(c, "full") / chip["hbm_bytes_per_s"])
    return 100.0 * least_s / (1e-3 * ms)
