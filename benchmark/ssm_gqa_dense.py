"""What the readers of family ``ssm_gqa_dense``'s metrics share. The mixers'
scopes are the other state-space family's (``benchmark/ssm_latent_moe.py``:
``ssm_mixer`` inside the attention's three scopes, ``ssm_conv`` and ``ssm_scan``
or ``ssm_step`` inside ``attn_core/ssm_mixer``), and so are its helpers for the
live rows and tokens a step and a final chunk's real tokens. The shares are
computed on the traced window's own counts (``benchmark/window_counts.py``,
through ``benchmark/kda_moe.py on_window``): device time and counts are then
of the same launches. The store of prefixes as
snapshots adds two programs, ``jit_store_snapshot`` (scope ``prefix_store``)
and ``jit_seed_prefix`` with a state (scope ``prefix_seed``), and the counters
``snapshots_stored``, ``snapshots_hit``, ``snapshot_store_bytes`` and
``snapshot_seed_bytes``. Against a program without these scopes, programs or
counters every function returns None."""

from __future__ import annotations

from benchmark import peaks, scopes, ssm_latent_moe, trace
from benchmark.families import ssm_gqa_dense as family
from benchmark.kda_moe import on_window  # noqa: F401 - the readers' wrapper, shared

STORE, SEED = "jit_store_snapshot", "jit_seed_prefix"


def _share(needed_bytes: float, ctx: dict, seconds: float) -> float:
    return 100.0 * needed_bytes / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / seconds


def ssm_step_share(ctx: dict) -> "float | None":
    """The float32 state of the live rows, all mamba layers, once in and once
    out, over a decode step's device time under ``ssm_step``, percent."""
    ms = ssm_latent_moe.under_ms(ctx, "jit_decode_fn", "ssm_step")
    rows = ssm_latent_moe.active_slots_per_step(ctx)
    if not ms or rows is None:
        return None
    return _share(family.ssm_state_bytes(ctx["config"], rows), ctx, 1e-3 * ms)


def decode_matmul_share(ctx: dict) -> "float | None":
    """The weights a decode step reads once, over the step's device time under
    the matmuls' scopes, percent."""
    ms = scopes.per_step_ms(ctx, "jit_decode_fn", scopes.DECODE_MATMULS)
    if not ms:
        return None
    return _share(family.decode_weight_bytes(ctx["config"]), ctx, 1e-3 * ms)


def decode_step_share(ctx: dict) -> "float | None":
    """Weights, the live rows' state and tails, and the live tokens' keys and
    values, over a decode step's device time, percent."""
    step_s = trace.module_mean_s(ctx["trace"], "jit_decode_fn")
    rows = ssm_latent_moe.active_slots_per_step(ctx)
    tokens = ssm_latent_moe.live_tokens_per_step(ctx)
    if step_s is None or rows is None or tokens is None:
        return None
    return _share(family.decode_step_bytes(ctx["config"], rows, tokens), ctx, step_s)


def ssm_scan_share(ctx: dict) -> "float | None":
    """Roofline share of the chunked scan of a mean final prompt chunk at its
    real tokens, all mamba layers, over the chunk's device time under
    ``ssm_scan``, percent."""
    ms = ssm_latent_moe.under_ms(ctx, "jit_chunk_final", "ssm_scan")
    tokens = ssm_latent_moe.mean_final_chunk_tokens(ctx)
    if not ms or not tokens:
        return None
    c, chip = ctx["config"], peaks.peaks(ctx["device_kind"])
    least = family.layer_rows(c)["ssm"] * max(
        family.ssm_scan_flops(c, tokens) / chip["bf16_flops_per_s"],
        family.ssm_scan_bytes(c, tokens, 1) / chip["hbm_bytes_per_s"],
    )
    return 100.0 * least / (1e-3 * ms)


def snapshot_copy_share(ctx: dict) -> "float | None":
    """Bytes the window's stores and seeds must move over the device time of
    their programs, percent. A seed reads an entry (state leaves, keys and
    values at the entry's length) and writes it into a stripe; a store reads
    and writes the keys and values alone (the state leaves of an entry are the
    final chunk's own arrays: nothing is copied)."""
    stored, hit = (scopes.counter(ctx, n) for n in ("snapshots_stored", "snapshots_hit"))
    store_bytes, seed_bytes = (
        scopes.counter(ctx, n) for n in ("snapshot_store_bytes", "snapshot_seed_bytes"))
    state = ssm_latent_moe.state_bytes_per_slot(ctx)
    modules = (ctx.get("trace") or {}).get("modules") or {}
    seconds = sum((modules.get(m) or {}).get("total_s", 0.0) for m in (STORE, SEED))
    if None in (stored, hit, store_bytes, seed_bytes, state) or not seconds or not (stored or hit):
        return None
    moved = 2.0 * (store_bytes - stored * state) + 2.0 * seed_bytes
    return _share(moved, ctx, seconds)


def store_bytes(ctx: dict) -> "int | None":
    """Bytes the prefix store holds at the window's close, where the engine
    counts snapshots at all."""
    if scopes.counter(ctx, "snapshots_stored") is None:
        return None
    return scopes.engine_stats(ctx).get("prefix_cache_bytes")
