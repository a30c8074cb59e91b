"""What the readers of family ``moe_latent``'s metrics share. The scopes the
program names inside known ones (``attn_core/latent``, ``moe_ffn/router``,
``moe_ffn/experts``, ``moe_ffn/shared_expert``) and the routing counters are
read with the other expert family's helpers (``benchmark/moe_window.py``:
time under an inner scope, experts touched a layer); here are the counters
this family adds. Against a program without them every function returns
None."""

from __future__ import annotations

from benchmark import scopes
from benchmark.families import moe_latent as family


def latent_tokens_per_step(ctx: dict) -> "float | None":
    """Mean over decode steps of the cached tokens the active slots' latent
    layers have to read (the slots' lengths)."""
    tokens, steps = scopes.counter(ctx, "decode_kv_tokens_latent"), scopes.counter(ctx, "decode_steps")
    return tokens / steps if tokens is not None and steps else None


def prefix_token_share(ctx: dict) -> "float | None":
    """Prompt tokens served from the prefix cache over all prompt tokens
    admitted, percent, as ``stats_at_end`` has the two counters."""
    prompt, prefix = (scopes.counter(ctx, k) for k in ("prompt_tokens", "prompt_tokens_from_prefix"))
    if not prompt or prefix is None:
        return None
    return 100.0 * prefix / prompt


def final_chunk(ctx: dict) -> "dict | None":
    """A mean final prompt chunk (``jit_chunk_final``): its query tokens and
    the cached positions its queries attend, summed over them, a layer."""
    tokens = scopes.counter(ctx, "prefill_query_tokens")
    attended = scopes.counter(ctx, "prefill_attended_positions")
    chunks = scopes.counter(ctx, "prefill_chunks")
    if not all(isinstance(x, dict) for x in (tokens, attended, chunks)) or not chunks.get("final"):
        return None
    n = chunks["final"]
    return {"query_tokens": tokens["chunk_final"] / n, "attended": attended["chunk_final"] / n}


def final_chunk_attention_least_s(ctx: dict) -> "float | None":
    """Seconds the chip needs at least for the attention of a
    mean final chunk, all layers: the larger of its operations in the cheaper
    of the two forms over the peak bf16 rate and the bytes of the cached tokens
    its last query sees over the peak HBM bandwidth. The last query of a
    chunk of ``n`` tokens that attends ``a`` pairs in all sees ``a / n + (n -
    1) / 2`` positions."""
    from benchmark import peaks

    chunk = final_chunk(ctx)
    if chunk is None:
        return None
    c = ctx["config"]
    layers = family.layer_rows(c)["all"]
    chip = peaks.peaks(ctx["device_kind"])
    n, a = chunk["query_tokens"], chunk["attended"]
    seen = a / n + (n - 1) / 2
    flops_s = layers * family.attention_flops(c, n, a, seen) / chip["bf16_flops_per_s"]
    bytes_s = layers * seen * family.latent_bytes_per_token_layer(c) / chip["hbm_bytes_per_s"]
    return max(flops_s, bytes_s)
