"""What the readers of family ``ssm_latent_moe``'s metrics share. The program
names a state-space mixer's work ``ssm_mixer`` *inside* the three scopes the
other readers and coverage know (``attn_qkv/ssm_mixer`` the input projection,
``attn_core/ssm_mixer`` the convolution and the recurrence with ``ssm_conv``
and ``ssm_scan`` or ``ssm_step`` inside it, ``attn_out/ssm_mixer`` gate, norm
and output projection), and the latent's two projections ``moe_ffn/moe_latent_proj``;
the routing counters are read with the other expert families' helper
(``benchmark/moe_window.py``), and here are the counters this family adds.
Against a program without them every function returns None."""

from __future__ import annotations

from benchmark import moe_window, scopes


def under_ms(ctx: dict, module: str, name: str) -> "float | None":
    """Mean device milliseconds of one execution of ``module`` under the scope
    ``name`` at any depth of an operation's path."""
    found = scopes.scoped_module_ops(ctx, module)
    if found is None:
        return None
    n, ops = found
    total = sum(
        end - start for start, end, _, op_name in ops
        if any(part == name for part in op_name.split("/"))
    )
    return 1e3 * total / n if total else None


def active_slots_per_step(ctx: dict) -> "float | None":
    """Mean over decode steps of the slots that hold a request: the rows whose
    state a step has to move."""
    rows, steps = scopes.counter(ctx, "decode_slot_steps"), scopes.counter(ctx, "decode_steps")
    return rows / steps if rows is not None and steps else None


def live_tokens_per_step(ctx: dict) -> "float | None":
    """Mean over decode steps of the cached tokens the attention block reads."""
    tokens, steps = scopes.counter(ctx, "decode_kv_tokens_global"), scopes.counter(ctx, "decode_steps")
    return tokens / steps if tokens is not None and steps else None


def held_share(ctx: dict) -> "float | None":
    """Assignments that fell on the experts held here over all the router
    made, over every expert-layer run of the three programs, percent."""
    held, r = scopes.counter(ctx, "moe_assignments_held"), moe_window.routing(ctx, *moe_window.PROGRAMS)
    if r is None or not isinstance(held, dict) or not r["moe_assignments"]:
        return None
    return 100.0 * sum(held.get(p, 0) for p in moe_window.PROGRAMS) / r["moe_assignments"]


def state_bytes_per_slot(ctx: dict) -> "int | None":
    """What the engine says a slot holds whatever its length, or None where
    it does not say (a program before the state-space layers) or holds none."""
    pools = scopes.engine_stats(ctx).get("pools") or []
    values = [p.get("state_bytes_per_slot") for p in pools]
    return values[0] if values and values[0] else None


def mean_final_chunk_tokens(ctx: dict) -> "float | None":
    """Real tokens of a mean final prompt chunk (a row of ``jit_chunk_final``)."""
    tokens, chunks = scopes.counter(ctx, "prefill_query_tokens"), scopes.counter(ctx, "prefill_chunks")
    if not isinstance(tokens, dict) or not isinstance(chunks, dict) or not chunks.get("final"):
        return None
    return tokens["chunk_final"] / chunks["final"]


def final_chunk_held_share(ctx: dict) -> "float | None":
    """Of the assignments the router made in the final prompt chunks, the
    share that fell on the experts held here (a fraction). The counters count
    a bucket's padding too; the share is taken to hold for the real tokens."""
    held, made = scopes.counter(ctx, "moe_assignments_held"), scopes.counter(ctx, "moe_assignments")
    if not isinstance(held, dict) or not isinstance(made, dict) or not made.get("chunk_final"):
        return None
    return held.get("chunk_final", 0) / made["chunk_final"]
