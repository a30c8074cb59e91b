"""What the readers of family ``moe_window_gqa``'s metrics share: time under
an inner scope, and the engine's routing and window counters.

``benchmark/scopes.py`` books an operation to the outermost known scope on
its path. The program names the new work *inside* known scopes
(``attn_core/window``, ``attn_core/global``, ``moe_ffn/router``,
``moe_ffn/experts``, ``moe_ffn/shared_expert``, ``attn_out/gate``), so the old
readers and coverage see it unedited and the readers here split it by the
part that follows the outer name. Against a program without these scopes or
counters every function returns None."""

from __future__ import annotations

from benchmark import scopes
from benchmark.families import moe_window_gqa as family


def inner_of(op_name: str, outer: str):
    """The scope part right after ``outer`` on an operation's path, or None."""
    parts = op_name.split("/")
    for i, part in enumerate(parts[:-1]):
        words = scopes._WORDS.findall(part)
        if words and words[-1] == outer and not part.startswith(("jit(", "pjit(")):
            return parts[i + 1]
    return None


def inner_ms(ctx: dict, module: str, outer: str, inner=None) -> "float | None":
    """Mean device milliseconds of one execution of ``module`` under
    ``outer`` (all of it) or under ``outer/inner``; None where the trace has
    no execution of the module, no scope at all, or nothing under the name."""
    found = scopes.scoped_module_ops(ctx, module)
    if found is None:
        return None
    n, ops = found
    total = sum(
        end - start for start, end, _, op_name in ops
        if scopes.scope_of(op_name) == outer and (inner is None or inner_of(op_name, outer) == inner)
    )
    return 1e3 * total / n if total else None


PROGRAMS = ("decode", "chunk_mid", "chunk_final")


def routing(ctx: dict, *programs: str) -> "dict | None":
    """The engine's routing counters summed over the named programs
    (``decode``, ``chunk_mid``, ``chunk_final``), cumulative since the engine
    started; None where the engine has none or none of those programs ran."""
    out = {}
    for name in ("moe_layer_steps", "moe_assignments", "moe_experts_touched",
                 "moe_max_expert_load_sum"):
        by_program = scopes.counter(ctx, name)
        if not isinstance(by_program, dict) or not all(p in by_program for p in programs):
            return None
        out[name] = sum(by_program[p] for p in programs)
    return out if out["moe_layer_steps"] else None


def touched_per_layer(ctx: dict, program: str) -> "float | None":
    """Mean number of experts that got a token in one expert-layer run of
    that program."""
    r = routing(ctx, program)
    return None if r is None else r["moe_experts_touched"] / r["moe_layer_steps"]


def kv_tokens_per_step(ctx: dict, which: str) -> "float | None":
    """Mean over decode steps of the positions the active slots' ``which``
    (``global`` or ``window``) layers have to read."""
    tokens, steps = scopes.counter(ctx, "decode_kv_tokens_" + which), scopes.counter(ctx, "decode_steps")
    return tokens / steps if tokens is not None and steps else None


def attention_share(ctx: dict, kind: str, inner: str, which: str) -> "float | None":
    """Bytes of keys and values the ``kind`` layers of one decode step need
    over the chip's bandwidth, over the step's time under
    ``attn_core/<inner>``, percent."""
    from benchmark import peaks

    ms = inner_ms(ctx, "jit_decode_fn", "attn_core", inner)
    tokens = kv_tokens_per_step(ctx, which)
    if not ms or tokens is None:
        return None
    c = ctx["config"]
    needed = tokens * family.layer_rows(c)[kind] * family.kv_bytes_per_token_layer(c)
    return 100.0 * needed / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / (1e-3 * ms)
