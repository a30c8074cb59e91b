"""What the readers of family ``looped_dense``'s metrics share. A decode step
of this family (``jit_decode_fn``) walks the one stack of layers
``total_ut_steps`` times: the layers' weights are read once a pass, the head
once, and attention reads a cache with a row for every pass and layer. The
program names the end of a pass (the final norm, the exit gate, the choice of
the pass the head reads) ``loop_exit`` inside ``norm``, and counts what its
programs hand out (``loop_forwards``, ``loop_stack_passes``,
``loop_exit_rows`` by pass) beside the counters every stripe-only pool has
(``decode_steps``, ``decode_kv_tokens_global``: the live slots' cached
positions a step). The byte counts are the family module's. The ``.window``
shares are computed on the traced window's own counts (``kda_moe.on_window``):
device time and counts are then of the same launches. Against a program
without these scopes or counters every function returns None."""

from __future__ import annotations

from benchmark import scopes, ssm_latent_moe, trace
from benchmark.families import looped_dense as family
from benchmark.kda_moe import _share, on_window  # noqa: F401 - the readers' own

STEP = "jit_decode_fn"


def step_share(ctx: dict) -> "float | None":
    """Bytes a decode step needs (``family.step_needed_bytes``: the layers'
    weights once a pass, the head once, the live slots' keys and values in
    every row of the cache) over the chip's bandwidth, over the step's device
    time, percent: the share of the whole step."""
    s = trace.module_mean_s(ctx["trace"], STEP)
    live = ssm_latent_moe.live_tokens_per_step(ctx)
    if s is None or live is None:
        return None
    return _share(family.step_needed_bytes(ctx["config"], live), ctx, 1e3 * s)


def matmul_share(ctx: dict) -> "float | None":
    """The projections' and feed-forwards' weights once a pass and the head
    once, over the chip's bandwidth, over the step's device time under
    ``attn_qkv``, ``attn_out``, ``ffn`` and ``lm_head``, percent."""
    ms = scopes.per_step_ms(ctx, STEP, scopes.DECODE_MATMULS)
    return _share(family.step_matmul_bytes(ctx["config"]), ctx, ms) if ms else None


def attention_share(ctx: dict) -> "float | None":
    """The live slots' keys and values in every row of the cache (a row a pass
    and layer) over the chip's bandwidth, over the step's device time under
    ``attn_core``, percent: the decode kernel's roofline share at this
    family's row count."""
    ms = scopes.per_step_ms(ctx, STEP, ("attn_core",))
    live = ssm_latent_moe.live_tokens_per_step(ctx)
    if not ms or live is None:
        return None
    return _share(live * family.kv_bytes_per_token(ctx["config"]), ctx, ms)


def passes_per_forward(ctx: dict) -> "float | None":
    """Layer-stack passes the programs ran over the forwards that reported
    them: the model's ``total_ut_steps`` while every pass is run."""
    passes, forwards = (scopes.counter(ctx, name) for name in ("loop_stack_passes", "loop_forwards"))
    return passes / forwards if passes is not None and forwards else None


def kv_bytes_per_token(ctx: dict) -> "float | None":
    """What the engine says a token holds in its pool: a row a pass and layer."""
    pools = scopes.engine_stats(ctx).get("pools") or []
    values = [p.get("kv_bytes_per_token") for p in pools]
    if not values or not values[0] or not scopes.counter(ctx, "loop_forwards"):
        return None  # (a program that runs its stack once counts no such forward)
    return values[0]
