"""What the readers of family ``block_moe``'s metrics share. A pool of this
family runs no ``jit_decode_fn``: its step is ``jit_block_step``, a forward of
a block of ``block_length`` positions a slot, whose attention the program names
``attn_core/block`` (the decode kernel with the block's queries folded beside
each key-value head's query heads: a slot's stripe is read once for all of
them), its router and grouped matmuls ``moe_ffn/router`` and
``moe_ffn/experts`` as every expert family's, and its choice of what to unmask
``sampling/confidence`` (the candidates, the softmax's maximum and sum over
the vocabulary for every position of every block) and ``sampling/unmask``.
The engine books a block step's routing counts and the positions it reads
under the names a decode step's have (``moe_*:decode``, ``decode_steps``,
``decode_slot_steps``, ``decode_kv_tokens_global``: the live slots' cached
positions and their blocks), with a step a forward, so the other expert
families' helpers read them. The shares are computed on the traced window's
own counts (``kda_moe.on_window``): device time and counts are then of the
same launches. Against a program without these scopes or counters every
function returns None."""

from __future__ import annotations

from benchmark import moe_window, peaks, scopes, ssm_latent_moe, trace
from benchmark.families import block_moe as family
from benchmark.kda_moe import _share, on_window  # noqa: F401 - the readers' own

STEP = "jit_block_step"


def step_ms(ctx: dict) -> "float | None":
    s = trace.module_mean_s(ctx["trace"], STEP)
    return None if s is None else 1e3 * s


def step_share(ctx: dict) -> "float | None":
    """Bytes a block step needs (``family.step_needed_bytes``: the weights a
    position passes through once, the touched experts of every layer, the
    live slots' keys and values once a slot) over the chip's bandwidth, over
    the step's device time, percent."""
    ms = step_ms(ctx)
    touched = moe_window.touched_per_layer(ctx, "decode")
    positions = ssm_latent_moe.live_tokens_per_step(ctx)
    if ms is None or touched is None or positions is None:
        return None
    return _share(family.step_needed_bytes(ctx["config"], touched, positions), ctx, ms)


def attention_share(ctx: dict) -> "float | None":
    """The live slots' keys and values of every layer, once a slot, over the
    chip's bandwidth, over the step's device time under ``attn_core/block``,
    percent."""
    ms = moe_window.inner_ms(ctx, STEP, "attn_core", "block")
    positions = ssm_latent_moe.live_tokens_per_step(ctx)
    if not ms or positions is None:
        return None
    c = ctx["config"]
    return _share(positions * c["num_hidden_layers"] * family.kv_bytes_per_token_layer(c), ctx, ms)


def moe_step_share(ctx: dict) -> "float | None":
    """The router and the touched experts' banks of every layer of one block
    step over the chip's bandwidth, over the step's device time under
    ``moe_ffn``, percent."""
    ms = moe_window.inner_ms(ctx, STEP, "moe_ffn")
    touched = moe_window.touched_per_layer(ctx, "decode")
    if not ms or touched is None:
        return None
    c = ctx["config"]
    layers = c["num_hidden_layers"]
    return _share(family.moe_needed_bytes(c, layers, layers * touched), ctx, ms)


def moe_prefill_share(ctx: dict) -> "float | None":
    """Roofline share of the expert layers of one final prompt chunk
    (``jit_chunk_final``): the larger of the operations its real tokens need
    over the chip's peak bf16 rate and of the router's and the touched
    experts' bytes over its bandwidth, over the chunk's device time under
    ``moe_ffn``, percent. Every layer runs its experts, the last one too
    (nothing reads it: this family's final chunk has no head; the layers are
    passes of one loop, which the compiler does not cut short)."""
    ms = moe_window.inner_ms(ctx, "jit_chunk_final", "moe_ffn")
    touched = moe_window.touched_per_layer(ctx, "chunk_final")
    tokens = ssm_latent_moe.mean_final_chunk_tokens(ctx)
    if not ms or touched is None or tokens is None:
        return None
    c, chip = ctx["config"], peaks.peaks(ctx["device_kind"])
    layers = c["num_hidden_layers"]
    least_s = max(
        family.moe_needed_flops(c, layers, tokens) / chip["bf16_flops_per_s"],
        family.moe_needed_bytes(c, layers, layers * touched) / chip["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (1e-3 * ms)


def confidence_ms(ctx: dict) -> "float | None":
    return moe_window.inner_ms(ctx, STEP, "sampling", "confidence")


def _forwards(ctx: dict) -> "tuple | None":
    """(denoise forwards, commits, tokens emitted) of live slots."""
    forwards, emitted = scopes.counter(ctx, "block_forwards"), scopes.counter(ctx, "block_tokens_emitted")
    if not isinstance(forwards, dict) or emitted is None or not sum(forwards.values()):
        return None
    return forwards.get("denoise", 0), forwards.get("commit", 0), emitted


def tokens_per_forward(ctx: dict) -> "float | None":
    """Tokens that reached a request over the forwards of live slots: 4 / 5 at
    four denoising steps a block of 4, 4 / 3 at two; the place an acceptance
    rate has where tokens are drafted."""
    f = _forwards(ctx)
    return None if f is None else f[2] / (f[0] + f[1])


def commit_share(ctx: dict) -> "float | None":
    """Of the forwards of live slots, those that only wrote a clean block's
    keys and values, percent."""
    f = _forwards(ctx)
    return None if f is None else 100.0 * f[1] / (f[0] + f[1])
