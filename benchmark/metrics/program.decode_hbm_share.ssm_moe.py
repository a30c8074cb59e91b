"""Bytes a decode step of an ``ssm_latent_moe`` model must move (the
state-space and attention blocks' weights, norms and the head once; router,
latent projections, shared expert and the touched held experts of each expert
block; the state and convolution tail of every slot that holds a request,
read and written; the keys and values of the live tokens in the one attention
block) over the chip's peak HBM bandwidth, over the device time of a decode
step, percent.

Reads low, by a known amount: the step's time is the traced window's, and
the touched experts, live slots and live tokens a step are means of counters
cumulative since the engine started, which the probe's steps at two live rows
dilute (``kernel.moe_decode_hbm_share.ssm_moe`` says by how much; the harness
hands a reader ``stats_at_end`` alone)."""

from benchmark import moe_window, peaks, ssm_latent_moe, trace
from benchmark.families import ssm_latent_moe as family


def read(ctx):
    step_s = trace.module_mean_s(ctx["trace"], "jit_decode_fn")
    touched = moe_window.touched_per_layer(ctx, "decode")
    slots = ssm_latent_moe.active_slots_per_step(ctx)
    tokens = ssm_latent_moe.live_tokens_per_step(ctx)
    if step_s is None or touched is None or slots is None or tokens is None:
        return None
    needed = family.decode_step_bytes(ctx["config"], slots, touched, tokens)
    return 100.0 * needed / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / step_s
