"""``kernel.moe_decode_hbm_share`` with family ``ssm_latent_moe``'s counts:
bytes the expert blocks of one decode step must read (router and its bias,
the latent's two projections and the shared expert of each block, and the
weights of the held experts that got a token: the engine's
``moe_experts_touched`` over ``moe_layer_steps`` of the decode program, which
count the held experts alone) over the chip's peak HBM bandwidth, over the
step's device time under ``moe_ffn``, percent.

Reads low, by a known amount: the time is the traced window's, where a step
has some 63 live rows and touches nearly all 128 held experts a block, while
the touched count is cumulative since the engine started (the harness hands a
reader ``stats_at_end`` alone, so no difference over the window can be
taken), and the probe's and the twice-sent request's steps at two live rows
or one (some 390 of 2,500 steps) pull it down to 84-90 (PERF.md section 5).
With all 128 touched the same time would read 1.3-1.4 times higher."""

from benchmark import moe_window, peaks
from benchmark.families import ssm_latent_moe as family


def read(ctx):
    ms = moe_window.inner_ms(ctx, "jit_decode_fn", "moe_ffn")
    touched = moe_window.touched_per_layer(ctx, "decode")
    if not ms or touched is None:
        return None
    c = ctx["config"]
    layers = family.layer_rows(c)["sparse"]
    needed = family.moe_needed_bytes(c, layers, layers * touched)
    return 100.0 * needed / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / (1e-3 * ms)
