"""Roofline share of the expert layers in one middle prompt chunk
(``jit_chunk_mid``): the larger of the operations the chunk's tokens need
(router, shared expert, 8 experts a token) over the chip's peak bf16 rate and
the bytes of the touched experts' weights over its peak HBM bandwidth, over
the chunk's device time under ``moe_ffn``, percent.

The need is counted over the expert layers a middle chunk has to run
(``family.chunk_mid_expert_layers``): it hands out keys and values only, so
nothing reads the last layer's feed-forward and the compiler drops it.
Counted over all four expert layers of the 5-layer cut, as first written, this
read 104 on a v5e; the chunk's op table shows nine grouped-matmul calls, not
twelve, each reading its bank at 84-95% of the peak (PERF.md section 6, PR
28). Experts touched are a middle chunk's own (``moe_experts_touched`` over
``moe_layer_steps`` of ``chunk_mid``: 249 of 256 a layer), not the mean with
the narrower final chunks."""

from benchmark import moe_window, peaks
from benchmark.families import moe_window_gqa as family


def read(ctx):
    ms = moe_window.inner_ms(ctx, "jit_chunk_mid", "moe_ffn")
    touched = moe_window.touched_per_layer(ctx, "chunk_mid")
    if not ms or touched is None:
        return None
    c = ctx["config"]
    layers = family.chunk_mid_expert_layers(c)
    tokens = c["run"]["engine"].get("prefill_chunk", 256)
    chip = peaks.peaks(ctx["device_kind"])
    least_s = max(
        family.moe_needed_flops(c, layers, tokens) / chip["bf16_flops_per_s"],
        family.moe_needed_bytes(c, layers, layers * touched) / chip["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (1e-3 * ms)
