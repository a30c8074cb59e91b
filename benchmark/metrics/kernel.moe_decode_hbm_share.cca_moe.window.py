"""``kernel.moe_decode_hbm_share`` with family ``cca_moe``'s counts, on the traced
window's own: bytes of the held experts that got a token in one decode step
(the engine's ``moe_experts_touched`` over ``moe_layer_steps`` of the decode
program, which count the held experts alone, times an expert's three matrices;
``family.bank_bytes``) over the chip's peak HBM bandwidth, over the step's
device time under ``moe_ffn/experts`` (the sort and the three grouped
matmuls: the router has a metric of its own), percent."""

from benchmark import cca_moe

read = cca_moe.on_window(cca_moe.moe_decode_share)
