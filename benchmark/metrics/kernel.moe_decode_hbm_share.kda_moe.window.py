"""``kernel.moe_decode_hbm_share`` with family ``kda_moe``'s counts, on the
traced window's own: bytes the expert layers of one decode step must read
(router and its bias and the shared expert of each layer, and the weights of
the held experts that got a token: the engine's ``moe_experts_touched`` over
``moe_layer_steps`` of the decode program, which count the held experts alone;
``family.moe_needed_bytes``) over the chip's peak HBM bandwidth, over the
step's device time under ``moe_ffn``, percent."""

from benchmark import kda_moe

read = kda_moe.on_window(kda_moe.moe_decode_share)
