"""``program.decode_hbm_share`` with family ``cca_moe``'s counts, on the traced
window's own: bytes one decode step must move (every layer's attention weights
with both convolutions, the live tokens' compressed keys and values, the live
rows' tails read and written, the routers, the held banks that got a token,
norms and scales, and the table as the head; ``family.decode_step_bytes``)
over the chip's peak HBM bandwidth, over ``jit_decode_fn``'s device time,
percent."""

from benchmark import cca_moe

read = cca_moe.on_window(cca_moe.decode_step_share)
