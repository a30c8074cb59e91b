"""``kernel.moe_decode_hbm_share`` with family ``sparse_latent``'s counts, on the
traced window's own: bytes the expert layers of one decode step must read
(router over all published experts, the shared expert, the held experts that
got a token) over the chip's peak HBM bandwidth, over the step's device time
under ``moe_ffn``, percent."""

from benchmark import sparse_latent

read = sparse_latent.on_window(sparse_latent.moe_decode_share)
