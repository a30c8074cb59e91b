"""``program.decode_hbm_share`` with family ``sparse_latent``'s counts, on the
traced window's own: bytes one decode step must move (every layer's attention
weights with the indexer's, layer 0's feed-forward, routers, shared experts and
the held experts that got a token, the head; of the cache the live positions'
index keys, the selected positions' keys and latents and the windows';
``family.decode_step_bytes``) over the chip's peak HBM bandwidth, over
``jit_decode_fn``'s device time, percent."""

from benchmark import sparse_latent

read = sparse_latent.on_window(sparse_latent.decode_step_share)
