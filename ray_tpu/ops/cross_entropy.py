"""Fused (blockwise) cross-entropy over a large vocabulary.

The naive loss path materializes fp32 logits ``[B, S, V]`` plus a second
``log_softmax`` tensor of the same size — for B=8, S=2048, V=32k that is
~4 GiB of HBM traffic per step, which dominates small-model train steps.
This implementation never materializes the full logit tensor: tokens are
processed in chunks under ``lax.scan``; each chunk computes its logits
``[C, V]`` in VMEM-sized pieces, reduces them to (logsumexp, label-logit),
and is wrapped in ``jax.checkpoint`` so the backward pass recomputes chunk
logits instead of saving them (dW accumulates across scan iterations).

The reference delegates loss computation entirely to user torch code
(``python/ray/train/torch``); this op exists because a TPU-first trainer
owns its fused loss the way it owns its kernels.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


@jax.named_scope("loss")
def fused_cross_entropy(
    x,
    unembed,
    labels,
    mask=None,
    chunk_size: int = 1024,
):
    """Mean next-token NLL without materializing [B, S, V] logits.

    Args:
      x: final hidden states ``[B, S, E]`` (bf16 ok — matmul accumulates fp32).
      unembed: projection ``[E, V]``.
      labels: int32 ``[B, S]``.
      mask: optional ``[B, S]`` 0/1 weights; mean is over mask sum.
      chunk_size: tokens per scan step (VMEM-friendly; [chunk, V] fp32 live).

    Returns scalar fp32 loss.
    """
    B, S, E = x.shape
    V = unembed.shape[-1]
    n = B * S
    xf = x.reshape(n, E)
    lf = labels.reshape(n)
    mf = (
        mask.reshape(n).astype(jnp.float32)
        if mask is not None
        else jnp.ones((n,), jnp.float32)
    )

    chunk_size = min(chunk_size, n)
    pad = (-n) % chunk_size
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        lf = jnp.pad(lf, (0, pad))
        mf = jnp.pad(mf, (0, pad))
    n_chunks = (n + pad) // chunk_size
    xf = xf.reshape(n_chunks, chunk_size, E)
    lf = lf.reshape(n_chunks, chunk_size)
    mf = mf.reshape(n_chunks, chunk_size)

    w = unembed.astype(x.dtype)

    @jax.checkpoint
    def chunk_nll(xc, lc, mc):
        logits = jnp.einsum(
            "ce,ev->cv", xc, w, preferred_element_type=jnp.float32
        )
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return ((lse - ll) * mc).sum()

    def body(acc, inp):
        xc, lc, mc = inp
        return acc + chunk_nll(xc, lc, mc), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xf, lf, mf))
    denom = jnp.maximum(mf.sum(), 1.0)
    return total / denom
