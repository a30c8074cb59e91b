"""Mamba-2's state-space recurrence in its two forms, and the short causal
convolution in front of it (Dao & Gu 2024, "Transformers are SSMs"; the
published instance here is NVIDIA Nemotron-3-Super's ``M`` blocks).

A head ``h`` of width ``P`` keeps a state ``S`` [P, N] a sequence. With a step
``dt_t > 0``, a head's decay rate ``a < 0``, the token's input ``x_t`` [P] and
its group's ``B_t``, ``C_t`` [N] (head ``h`` reads group ``h // (H / G)``):

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

``ssm_step`` is that line for one token a row (a decode step: all it has to
move is the state, once in and once out). ``ssm_scan`` computes the same over
``T`` tokens in chunks of ``chunk``: inside a chunk every pair (t, s <= t) at
once, ``y_t += exp(cs_t - cs_s) (C_t . B_s) dt_s x_s`` with ``cs`` the running
sum of ``dt a`` (matrix multiplications, no loop over tokens); from chunk to
chunk one state a head, carried by a scan over the ``T / chunk`` chunks. A
token whose ``dt`` is 0 leaves the state as it was and adds nothing to any
later token: that is how a right-padded row stops at its own length.

Plain ``jax.numpy``. On a v5e XLA makes two fusions of the step, the sum for
``y`` (it reads the state) and the update in place (it reads and writes it):
three passes over the state where two would do, at 650-730 GB/s each (PERF.md
section 5, PR 35); the chunked form is batched matrix multiplications. The
state, the decays and the sums over a sequence are float32 whatever type the
weights have: a state sums thousands of steps."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(tail, x, w, b):
    """Depthwise causal convolution of x [B, T, C] behind the ``K - 1``
    inputs that came before it, ``tail`` [B, K - 1, C]; w [K, C] (``w[K - 1]``
    multiplies the token itself), b [C]. Returns (y [B, T, C] float32, the
    inputs in front of and with x [B, K - 1 + T, C]: a caller cuts the next
    tail out of it where its row ends)."""
    T = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w32 = w.astype(jnp.float32)
    y = b.astype(jnp.float32)
    for j in range(w.shape[0]):
        y = y + w32[j] * ext[:, j:j + T].astype(jnp.float32)
    return y, ext


def ssm_step(state, x, dt, a, B, C, D):
    """One token a row. state [b, H, P, N] float32; x [b, H, P]; dt [b, H]
    (after its softplus); a [H] (negative); B, C [b, G, N]; D [H]. Returns
    (y [b, H, P] float32, the new state)."""
    b, H, P, N = state.shape
    G = B.shape[1]
    f32 = jnp.float32
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    # heads by group, so that a group's B and C broadcast over its heads
    s = state.reshape(b, G, H // G, P, N)
    decay = jnp.exp(dt * a.astype(f32)).reshape(b, G, H // G, 1, 1)
    xdt = (x * dt[..., None]).reshape(b, G, H // G, P, 1)
    s = s * decay + xdt * B[:, :, None, None, :]
    y = (s * C[:, :, None, None, :]).sum(axis=-1).reshape(b, H, P)
    return y + D.astype(f32)[None, :, None] * x, s.reshape(b, H, P, N)


def ssm_scan(state, x, dt, a, B, C, D, chunk: int):
    """``T`` tokens a row, in chunks. state [b, H, P, N] float32 (what the
    row's earlier tokens left); x [b, T, H, P]; dt [b, T, H] (after its
    softplus; 0 where the row has no token); a [H]; B, C [b, T, G, N]; D [H].
    Returns (y [b, T, H, P] float32, the state after the row's last token)."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    f32 = jnp.float32
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    Q = min(chunk, T)
    pad = -T % Q
    if pad:  # whole chunks; a padded token has dt 0
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    nc, Hg = (T + pad) // Q, H // G
    x = x.reshape(b, nc, Q, G, Hg, P)
    dt = dt.reshape(b, nc, Q, G, Hg)
    B, C = B.reshape(b, nc, Q, G, N), C.reshape(b, nc, Q, G, N)
    cs = jnp.cumsum(dt * a.astype(f32).reshape(G, Hg), axis=2)  # [b, nc, Q, G, Hg], falling
    xdt = x * dt[..., None]
    # inside a chunk: every pair (t, s <= t)
    seg = cs[:, :, :, None] - cs[:, :, None, :]  # [b, nc, t, s, G, Hg]
    causal = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])[None, None, :, :, None, None]
    pair = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bctsg", C, B)
    y = jnp.einsum("bctsgh,bcsghp->bctghp", pair * cb[..., None], xdt)
    # what a chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cs[:, :, -1:] - cs)
    added = jnp.einsum("bcsgn,bcsghp->bcghpn", B, xdt * to_end[..., None])
    over = jnp.exp(cs[:, :, -1])  # a chunk's whole decay [b, nc, G, Hg]

    def carry(s, inp):
        add, d = inp
        return s * d[..., None, None] + add, s  # hand out the state a chunk starts from

    last, before = jax.lax.scan(
        carry, state.reshape(b, G, Hg, P, N),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(over, 1, 0)),
    )
    before = jnp.moveaxis(before, 0, 1)  # [b, nc, G, Hg, P, N]
    y = y + jnp.einsum("bctgn,bcghpn->bctghp", C, before) * jnp.exp(cs)[..., None]
    y = y + D.astype(f32).reshape(G, Hg)[..., None] * x
    return y.reshape(b, nc * Q, H, P)[:, :T], last.reshape(b, H, P, N)
