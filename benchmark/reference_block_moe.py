"""The plain reference of family ``block_moe``: a pre-norm decoder of
grouped-query attention under per-head query and key norms and a feed-forward
of routed experts alone, read under the mask of generation by diffusion over
blocks (JetLM SDAR-30B-A3B-Chat, ``model_type: sdar_moe``). Written from the
equations of ISSUE 55 and the catalog row, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. A layer at a time, no kernel, no
cache, no scan, no sorting of tokens, no batching; nothing from ``ray_tpu`` is
imported.

With x a layer's input, B the block length, H query heads, K key-value heads,
D the head width:

    h  = rmsnorm(x, attn_norm)
    q  = rope(headnorm(h Wq, q_head_norm));  k = rope(headnorm(h Wk, k_head_norm));  v = h Wv
         headnorm: an RMSNorm over each head's D numbers, one learned scale [D]
         for the queries and one for the keys a layer; rope: halves, theta 1e6
    o  = softmax(q k^T / sqrt(D) + mask) v
         mask: query t sees position s iff floor(s / B) <= floor(t / B)
         (causal across blocks, both ways inside one)
    x  = x + o Wo
    h2 = rmsnorm(x, mlp_norm)
    p  = softmax(h2 Wr) in float32; (p_k, i_k) = top_k(p); p_k /= sum(p_k)
    x  = x + sum_k p_k expert_{i_k}(h2),  expert(h) = (silu(h Wg) * (h Wu)) Wd
    logits = rmsnorm(x, final_norm) W_head: at position t, of the token at t
    itself (a masked position predicts its own token; no shift by one)

A *denoise forward* of a block over a prefix of whole blocks is, under that
mask, the last B positions of the forward pass over prefix and block together:
the prefix's keys and values do not depend on what follows them, which is also
why a prompt's whole blocks can be prefilled and kept. So one function serves
both: ``forward`` gives every layer's keys and values and the logits at the
positions asked for, and ``denoise_logits`` is its last block. ``denoise_rows``
is the same forward for several blocks of one sequence at once, each over the
prefix it stood behind: the prefix's keys and values are taken from this
reference's own ``forward`` over the sequence, not computed again.

Memory: the routed sum goes a block of experts at a time (each expert's SwiGLU
over a block of the tokens, weighted by the token's renormalised probability
for it, zero where it was not chosen), attention a block of query rows at a
time, the head a block of the vocabulary at a time, each upcast from the served type where it
is used: the float32 copy of a whole leaf never exists beside a resident
engine (one expert layer is 2.4 GB in float32, the head 1.2 GB).

What the configuration leaves open is assumed as the configuration file's
``assumed`` says: the per-head norms, the rotary layout, no shift of the
logits, and the mask."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 32
QUERY_BLOCK = 256
ROW_BLOCK = 1024
VOCAB_BLOCK = 16384
MOE_LEAVES = ("moe_w_gate", "moe_w_up", "moe_w_down")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta: float):
    """x [T, H, D] rotated at ``positions`` [T], the whole head, by halves."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def project(x, w, positions, *, theta, eps):
    """Normed input, rotated queries [T, H, D] and keys, and values [T, K, D]."""
    h = rmsnorm(x, w["attn_norm"], eps)
    q = rmsnorm(jnp.einsum("te,ehd->thd", h, w["wq"]), w["q_head_norm"], eps)
    k = rmsnorm(jnp.einsum("te,ehd->thd", h, w["wk"]), w["k_head_norm"], eps)
    return rope(q, positions, theta), rope(k, positions, theta), jnp.einsum("te,ehd->thd", h, w["wv"])


def block_mask(q_pos, k_pos, block: int):
    """[Tq, T]: the query at ``q_pos`` sees the position ``k_pos``."""
    return (k_pos[None, :] // block) <= (q_pos[:, None] // block)


def attend(q, keys, values, allowed):
    """q [Tq, H, D] over keys and values [T, K, D] where ``allowed`` [Tq, T]
    -> [Tq, H, D]."""
    groups = q.shape[1] // keys.shape[1]
    k = jnp.repeat(keys, groups, axis=1)
    v = jnp.repeat(values, groups, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v)


def route(x, norm, router, *, top_k, eps):
    """Normed input of the expert layer, and each token's weight for every
    expert [T, E]: its renormalised probability where the expert is one of
    its ``top_k``, zero elsewhere; and the experts chosen [T, k]."""
    h = rmsnorm(x, norm, eps)
    probs = jax.nn.softmax(h @ router, axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / top.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype) * top[..., None]).sum(-2)
    return h, weights, idx


def expert_block(h, gate, up, down, weights):
    """sum over the block's experts of weights[:, n] * expert_n(h); gate, up
    [N, E, F], down [N, F, E], weights [T, N]."""
    act = jax.nn.silu(jnp.einsum("te,nef->ntf", h, gate)) * jnp.einsum("te,nef->ntf", h, up)
    return jnp.einsum("nte,tn->te", jnp.einsum("ntf,nfe->nte", act, down), weights)


class Reference:
    """Holds the jitted pieces for one configuration, on one device."""

    def __init__(self, config: dict, devices=None):
        self.config = c = config
        devices = list(devices or jax.local_devices())
        if len(devices) != 1:
            raise ValueError("this reference runs on one device")
        self.device = devices[0]
        eps = float(c["rms_norm_eps"])
        self.n_layers = c["num_hidden_layers"]
        self.block_length = int(c["generation"]["block_length"])
        self._project = jax.jit(_highest(functools.partial(
            project, theta=float(c["rope_theta"]), eps=eps)))
        self._attend = jax.jit(_highest(attend))
        self._out = jax.jit(_highest(lambda x, attn, wo: x + jnp.einsum("thd,hde->te", attn, wo)))
        self._route = jax.jit(_highest(functools.partial(
            route, top_k=c["num_experts_per_tok"], eps=eps)))
        self._experts = jax.jit(_highest(expert_block))
        self._head = jax.jit(_highest(lambda x, norm, w: rmsnorm(x, norm, eps) @ w))
        self._take = jax.jit(
            lambda leaf, i: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
            .astype(jnp.float32))
        self.experts_a_block = math.gcd(c["num_experts"], EXPERT_BLOCK)
        self._take_experts = jax.jit(
            lambda leaf, i, at: jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False),
                at, self.experts_a_block, 0).astype(jnp.float32))

    def _layer(self, params, l, x, positions, behind=None):
        """x [T, E] after layer ``l``, the layer's keys (rotated) and values
        [T, K, D], and the experts each token chose [T, k]. The rows see each
        other under the block mask; with ``behind`` (keys and values [S, K, D]
        that stand in front of the rows' own, and which of all S + T each row
        sees, [T, S + T]) they see what it says."""
        w = {"attn_norm": self._take(params["attn_norm"], l),
             "q_head_norm": self._take(params["q_head_norm"], l),
             "k_head_norm": self._take(params["k_head_norm"], l),
             "wq": self._take(params["wq_full"], l), "wk": self._take(params["wk"], l),
             "wv": self._take(params["wv"], l)}
        q, keys, values = self._project(x, w, positions)
        del w
        if behind is None:
            seen_k, seen_v = keys, values
            allowed = block_mask(positions, positions, self.block_length)
        else:
            seen_k = jnp.concatenate([behind[0], keys])
            seen_v = jnp.concatenate([behind[1], values])
            allowed = behind[2]
        attn = jnp.concatenate([
            self._attend(q[at:at + QUERY_BLOCK], seen_k, seen_v, allowed[at:at + QUERY_BLOCK])
            for at in range(0, q.shape[0], QUERY_BLOCK)
        ])
        x = self._out(x, attn, self._take(params["wo_full"], l))
        h, weights, chosen = self._route(
            x, self._take(params["mlp_norm"], l), self._take(params["moe_router"], l))
        n = self.experts_a_block
        for at in range(0, self.config["num_experts"], n):
            bank = [self._take_experts(params[name], l, at) for name in MOE_LEAVES]
            x = x + jnp.concatenate([
                self._experts(h[r:r + ROW_BLOCK], *bank, weights[r:r + ROW_BLOCK, at:at + n])
                for r in range(0, h.shape[0], ROW_BLOCK)
            ])
        return x, keys, values, chosen

    def _logits(self, params, rows):
        """The head over ``rows`` [n, E] -> [n, V] NumPy."""
        norm = params["final_norm"].astype(jnp.float32)
        head = params["embed"].T if self.config["tie_word_embeddings"] else params["unembed"]
        return np.concatenate([
            np.asarray(self._head(rows, norm, head[:, at:at + VOCAB_BLOCK].astype(jnp.float32)))
            for at in range(0, head.shape[1], VOCAB_BLOCK)
        ], axis=-1)

    def forward(self, params, tokens, logits_at=None) -> dict:
        """The forward pass over one sequence ``tokens`` [T] (a whole number
        of blocks, or any length: a cut last block sees what is there) under
        the block mask. Returns every layer's keys (rotated) and values ``kv``
        ([L, T, K, D] each, NumPy), the logits at the positions ``logits_at``
        ([n, V] NumPy; none asked, none computed) and each layer's chosen
        experts ``choices`` [L, T, k]."""
        tokens = np.asarray(tokens)
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = params["embed"][tokens].astype(jnp.float32)
        ks, vs, choices = [], [], []
        for l in range(self.n_layers):
            x, keys, values, chosen = self._layer(params, l, x, positions)
            ks.append(np.asarray(keys))
            vs.append(np.asarray(values))
            choices.append(np.asarray(chosen))
        out = {"kv": (np.stack(ks), np.stack(vs)), "choices": np.stack(choices)}
        if logits_at is not None:
            out["logits"] = self._logits(params, x[np.asarray(logits_at)])
        return out

    def denoise_logits(self, params, prefix, block) -> np.ndarray:
        """The logits [B, V] of one denoise forward of ``block`` [B] (mask
        tokens where it is masked) over ``prefix`` (whole blocks)."""
        prefix, block = np.asarray(prefix), np.asarray(block)
        if len(prefix) % self.block_length or len(block) != self.block_length:
            raise ValueError("a prefix of whole blocks and one block")
        at = np.arange(len(prefix), len(prefix) + len(block))
        return self.forward(params, np.concatenate([prefix, block]), logits_at=at)["logits"]

    def denoise_rows(self, params, kv, lengths, blocks) -> np.ndarray:
        """The logits [n, B, V] of n denoise forwards of one sequence: forward
        ``i`` is ``blocks[i]`` [B] (mask tokens where it is masked) at
        positions ``[lengths[i], lengths[i] + B)`` over the sequence's first
        ``lengths[i]`` positions (whole blocks). ``kv`` is what ``forward``
        gave for the sequence, at least as long as the longest of them: a
        prefix's keys and values do not depend on what follows it, so each
        forward reads its prefix out of them and its own block beside it;
        ``denoise_logits`` of the same prefix and block gives the same
        numbers."""
        lengths, blocks = np.asarray(lengths), np.asarray(blocks)
        B = self.block_length
        if (lengths % B).any() or blocks.shape != (len(lengths), B):
            raise ValueError("prefixes of whole blocks and one block each")
        at = np.repeat(lengths, B)  # a row's prefix
        positions = jnp.asarray(at + np.tile(np.arange(B), len(lengths)), jnp.int32)
        own = np.repeat(np.arange(len(lengths)), B)
        before = np.arange(kv[0].shape[1])[None, :] < at[:, None]  # of the sequence's positions
        allowed = jnp.asarray(np.concatenate([before, own[:, None] == own[None, :]], axis=1))
        x = params["embed"][blocks.reshape(-1)].astype(jnp.float32)
        for l in range(self.n_layers):
            x = self._layer(params, l, x, positions,
                            behind=(jnp.asarray(kv[0][l]), jnp.asarray(kv[1][l]), allowed))[0]
        return self._logits(params, x).reshape(len(lengths), B, -1)
