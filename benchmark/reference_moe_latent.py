"""The plain reference of family ``moe_latent``: a pre-norm decoder with
latent attention (DeepSeek-V3's, without a query latent) and, after a leading
dense layer, sigmoid-routed experts with a selection bias beside shared ones.
Written from the equations of ISSUE 33 and the catalog row of kakaocorp
Kanana-2-30B-A3B (``config.json``, ``model_type: deepseek_v3``:
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``rope_interleave``, ``first_k_dense_replace``, ``n_routed_experts``,
``num_experts_per_tok``, ``n_shared_experts``, ``scoring_func``,
``norm_topk_prob``, ``routed_scaling_factor``), in ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``. The *expanded* form of the
attention only: every head's keys and values are expanded from the latents and
attended to as any head's. A layer at a time, no kernel, no cache, no
absorbed projection, no scan, no sorting of tokens; nothing from ``ray_tpu``
is imported.

For the normed input h of a token at position t, H heads:

    q_i      = h Wq_i                  split q_nope_i (nope), q_pe_i (rope)
    [c_raw ; k_raw] = h Wkv_a          c = rmsnorm(c_raw, kv_norm)   (rank)
    q_pe_i, k_pe = rope(q_pe_i, t), rope(k_raw, t)   pairs (2j, 2j+1), one k_pe for all heads
    k_nope_i = c Wuk_i^T ;  v_i = c Wuv_i            (Wkv_b's two halves of head i)
    p_i      = softmax_j((q_nope_i . k_nope_ij + q_pe_i . k_pe_j) / sqrt(nope + rope)),  j <= t
    x        = x + concat_i(sum_j p_ij v_ij) Wo
    h2       = rmsnorm(x, mlp_norm)
    dense:   x = x + (silu(h2 Wgate) * (h2 Wup)) Wdown
    sparse:  s = sigmoid(h2 Wr); idx = top_k(s + b); w = s[idx] / sum(s[idx])
             x = x + shared(h2) + scale * sum_k w_k expert_{idx_k}(h2)

What the cache of the program holds of a token is ``(k_pe, c)``; ``kv`` of
``forward_rows`` gives that pair, [L, T, 1, D] each, the rotated key at the
front of a row of whole ``KEY_TILE``-lane tiles with zeros behind it, which is how the
program's cache keeps it (``models/llama.py init_kv_cache``: whole 128-lane
tiles) and so the shape ``benchmark/compare.py engine_probe`` reads.

Attention goes a block of queries at a time and the routed sum a block of
experts at a time (weights arrive in the type they are served in and are
upcast by the block), so that a 2,500-token row at 128 experts fits beside a
resident engine. What the configuration leaves open is in the configuration
file's ``assumed``."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 16
QUERY_BLOCK = 512
KEY_TILE = 128  # lanes of a tile on the chip
MOE_LEAVES = ("moe_w_gate", "moe_w_up", "moe_w_down")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_pairs(x, positions, theta: float):
    """x [B, T, H, D], all of D rotated: neighbours (2j, 2j+1) by the angle
    ``t / theta ** (2j / D)`` (``rope_interleave: true``)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = positions[..., None].astype(jnp.float32) * inv  # [B, T, D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def attention_part(x, w, positions, *, rank, nope, theta, eps):
    """The attention half of a layer on x [B, T, E], expanded form. Returns x
    after the residual, the rotated shared key [B, T, 1, rope] and the normed
    latent [B, T, 1, rank]."""
    h = rmsnorm(x, w["attn_norm"], eps)
    q = jnp.einsum("bte,ehd->bthd", h, w["wq"])
    q_nope, q_pe = q[..., :nope], rope_pairs(q[..., nope:], positions, theta)
    kv = h @ w["wkv_a"]
    c = rmsnorm(kv[..., :rank], w["kv_norm"], eps)
    k_pe = rope_pairs(kv[:, :, None, rank:], positions, theta)
    k_nope = jnp.einsum("bsr,hnr->bshn", c, w["wuk"])
    v = jnp.einsum("bsr,hrv->bshv", c, w["wuv"])
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = []
    for at in range(0, x.shape[1], QUERY_BLOCK):
        rows = slice(at, at + QUERY_BLOCK)
        scores = (jnp.einsum("bqhn,bkhn->bhqk", q_nope[:, rows], k_nope)
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe[:, rows], k_pe[:, :, 0])) * scale
        allowed = positions[:, None, rows, None] >= positions[:, None, None, :]
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhv->bqhv", probs, v))
    attn = jnp.concatenate(out, axis=1)
    return x + jnp.einsum("bthv,hve->bte", attn, w["wo"]), k_pe, c[:, :, None, :]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def dense_part(x, w, *, eps):
    return x + swiglu(rmsnorm(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"], w["w_down"])


def route(x, w, *, top_k, eps):
    """Normed input of the expert layer, each token's weight for every
    expert [B, T, E] (its score over the sum of its chosen scores where the
    expert is one of the ``top_k`` by score plus bias, zero elsewhere), and
    the chosen experts."""
    h = rmsnorm(x, w["mlp_norm"], eps)
    scores = jax.nn.sigmoid(h @ w["moe_router"])
    _, idx = jax.lax.top_k(scores + w["moe_router_bias"], top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = top / top.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype) * top[..., None]).sum(-2)
    return h, weights, idx


def expert_block(h, gate, up, down, weights):
    """sum over the block's experts of weights[..., n] * expert_n(h);
    gate, up [N, E, F], down [N, F, E], weights [B, T, N]."""
    act = jax.nn.silu(jnp.einsum("bte,nef->bntf", h, gate)) * jnp.einsum("bte,nef->bntf", h, up)
    return jnp.einsum("bnte,btn->bte", jnp.einsum("bntf,nfe->bnte", act, down), weights)


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
        self.dense_lead = min(c["first_k_dense_replace"], self.n_layers)
        self._attn = jax.jit(_highest(functools.partial(
            attention_part, rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
            theta=float(c["rope_theta"]), eps=eps)))
        self._dense = jax.jit(_highest(functools.partial(dense_part, eps=eps)))
        self._route = jax.jit(_highest(functools.partial(
            route, top_k=c["num_experts_per_tok"], eps=eps)))
        self._block = jax.jit(_highest(expert_block))
        self._shared = jax.jit(_highest(swiglu))
        self._logits = jax.jit(_highest(
            lambda x, norm, unembed: rmsnorm(x, norm, eps) @ unembed))
        self._take = jax.jit(
            lambda leaf, i: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
            .astype(jnp.float32))
        self.block = math.gcd(c["n_routed_experts"], EXPERT_BLOCK)
        self._take_block = jax.jit(
            lambda leaf, i, at: jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False), at, self.block, 0)
            .astype(jnp.float32))

    # -- the served weights, a layer (or a block of experts) at a time --------

    def _attn_weights(self, params, l):
        names = {"attn_norm": "attn_norm", "wq": "wq_latent", "wkv_a": "wkv_a_latent",
                 "kv_norm": "kv_norm_latent", "wuk": "wuk_latent", "wuv": "wuv_latent",
                 "wo": "wo_latent"}
        return {k: self._take(params[leaf], l) for k, leaf in names.items()}

    def _feed_forward(self, params, l, xs):
        """The rows after layer l's feed-forward, and for an expert layer the
        experts each row's tokens chose (else None)."""
        norm = self._take(params["mlp_norm"], l)
        if l < self.dense_lead:
            w = {"mlp_norm": norm, **{k: self._take(params[k], l)
                                      for k in ("w_gate", "w_up", "w_down")}}
            return [self._dense(x, w) for x in xs], None
        row = l - self.dense_lead
        router = {"mlp_norm": norm, "moe_router": self._take(params["moe_router"], row),
                  "moe_router_bias": self._take(params["moe_router_bias"], row)}
        routed = [self._route(x, router) for x in xs]
        shared = [self._take(params["moe_shared_" + k], row) for k in ("gate", "up", "down")]
        sums = [self._shared(h, *shared) for h, _, _ in routed]
        scale = float(self.config["routed_scaling_factor"])
        for at in range(0, self.config["n_routed_experts"], self.block):
            block = [self._take_block(params[k], row, at) for k in MOE_LEAVES]
            sums = [s + scale * self._block(h, *block, wts[..., at:at + self.block])
                    for s, (h, wts, _) in zip(sums, routed)]
        return [x + s for x, s in zip(xs, sums)], [np.asarray(idx[0]) for _, _, idx in routed]

    # -- what the comparison calls -------------------------------------------

    def forward_rows(self, params, rows, last, kv_rows=()) -> dict:
        """Full forward pass over rows of different lengths (1-D token
        arrays). Returns the logits of each row's ``last`` positions, and for
        the rows named in ``kv_rows`` what a cache holds of every layer: the
        pair (rotated shared key at the front of a row of whole tiles, normed latent),
        [L, T, 1, D] each. ``choices`` holds, for each expert layer and row,
        the experts each token chose [T, k]."""
        rows = [np.asarray(r)[None] for r in rows]
        xs = [params["embed"][r].astype(jnp.float32) for r in rows]
        pos = [jnp.broadcast_to(jnp.arange(r.shape[1], dtype=jnp.int32), r.shape) for r in rows]
        kv = {i: ([], []) for i in kv_rows}
        choices = []
        for l in range(self.n_layers):
            w = self._attn_weights(params, l)
            for i, p in enumerate(pos):
                xs[i], k_pe, c = self._attn(xs[i], w, p)
                if i in kv:
                    k_pe = np.asarray(k_pe[0])
                    kv[i][0].append(np.pad(k_pe, ((0, 0), (0, 0), (0, -k_pe.shape[-1] % KEY_TILE))))
                    kv[i][1].append(np.asarray(c[0]))
            del w
            xs, chosen = self._feed_forward(params, l, xs)
            if chosen is not None:
                choices.append(chosen)
        norm = params["final_norm"].astype(jnp.float32)
        unembed = (params["embed"].T if self.config["tie_word_embeddings"]
                   else params["unembed"]).astype(jnp.float32)
        logits = [np.asarray(self._logits(x[:, -last:], norm, unembed))[0] for x in xs]
        return {"logits": logits, "choices": choices,
                "kv": {i: (np.stack(k), np.stack(v)) for i, (k, v) in kv.items()}}
