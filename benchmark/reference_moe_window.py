"""The plain reference of family ``moe_window_gqa``: a pre-norm decoder whose
layers alternate full and sliding-window grouped-query attention, each kind
with its own number of query heads and its own rotary settings, a per-head
sigmoid gate on the attention output, and a feed-forward that is dense in the
leading layer and 256 routed experts plus a shared one after it. Written from
the equations of ISSUE 28 and the catalog row of poolside Laguna-XS.2
(``config.json``: ``layer_types``, ``num_attention_heads_per_layer``,
``mlp_layer_types``, ``rope_parameters``, ``sliding_window``, ``gating``,
``num_experts``, ``num_experts_per_tok``, ``moe_routed_scaling_factor``), in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.
A layer at a time, no kernel, no cache, no scan, no sorting of tokens; nothing
from ``ray_tpu`` is imported.

For layer l of type t, H = heads of that layer, K key-value heads, D head width:

    h  = rmsnorm(x, attn_norm);  q, k = rope_t(h Wq), rope_t(h Wk);  v = h Wv
    o  = softmax(q k^T / sqrt(D) + mask_t) v
         mask_full: j <= i;  mask_sliding: 0 <= i - j < sliding_window
    o  = sigmoid(h Wg)[..., None] * o            (one scalar a head)
    x  = x + o Wo
    h2 = rmsnorm(x, mlp_norm)
    dense:  x = x + (silu(h2 Wgate) * (h2 Wup)) Wdown
    sparse: p = softmax(h2 Wr); (p_k, i_k) = top_k(p); p_k /= sum(p_k)
            x = x + shared(h2) + scale * sum_k p_k expert_{i_k}(h2)

``rope_full`` rotates the first ``partial_rotary_factor`` of each head with
YaRN's inverse frequencies (transformers ``_compute_yarn_parameters`` over the
rotated dims) and multiplies cos and sin by ``attention_factor``;
``rope_sliding`` rotates the whole head at its own theta.

The routed sum goes expert by expert: each expert's SwiGLU over the row's
tokens, weighted by the token's renormalised probability for that expert,
which is zero for a token that did not choose it. Expert weights arrive in the
type they are served in and are upcast a block of experts at a time (a whole
expert layer in float32 is 3.2 GB and does not fit beside a resident engine).

What the configuration leaves open, and is assumed here as in the
configuration file's ``assumed``: the gate's shape and place, the router's
softmax and renormalisation, the ungated shared expert, no query/key norm, and
the window's edge."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 32
MOE_LEAVES = ("moe_w_gate", "moe_w_up", "moe_w_down")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """transformers ``_compute_yarn_parameters``, ``truncate`` true."""
    def correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    return (interpolation * (1 - extrapolation_factor)
            + extrapolation * extrapolation_factor).astype(np.float32)


def rope_tables(config: dict, kind: str):
    """(inverse frequencies over the rotated dims, factor on cos and sin)."""
    rp = config["rope_parameters"][kind]
    dim = int(config["head_dim"] * rp["partial_rotary_factor"])
    base = float(rp["rope_theta"])
    if rp["rope_type"] == "yarn":
        inv = yarn_inv_freq(dim, base, float(rp["factor"]),
                            int(rp["original_max_position_embeddings"]),
                            float(rp["beta_fast"]), float(rp["beta_slow"]))
        return inv, float(rp["attention_factor"])
    if rp["rope_type"] != "default":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    return inv.astype(np.float32), 1.0


def rope(x, positions, inv_freq, factor):
    """x [B, T, H, D]: the first ``2 * len(inv_freq)`` dims of each head in
    the rotate-half form of the published code, the rest passed through."""
    rot = 2 * len(inv_freq)
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * factor)[:, :, None, :]
    sin = (jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * factor)[:, :, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    return jnp.concatenate([xr * cos + jnp.concatenate([-x2, x1], -1) * sin, rest], -1)


def attention_part(x, w, positions, *, kv_heads, window, inv_freq, factor, eps):
    """The attention half of a layer on x [B, T, E]. ``window`` None: causal.
    Returns x after the residual, and the layer's keys (rotated) and values
    [B, T, KV, D]."""
    h = rmsnorm(x, w["attn_norm"], eps)
    q = rope(jnp.einsum("bte,ehd->bthd", h, w["wq"]), positions, inv_freq, factor)
    keys = rope(jnp.einsum("bte,ehd->bthd", h, w["wk"]), positions, inv_freq, factor)
    values = jnp.einsum("bte,ehd->bthd", h, w["wv"])
    groups = q.shape[2] // kv_heads
    k = jnp.repeat(keys, groups, axis=2)
    v = jnp.repeat(values, groups, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    back = positions[:, None, :, None] - positions[:, None, None, :]  # query - key
    allowed = back >= 0
    if window is not None:
        allowed = allowed & (back < window)
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if "wg" in w:
        attn = attn * jax.nn.sigmoid(jnp.einsum("bte,eh->bth", h, w["wg"]))[..., None]
    return x + jnp.einsum("bthd,hde->bte", attn, w["wo"]), keys, values


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def dense_part(x, w, *, eps):
    return x + swiglu(rmsnorm(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"], w["w_down"])


def route(x, w, *, top_k, eps):
    """Normed input of the expert layer, and each token's weight for every
    expert [B, T, E]: its renormalised probability where the expert is one
    of its ``top_k``, zero elsewhere."""
    h = rmsnorm(x, w["mlp_norm"], eps)
    probs = jax.nn.softmax(h @ w["moe_router"], axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / top.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype) * top[..., None]).sum(-2)
    return h, weights, idx


def expert_block(h, gate, up, down, weights):
    """sum over the block's experts of weights[..., n] * expert_n(h);
    gate, up [N, E, F], down [N, F, E], weights [B, T, N]."""
    act = jax.nn.silu(jnp.einsum("bte,nef->bntf", h, gate)) * jnp.einsum("bte,nef->bntf", h, up)
    return jnp.einsum("bnte,btn->bte", jnp.einsum("bntf,nfe->bnte", act, down), weights)


class Reference:
    """Holds the jitted pieces for one configuration, on one device."""

    def __init__(self, config: dict, devices=None):
        self.config = config
        devices = list(devices or jax.local_devices())
        if len(devices) != 1:
            raise ValueError("this reference runs on one device")
        self.device = devices[0]
        c = config
        eps = float(c["rms_norm_eps"])
        self.n_layers = c["num_hidden_layers"]
        self.types = [t.split("_")[0] for t in c["layer_types"]]  # full | sliding
        self.sparse = [m == "sparse" for m in c["mlp_layer_types"]]
        # a layer's row in the stack of its kind (benchmark/families/moe_window_gqa.py)
        self.attn_row = [self.types[:i].count(t) for i, t in enumerate(self.types)]
        self.mlp_row = [self.sparse[:i].count(s) for i, s in enumerate(self.sparse)]
        self._attn = {}
        for kind, window in (("full", None), ("sliding", c["sliding_window"])):
            inv_freq, factor = rope_tables(c, kind + "_attention")
            self._attn[kind] = jax.jit(_highest(functools.partial(
                attention_part, kv_heads=c["num_key_value_heads"], window=window,
                inv_freq=inv_freq, factor=factor, eps=eps)))
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
        self.block = math.gcd(c["num_experts"], EXPERT_BLOCK)
        self._take_block = jax.jit(
            lambda leaf, i, at: jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False), at, self.block, 0)
            .astype(jnp.float32))

    # -- the served weights, a layer (or a block of experts) at a time --------

    def _attn_weights(self, params, l):
        kind, row = self.types[l], self.attn_row[l]
        w = {"attn_norm": self._take(params["attn_norm"], l),
             "wk": self._take(params["wk"], l), "wv": self._take(params["wv"], l),
             "wq": self._take(params["wq_" + kind], row),
             "wo": self._take(params["wo_" + kind], row)}
        if self.config["gating"]:
            w["wg"] = self._take(params["wg_" + kind], row)
        return w

    def _feed_forward(self, params, l, xs):
        """The rows after layer l's feed-forward, and for an expert layer the
        experts each row's tokens chose (else None)."""
        row = self.mlp_row[l]
        norm = self._take(params["mlp_norm"], l)
        if not self.sparse[l]:
            w = {"mlp_norm": norm, **{k: self._take(params[k], row)
                                      for k in ("w_gate", "w_up", "w_down")}}
            return [self._dense(x, w) for x in xs], None
        routed = [self._route(x, {"mlp_norm": norm,
                                  "moe_router": self._take(params["moe_router"], row)})
                  for x in xs]
        shared = [self._take(params["moe_shared_" + k], row) for k in ("gate", "up", "down")]
        sums = [self._shared(h, *shared) for h, _, _ in routed]
        scale = float(self.config["moe_routed_scaling_factor"])
        for at in range(0, self.config["num_experts"], self.block):
            block = [self._take_block(params[k], row, at) for k in MOE_LEAVES]
            sums = [s + scale * self._block(h, *block, wts[..., at:at + self.block])
                    for s, (h, wts, _) in zip(sums, routed)]
        return [x + s for x, s in zip(xs, sums)], [np.asarray(idx[0]) for _, _, idx in routed]

    # -- what the comparison calls -------------------------------------------

    def forward_rows(self, params, rows, last, kv_rows=()) -> dict:
        """Full forward pass over rows of different lengths (1-D token
        arrays). Returns the logits of each row's ``last`` positions, and for
        the rows named in ``kv_rows`` every layer's keys (rotated) and values
        [L, T, KV, D]. ``choices`` holds, for each expert layer and row, the
        experts each token chose [T, k] (for a caller that counts how many
        choices a lower precision moved)."""
        rows = [np.asarray(r)[None] for r in rows]
        xs = [params["embed"][r].astype(jnp.float32) for r in rows]
        pos = [jnp.broadcast_to(jnp.arange(r.shape[1], dtype=jnp.int32), r.shape) for r in rows]
        kv = {i: ([], []) for i in kv_rows}
        choices = []
        for l in range(self.n_layers):
            w = self._attn_weights(params, l)
            for i, p in enumerate(pos):
                xs[i], k, v = self._attn[self.types[l]](xs[i], w, p)
                if i in kv:
                    kv[i][0].append(np.asarray(k[0]))
                    kv[i][1].append(np.asarray(v[0]))
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
