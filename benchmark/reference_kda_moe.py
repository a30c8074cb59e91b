"""The plain reference of family ``kda_moe``: a pre-norm decoder whose every
layer is ``x <- x + mixer(rmsnorm(x))``, ``x <- x + experts(rmsnorm(x))``, the
mixer Kimi Delta Attention (``kda``: a gated delta rule with a decay a
channel; Kimi Linear, arXiv:2510.26692) or, in the layers ``gqa_layers``,
grouped-query attention without any position signal and with an output gate a
channel; the experts sigmoid-routed SwiGLU ones beside a shared one. Written
from the equations of ISSUE 42 and the catalog row of upstage Solar-Open2-250B
(``config.json``, ``model_type: solar_open2``: ``linear_attn_config``,
``gqa_layers``, ``use_rope`` false, ``use_gqa_gate``, ``kda_use_full_proj``
false, ``kda_allow_neg_eigval``, ``n_routed_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``n_shared_experts``, ``norm_topk_prob``,
``routed_scaling_factor``), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. A layer at a time, the
recurrence a token at a time (``lax.scan`` over the tokens: no chunked form,
no cache, no kernel, no sorting of tokens into groups); nothing from
``ray_tpu`` is imported.

For the normed input u of a mixer (``rms_norm_eps`` 1e-5), H heads of d:

    kda: [q~ | k~ | v~ | f | z | b] = u W_in        widths 3 x H d | rank | rank | H
         q~, k~, v~ each through a depthwise causal convolution of K taps (zeros
         before the row's first token, no bias) and SiLU
         q = l2norm_head(q~) d^-0.5;  k = l2norm_head(k~)      (x / sqrt(sum x^2 + 1e-6))
         g_t = -exp(A_log_h) softplus(f W_f2 + dt_bias)   [H, d];  alpha_t = exp(g_t)
         beta_t = 2 sigmoid(b)                            [H]
         S' = diag(alpha_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  S_0 = 0
         o_t = S_t^T q_t
         o = rmsnorm_head(o_t; w[d]) * sigmoid(z W_g2);   out = o W_o
    gqa: q, k, v = u Wq, u Wk, u Wv;  o = softmax(q k^T / sqrt(D) + causal) v
         o = o * sigmoid(u W_gate)  (a channel);          out = o Wo
    experts, for the normed input u of the layer's second half:
         s = sigmoid(u W_r) (all experts);  idx = top_k(s + bias);  w = scale s[idx] / sum(s[idx])
         expert_e(u) = (silu(u W1_e) * (u W2_e)) W3_e
         out = sum_{k: idx_k held here} w_k expert_{idx_k}(u) + shared(u)

The weights hold a share of the experts (``n_routed_experts`` of the router's
``published.n_routed_experts``, from ``run.experts_first``) and of the
vocabulary, as one chip of the stated deployment does: the router scores and
chooses over all experts, and what an absent expert would add to a token is
left out, here as in the program. The served tree holds the mixer's input
projections as one matrix (``kda_w_in``: the published q, k, v, the two low
ranks' first halves and b side by side) and the three convolutions as one
weight [taps, channels].

``kv`` of ``forward_rows`` gives what a cache holds of the attention layers
(they alone have keys and values): [L*, T, KV, D] each, ``L*`` the number of
attention layers, which is the shape ``benchmark/compare.py engine_probe``
reads. What the configuration leaves open is in the configuration file's
``assumed``."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 8
QUERY_BLOCK = 512
L2_EPS = 1e-6
MOE_LEAVES = ("moe_w_gate", "moe_w_up", "moe_w_down")
KDA_LEAVES = ("kda_w_in", "kda_conv_w", "kda_w_decay", "kda_dt_bias", "kda_a_log", "kda_w_gate",
              "kda_norm", "kda_w_out")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(axis=-1, keepdims=True) + L2_EPS)


def kda_part(x, w, *, heads, head_dim, eps, state_dtype=jnp.float32):
    """A ``kda`` mixer on x [1, T, E]. ``state_dtype`` is the type the state
    is held in and the recurrence's products and sums are made in: float32 is
    the reference; bfloat16 the control of the configuration's
    ``assumed.kda_precision`` (``benchmark/tools/state_precision.py``)."""
    u = rmsnorm(x, w["norm"], eps)[0]  # [T, E]
    T = u.shape[0]
    inner = heads * head_dim
    rank = w["kda_w_decay"].shape[0]
    proj = u @ w["kda_w_in"]
    qkv, f = proj[:, :3 * inner], proj[:, 3 * inner:3 * inner + rank]
    z, b = proj[:, 3 * inner + rank:3 * inner + 2 * rank], proj[:, -heads:]
    taps = w["kda_conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1]), qkv.dtype), qkv], axis=0)
    qkv = jax.nn.silu(sum(w["kda_conv_w"][j] * padded[j:j + T] for j in range(taps)))
    q, k, v = (qkv[:, j * inner:(j + 1) * inner].reshape(T, heads, head_dim) for j in range(3))
    q, k = l2norm(q) * head_dim ** -0.5, l2norm(k)
    g = -jnp.exp(w["kda_a_log"])[:, None] * jax.nn.softplus(
        f @ w["kda_w_decay"] + w["kda_dt_bias"]).reshape(T, heads, head_dim)
    beta = 2.0 * jax.nn.sigmoid(b)  # [T, H]

    def token(S, inp):
        q_t, k_t, v_t, g_t, beta_t = inp
        S = jnp.exp(g_t).astype(state_dtype)[:, :, None] * S
        k_s = k_t.astype(state_dtype)
        read = jnp.einsum("hkv,hk->hv", S, k_s)  # S'^T k
        write = (beta_t[:, None] * (v_t - read.astype(jnp.float32))).astype(state_dtype)
        S = S + k_s[:, :, None] * write[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t.astype(state_dtype)).astype(jnp.float32)

    _, o = jax.lax.scan(
        token, jnp.zeros((heads, head_dim, head_dim), state_dtype), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w["kda_norm"]
    o = o.reshape(T, inner) * jax.nn.sigmoid(z @ w["kda_w_gate"])
    return x + (o @ w["kda_w_out"])[None]


def attention_part(x, w, *, kv_heads, eps):
    """A ``gqa`` mixer on x [B, T, E]: causal grouped-query attention, no
    rotation, the output times a sigmoid gate a channel read from the normed
    input. Returns x after the residual, and the keys and values
    [B, T, KV, D]."""
    h = rmsnorm(x, w["norm"], eps)
    q = jnp.einsum("bte,ehd->bthd", h, w["wq"])
    k = jnp.einsum("bte,ekd->btkd", h, w["wk"])
    v = jnp.einsum("bte,ekd->btkd", h, w["wv"])
    B, T, H, D = q.shape
    qg = q.reshape(B, T, kv_heads, H // kv_heads, D)
    at = jnp.arange(T)
    out = []
    for lo in range(0, T, QUERY_BLOCK):
        rows = slice(lo, lo + QUERY_BLOCK)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg[:, rows], k) / np.sqrt(D)
        allowed = at[rows, None] >= at[None, :]
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bkgqs,bskd->bqkgd", probs, v))
    attn = jnp.concatenate(out, axis=1).reshape(B, T, H, D)
    attn = attn * jax.nn.sigmoid(h @ w["wg"]).reshape(B, T, H, D)
    return x + jnp.einsum("bthd,hde->bte", attn, w["wo"]), k, v


def route(x, w, *, top_k, scale, eps):
    """The normed input of an expert layer, each token's weight for every
    expert the router knows [B, T, E] (``scale`` times its score over the sum
    of its chosen scores where the expert is one of the ``top_k`` by score
    plus bias, zero elsewhere), and the chosen experts."""
    u = rmsnorm(x, w["norm"], eps)
    scores = jax.nn.sigmoid(u @ w["moe_router"])
    _, idx = jax.lax.top_k(scores + w["moe_router_bias"], top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = scale * top / top.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype) * top[..., None]).sum(-2)
    return u, weights, idx


def expert_block(u, gate, up, down, weights):
    """sum over the block's experts of weights[..., n] * expert_n(u); gate, up
    [N, E, F], down [N, F, E], weights [B, T, N]."""
    act = jax.nn.silu(jnp.einsum("bte,nef->bntf", u, gate)) * jnp.einsum("bte,nef->bntf", u, up)
    return jnp.einsum("bnte,btn->bte", jnp.einsum("bntf,nfe->bnte", act, down), weights)


class Reference:
    """Holds the jitted pieces for one configuration, on one device."""

    def __init__(self, config: dict, devices=None, state_dtype=jnp.float32):
        self.config = c = config
        devices = list(devices or jax.local_devices())
        if len(devices) != 1:
            raise ValueError("this reference runs on one device")
        self.device = devices[0]
        eps = float(c["rms_norm_eps"])
        self.layers = c["num_hidden_layers"]
        self.gqa = set(c["gqa_layers"])
        if not self.gqa <= set(range(self.layers)):
            raise ValueError("gqa_layers name the attention layers among num_hidden_layers")
        # this chip's experts among the router's
        self.held = c["n_routed_experts"]
        self.first = int(c.get("run", {}).get("experts_first", 0))
        lin = c["linear_attn_config"]
        self._kda = jax.jit(_highest(functools.partial(
            kda_part, heads=lin["num_heads"], head_dim=lin["head_dim"], eps=eps,
            state_dtype=state_dtype)))
        self._attn = jax.jit(_highest(functools.partial(
            attention_part, kv_heads=c["num_key_value_heads"], eps=eps)))
        self._route = jax.jit(_highest(functools.partial(
            route, top_k=c["num_experts_per_tok"], scale=float(c["routed_scaling_factor"]),
            eps=eps)))
        self._block = jax.jit(_highest(expert_block))
        self._swiglu = jax.jit(_highest(swiglu))
        self._logits = jax.jit(_highest(
            lambda x, norm, unembed: rmsnorm(x, norm, eps) @ unembed))
        self._take = jax.jit(
            lambda leaf, i: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
            .astype(jnp.float32))
        self.block = math.gcd(self.held, EXPERT_BLOCK)
        self._take_block = jax.jit(
            lambda leaf, i, at: jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False), at, self.block, 0)
            .astype(jnp.float32))

    def _experts(self, params, row, xs):
        """The rows after the expert half of layer ``row`` (its row in
        ``mlp_norm`` and in every ``moe_*`` stack), and the experts each row's
        tokens chose."""
        w = {"norm": self._take(params["mlp_norm"], row),
             **{k: self._take(params[k], row) for k in ("moe_router", "moe_router_bias")}}
        routed = [self._route(x, w) for x in xs]
        sums = [jnp.zeros_like(u) for u, _, _ in routed]
        for at in range(0, self.held, self.block):
            block = [self._take_block(params[k], row, at) for k in MOE_LEAVES]
            lo = self.first + at
            sums = [s + self._block(u, *block, wts[..., lo:lo + self.block])
                    for s, (u, wts, _) in zip(sums, routed)]
        shared = [self._take(params["moe_shared_" + k], row) for k in ("gate", "up", "down")]
        out = [x + s + self._swiglu(u, *shared) for x, s, (u, _, _) in zip(xs, sums, routed)]
        return out, [np.asarray(idx[0]) for _, _, idx in routed]

    def forward_rows(self, params, rows, last, kv_rows=()) -> dict:
        """Full forward pass over rows of different lengths (1-D token
        arrays). Returns the logits of each row's ``last`` positions, and for
        the rows named in ``kv_rows`` the keys and values of the attention
        layers, [L*, T, KV, D] each. ``choices`` holds, for each layer and
        row, the experts each token chose [T, k] (of all the router's)."""
        rows = [np.asarray(r)[None] for r in rows]
        xs = [params["embed"][r].astype(jnp.float32) for r in rows]
        kv = {i: ([], []) for i in kv_rows}
        choices = []
        n = {"kda": 0, "gqa": 0}  # mixers of each kind so far
        for layer in range(self.layers):
            norm = self._take(params["attn_norm"], layer)
            if layer in self.gqa:
                a = n["gqa"]
                w = {"norm": norm, "wq": self._take(params["wq_full"], a),
                     "wo": self._take(params["wo_full"], a), "wg": self._take(params["wg_full"], a),
                     "wk": self._take(params["wk"], a), "wv": self._take(params["wv"], a)}
                for i in range(len(xs)):
                    xs[i], k, v = self._attn(xs[i], w)
                    if i in kv:
                        kv[i][0].append(np.asarray(k[0]))
                        kv[i][1].append(np.asarray(v[0]))
                n["gqa"] += 1
            else:
                w = {"norm": norm, **{k: self._take(params[k], n["kda"]) for k in KDA_LEAVES}}
                xs = [self._kda(x, w) for x in xs]
                n["kda"] += 1
            xs, chosen = self._experts(params, layer, xs)
            choices.append(chosen)
        norm = params["final_norm"].astype(jnp.float32)
        unembed = (params["embed"].T if self.config["tie_word_embeddings"]
                   else params["unembed"]).astype(jnp.float32)
        logits = [np.asarray(self._logits(x[:, -last:], norm, unembed))[0] for x in xs]
        return {"logits": logits, "choices": choices,
                "kv": {i: (np.stack(k), np.stack(v)) for i, (k, v) in kv.items()}}
