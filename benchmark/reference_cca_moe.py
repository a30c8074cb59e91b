"""The plain reference of family ``cca_moe``: a pre-norm decoder whose every
layer is two joins under learned scales, compressed convolutional attention
and a top-1 expert layer routed by a small MLP with a stream of its own
through the depth (``model_type: zaya``; Zyphra ZAYA1-8B). Written from the
equations of ISSUE 48, the catalog row's ``config.json`` (``cca_time0``,
``cca_time1``, ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``partial_rotary_factor``, ``rope_parameters.hybrid.rope_theta``,
``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
``router_hidden_size``, ``tie_word_embeddings``) and the two papers
("Compressed Convolutional Attention", arXiv 2510.04476; the ZAYA1 technical
report, arXiv 2511.17127), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. A layer at a time over a whole
sequence: no cache, no tails, no chunks, no kernel, no scan, no sorting of
tokens into groups; nothing from ``ray_tpu`` is imported.

With ``d`` the hidden size, ``H`` query and ``K`` key-value heads of ``D``
(group ``H / K``), ``h = rmsnorm(x)`` (``rms_norm_eps``):

    x <- E[token]
    every layer:  x <- a1 * x + b1 * CCA(rmsnorm(x));  x <- a2 * x + b2 * MoE(rmsnorm(x))
                  (a, b learned vectors of d)
    logits = rmsnorm(x) E^T        (tied, no bias)

    CCA:  qk~_t = [W_q h_t | W_k h_t]                         (H + K heads of D)
          u_t   = c0[0] * qk~_(t-1) + c0[1] * qk~_t + c0_b    (depthwise, cca_time0 taps,
                                                               zeros before the first token)
          w_t[g] = u_(t-1)[g] C1[g, 0] + u_t[g] C1[g, 1] + c1_b[g]   (a D x D matrix a head
                                                               and tap: cca_time1, groups = heads)
          m_q[i] = (q~[i] + k~[i // (H/K)]) / 2;  m_k[j] = mean of m_q[i] over group j
          q = w_q + m_q;  k = w_k + m_k
          q <- sqrt(D) q / |q|;  k <- tau_j sqrt(D) k / |k|   (float32; |x| = sqrt(sum x^2 + 1e-6))
          the first partial_rotary_factor of each head rotated (pairs (i, i + rot/2)) at rope_theta
          v_t = [W_v1 h_t | W_v2 h_(t-1)]                     (h_(-1) = 0; K / 2 heads each)
          out = softmax(q k^T / sqrt(D) + causal) v  ->  W_o

    MoE:  r_l = h W_d + gamma_l * r_(l-1)                     (R = router_hidden_size; r_(-1) = 0)
          s = W_3 gelu(W_2 gelu(W_1 rmsnorm(r_l) + b_1) + b_2) + b_3;  p = softmax(s)
          e = argmax(p + beta);  y = p_e * W_down_e (silu(W_gate_e h) * W_up_e h)
          y = 0 for a token whose e is not held here

Departures from the papers, each a reading where they leave the form open (the
configuration file's ``assumed`` gives the reasons): the order norm,
temperature, rotation; which key-value head holds the shifted value; biases on
the convolutions and on the router's MLP and none on a projection; the
router's input norm and GELU (tanh form, ``jax.nn.gelu``'s default); ``gamma`` a
vector; no skip choice among the router's outputs (``left_out``).

The weights hold a share of the experts (``num_experts`` of the router's
``published.num_experts``, from ``run.experts_first``), as one chip of the
stated deployment does: the router scores and chooses over all of them. The
served tree holds ``W_v1`` and ``W_v2`` as the two halves of ``wv``'s heads,
the depthwise convolution as [taps, channels] and the one that mixes a head's
channels as [heads, taps x D, D] (row ``tap * D + i`` multiplies channel ``i``
of that tap; tap 0 the older).

``kv`` of ``forward_rows`` gives what a cache holds of every layer: the
convolved, normed, rotated keys and the values with their shifted half,
[L, T, K, D] each: the shape ``benchmark/compare.py engine_probe`` reads."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 4
QUERY_BLOCK = 512
VOCAB_BLOCK = 32768  # rows of the table a product: its float32 copy is 0.27 GB, not 2.1
L2_EPS = 1e-6
MOE_LEAVES = ("moe_w_gate", "moe_w_up", "moe_w_down")
CCA_LEAVES = ("cca_conv0_w", "cca_conv0_b", "cca_conv1_w", "cca_conv1_b", "cca_temp")
ROUTER_LEAVES = ("down", "gamma", "norm", "w1", "b1", "w2", "b2", "w3", "b3", "bias")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def shifted(x, by: int):
    """x [T, ...] moved ``by`` tokens later, zeros in front."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros((by,) + x.shape[1:], x.dtype), x[:-by]], axis=0)


def rotate(x, theta: float, share: float):
    """x [T, heads, D]: the first ``share`` of each head rotated by the
    token's position, pairs (i, i + rot / 2)."""
    T, _, D = x.shape
    rot = int(D * share)
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.asarray(np.arange(T, dtype=np.float64)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def cca_part(x, w, *, heads, kv_heads, taps, theta, rotary, eps):
    """Compressed convolutional attention on x [T, E]: the branch's output
    (before the join) and the keys and values [T, K, D]."""
    h = rmsnorm(x, w["norm"], eps)
    T = h.shape[0]
    H, K = heads, kv_heads
    D = w["wq"].shape[-1]
    group = H // K
    qk = jnp.concatenate([jnp.einsum("te,ehd->thd", h, w["wq"]),
                          jnp.einsum("te,ekd->tkd", h, w["wk"])], axis=1)  # [T, H + K, D]
    flat = qk.reshape(T, (H + K) * D)
    t0, t1 = taps
    u = w["cca_conv0_b"] + sum(w["cca_conv0_w"][j] * shifted(flat, t0 - 1 - j) for j in range(t0))
    u = u.reshape(T, H + K, D)
    mix = w["cca_conv1_w"].reshape(H + K, t1, D, D)
    conv = w["cca_conv1_b"].reshape(H + K, D) + sum(
        jnp.einsum("tgc,gcd->tgd", shifted(u, t1 - 1 - j), mix[:, j]) for j in range(t1))
    mean_q = 0.5 * (qk[:, :H] + jnp.repeat(qk[:, H:], group, axis=1))
    mean_k = mean_q.reshape(T, K, group, D).mean(axis=2)
    q, k = conv[:, :H] + mean_q, conv[:, H:] + mean_k

    def to_length(t):
        return math.sqrt(D) * t / jnp.sqrt((t * t).sum(axis=-1, keepdims=True) + L2_EPS)

    q, k = to_length(q), to_length(k) * w["cca_temp"][:, None]
    q, k = rotate(q, theta, rotary), rotate(k, theta, rotary)
    v_all = jnp.einsum("te,ekd->tkd", h, w["wv"])
    v = jnp.concatenate([v_all[:, : K // 2], shifted(v_all[:, K // 2:], 1)], axis=1)
    qg = q.reshape(T, K, group, D)
    at = jnp.arange(T)
    out = []
    for lo in range(0, T, QUERY_BLOCK):
        rows = slice(lo, lo + QUERY_BLOCK)
        scores = jnp.einsum("qkgd,skd->kgqs", qg[rows], k) / math.sqrt(D)
        allowed = at[rows, None] >= at[None, :]
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgqs,skd->qkgd", probs, v))
    attn = jnp.concatenate(out, axis=0).reshape(T, H, D)
    return jnp.einsum("thd,hde->te", attn, w["wo"]), k, v


def route(x, r_prev, w, *, eps):
    """The normed input of an expert layer [T, E], each token's weight for
    every expert the router knows [T, experts] (its probability where the
    expert is the one chosen by probability plus bias, zero elsewhere), the
    chosen expert [T, 1] and the router's own vector [T, R]."""
    h = rmsnorm(x, w["norm"], eps)
    r = h @ w["down"] + w["gamma"] * r_prev
    z = rmsnorm(r, w["rnorm"], eps)
    z = jax.nn.gelu(z @ w["w1"] + w["b1"])
    z = jax.nn.gelu(z @ w["w2"] + w["b2"])
    p = jax.nn.softmax(z @ w["w3"] + w["b3"], axis=-1)
    chosen = jnp.argmax(p + w["bias"], axis=-1)
    weights = jax.nn.one_hot(chosen, p.shape[-1], dtype=p.dtype) * p
    return h, weights, chosen[:, None], r


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
        if c["num_experts_per_tok"] != 1:
            raise ValueError("this reference routes one expert a token")
        self.eps = eps = float(c["rms_norm_eps"])
        self.layers = c["num_hidden_layers"]
        self.held = c["num_experts"]  # this chip's experts among the router's
        self.first = int(c.get("run", {}).get("experts_first", 0))
        self._cca = jax.jit(_highest(functools.partial(
            cca_part, heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            taps=(c["cca_time0"], c["cca_time1"]),
            theta=float(c["rope_parameters"]["hybrid"]["rope_theta"]),
            rotary=float(c["partial_rotary_factor"]), eps=eps)))
        self._route = jax.jit(_highest(functools.partial(route, eps=eps)))
        self._block = jax.jit(_highest(expert_block))
        self._final = jax.jit(lambda x, norm: rmsnorm(x, norm, eps))
        self._logits = jax.jit(_highest(lambda x, rows: x @ rows.astype(jnp.float32).T))
        self._take = jax.jit(
            lambda leaf, i: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
            .astype(jnp.float32))
        self.block = math.gcd(self.held, EXPERT_BLOCK)
        self._take_block = jax.jit(
            lambda leaf, i, at: jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False), at, self.block, 0)
            .astype(jnp.float32))

    def _experts(self, params, layer, xs, rs):
        """The rows after the expert half of ``layer``, the router's vectors
        it hands on, and the expert each row's tokens chose."""
        w = {"norm": self._take(params["mlp_norm"], layer),
             "rnorm": self._take(params["moe_router_norm"], layer),
             **{k: self._take(params["moe_router_" + k], layer) for k in ROUTER_LEAVES
                if k != "norm"}}
        a, b = self._take(params["mlp_scale"], layer)
        routed = [self._route(x, r, w) for x, r in zip(xs, rs)]
        sums = [jnp.zeros_like(h) for h, *_ in routed]
        for at in range(0, self.held, self.block):
            block = [self._take_block(params[k], layer, at) for k in MOE_LEAVES]
            lo = self.first + at
            sums = [s + self._block(h, *block, wts[:, lo:lo + self.block])
                    for s, (h, wts, _, _) in zip(sums, routed)]
        return ([a * x + b * s for x, s in zip(xs, sums)], [r for *_, r in routed],
                [np.asarray(idx) for _, _, idx, _ in routed])

    def forward_rows(self, params, rows, last, kv_rows=()) -> dict:
        """Full forward pass over rows of different lengths (1-D token
        arrays). Returns the logits of each row's ``last`` positions, and for
        the rows named in ``kv_rows`` the keys and values of every layer,
        [L, T, K, D] each. ``choices`` holds, for each layer and row, the
        expert each token chose [T, 1] (of all the router's)."""
        rows = [np.asarray(r) for r in rows]
        xs = [params["embed"][r].astype(jnp.float32) for r in rows]
        R = params["moe_router_down"].shape[-1]
        rs = [jnp.zeros((len(r), R), jnp.float32) for r in rows]
        kv = {i: ([], []) for i in kv_rows}
        choices = []
        for layer in range(self.layers):
            w = {"norm": self._take(params["attn_norm"], layer),
                 "wq": self._take(params["wq_cca"], layer),
                 "wo": self._take(params["wo_cca"], layer),
                 "wk": self._take(params["wk"], layer), "wv": self._take(params["wv"], layer),
                 **{k: self._take(params[k], layer) for k in CCA_LEAVES}}
            a, b = self._take(params["attn_scale"], layer)
            for i in range(len(xs)):
                out, k, v = self._cca(xs[i], w)
                xs[i] = a * xs[i] + b * out
                if i in kv:
                    kv[i][0].append(np.asarray(k))
                    kv[i][1].append(np.asarray(v))
            xs, rs, chosen = self._experts(params, layer, xs, rs)
            choices.append(chosen)
        norm = params["final_norm"].astype(jnp.float32)
        if not self.config["tie_word_embeddings"]:
            raise ValueError("this family's head is the table")
        table = params["embed"]
        blocks = [table[lo:lo + VOCAB_BLOCK] for lo in range(0, table.shape[0], VOCAB_BLOCK)]
        logits = []
        for x in xs:
            x = self._final(x[-last:], norm)
            logits.append(np.concatenate([np.asarray(self._logits(x, b)) for b in blocks], axis=-1))
        return {"logits": logits, "choices": choices,
                "kv": {i: (np.stack(k), np.stack(v)) for i, (k, v) in kv.items()}}
