"""The plain reference of family ``ssm_latent_moe``: a pre-norm decoder whose
blocks are a mixer or a feed-forward alone, ``x <- x + f(rmsnorm(x))`` with one
``f`` a block, by a pattern of ``M`` (a Mamba-2 state-space mixer), ``E``
(sigmoid-routed squared-ReLU experts in a latent, beside a shared expert on
the input itself) and ``*`` (grouped-query attention without any position
signal). Written from the equations of ISSUE 35 and the catalog row of
NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (``config.json``, ``model_type:
nemotron_h``: ``hybrid_override_pattern``, ``mamba_num_heads``,
``mamba_head_dim``, ``n_groups``, ``ssm_state_size``, ``conv_kernel``,
``n_routed_experts``, ``num_experts_per_tok``, ``moe_latent_size``,
``moe_intermediate_size``, ``moe_shared_expert_intermediate_size``,
``routed_scaling_factor``, ``mlp_hidden_act: relu2``), in ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. A block at a time,
the recurrence a token at a time (``lax.scan`` over the tokens: no chunked
form, no cache, no kernel, no sorting of tokens into groups); nothing from
``ray_tpu`` is imported.

For the normed input u of a block (``norm_eps`` 1e-5):

    M:  [z | xBC | dt] = u W_in                 widths inner | inner + 2 G N | H
        xBC_t = silu(b + sum_j w_j xBC_{t - (K-1) + j})   (depthwise, causal, K taps;
                                                 zeros before the row's first token)
        x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t);  head h reads group h // (H / G)
        dt_t = softplus(dt_t + dt_bias);  a = -exp(A_log)                (a head each)
        S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t     S_0 = 0
        y_t = rmsnorm_grouped(y_t * silu(z_t)) * norm     (G groups; the gate before the norm)
        out = y W_out
    E:  s = sigmoid(u W_r) (all experts);  idx = top_k(s + bias);  w = scale * s[idx] / sum(s[idx])
        l = u W_down (into the latent);  expert_e(l) = relu(l W1_e)^2 W2_e
        out = (sum_{k: idx_k held here} w_k expert_{idx_k}(l)) W_up + relu(u W1_s)^2 W2_s
    *:  q, k, v = u Wq, u Wk, u Wv;  o = softmax(q k^T / sqrt(D) + causal) v;  out = o Wo

The weights hold a share of the experts (``n_routed_experts`` of the router's
``published.n_routed_experts``, from ``run.experts_first``) and of the
vocabulary, as one chip of the stated deployment does: the router scores and
chooses over all experts, and what an absent expert would add to a token is
left out, here as in the program. The convolution's weight lies [taps,
channels] (the published [channels, 1, taps] with the channels last).

``kv`` of ``forward_rows`` gives what a cache holds of the attention layers
(they alone have keys and values): [L*, T, KV, D] each, ``L*`` the number of
``*`` blocks, which is the shape ``benchmark/compare.py engine_probe`` reads.
What the configuration leaves open is in the configuration file's
``assumed``."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 16
QUERY_BLOCK = 512
MOE_LEAVES = ("moe_w_up", "moe_w_down")
SSM_LEAVES = ("ssm_w_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log", "ssm_d",
              "ssm_norm", "ssm_w_out")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


def ssm_part(x, w, *, heads, head_dim, groups, state, eps, state_dtype=jnp.float32):
    """An ``M`` block on x [1, T, E]. ``state_dtype`` is the type the state is
    held in and the recurrence's products and sums are made in: float32 is
    the reference; bfloat16 is the control of the configuration's
    ``assumed.ssm_precision`` (``benchmark/tools/state_precision.py``)."""
    u = rmsnorm(x, w["norm"], eps)[0]  # [T, E]
    T = u.shape[0]
    inner, bc = heads * head_dim, groups * state
    proj = u @ w["ssm_w_in"]
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + inner + 2 * bc], proj[:, -heads:]
    taps = w["ssm_conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    conv = w["ssm_conv_b"] + sum(w["ssm_conv_w"][j] * padded[j:j + T] for j in range(taps))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :inner].reshape(T, heads, head_dim)
    per = heads // groups
    # a group's B and C for each of its heads
    B = jnp.repeat(xbc[:, inner:inner + bc].reshape(T, groups, state), per, axis=1)
    C = jnp.repeat(xbc[:, inner + bc:].reshape(T, groups, state), per, axis=1)
    dt = jax.nn.softplus(dt + w["ssm_dt_bias"])  # [T, H]
    a = -jnp.exp(w["ssm_a_log"])  # [H]

    def token(S, inp):
        x_t, B_t, C_t, dt_t = inp
        decay = jnp.exp(dt_t * a).astype(state_dtype)[:, None, None]
        fed = ((dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]).astype(state_dtype)
        S = decay * S + fed
        read = jnp.einsum("hpn,hn->hp", S, C_t.astype(state_dtype)).astype(jnp.float32)
        return S, read + w["ssm_d"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, head_dim, state), state_dtype), (xs, B, C, dt))
    y = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(T, inner) * w["ssm_norm"]
    return x + (y @ w["ssm_w_out"])[None]


def attention_part(x, w, *, kv_heads, eps):
    """A ``*`` block on x [B, T, E]: causal grouped-query attention, no
    rotation. Returns x after the residual, and the keys and values
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
    return x + jnp.einsum("bthd,hde->bte", attn, w["wo"]), k, v


def route(x, w, *, top_k, scale, eps):
    """The normed input of an ``E`` block, the routed experts' input in the
    latent, each token's weight for every expert the router knows [B, T, E]
    (``scale`` times its score over the sum of its chosen scores where the
    expert is one of the ``top_k`` by score plus bias, zero elsewhere), and
    the chosen experts."""
    u = rmsnorm(x, w["norm"], eps)
    scores = jax.nn.sigmoid(u @ w["moe_router"])
    _, idx = jax.lax.top_k(scores + w["moe_router_bias"], top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = scale * top / top.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype) * top[..., None]).sum(-2)
    return u, u @ w["moe_latent_down"], weights, idx


def expert_block(l, up, down, weights):
    """sum over the block's experts of weights[..., n] * expert_n(l); up
    [N, latent, F], down [N, F, latent], weights [B, T, N]."""
    act = jnp.square(jax.nn.relu(jnp.einsum("btl,nlf->bntf", l, up)))
    return jnp.einsum("bntl,btn->btl", jnp.einsum("bntf,nfl->bntl", act, down), weights)


class Reference:
    """Holds the jitted pieces for one configuration, on one device."""

    def __init__(self, config: dict, devices=None, state_dtype=jnp.float32):
        self.config = c = config
        devices = list(devices or jax.local_devices())
        if len(devices) != 1:
            raise ValueError("this reference runs on one device")
        self.device = devices[0]
        eps = float(c["norm_eps"])
        self.pattern = c["hybrid_override_pattern"]
        if len(self.pattern) != c["num_hidden_layers"] or set(self.pattern) - set("ME*"):
            raise ValueError("a pattern of M, E and * blocks, one a layer")
        # this chip's experts among the router's
        self.held = c["n_routed_experts"]
        self.first = int(c.get("run", {}).get("experts_first", 0))
        self._ssm = jax.jit(_highest(functools.partial(
            ssm_part, heads=c["mamba_num_heads"], head_dim=c["mamba_head_dim"],
            groups=c["n_groups"], state=c["ssm_state_size"], eps=eps,
            state_dtype=state_dtype)))
        self._attn = jax.jit(_highest(functools.partial(
            attention_part, kv_heads=c["num_key_value_heads"], eps=eps)))
        self._route = jax.jit(_highest(functools.partial(
            route, top_k=c["num_experts_per_tok"], scale=float(c["routed_scaling_factor"]),
            eps=eps)))
        self._block = jax.jit(_highest(expert_block))
        self._relu2 = jax.jit(_highest(relu2))
        self._matmul = jax.jit(_highest(lambda a, b: a @ b))
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

    # -- a block's weights out of the served tree, in float32 -----------------

    def _experts(self, params, row, xs):
        """The rows after ``E`` block ``row`` (the ``row``-th of them, which
        is its row in ``mlp_norm`` and in every ``moe_*`` stack), and the
        experts each row's tokens chose."""
        w = {"norm": self._take(params["mlp_norm"], row),
             **{k: self._take(params[k], row)
                for k in ("moe_router", "moe_router_bias", "moe_latent_down")}}
        routed = [self._route(x, w) for x in xs]
        sums = [jnp.zeros_like(l) for _, l, _, _ in routed]
        for at in range(0, self.held, self.block):
            block = [self._take_block(params[k], row, at) for k in MOE_LEAVES]
            lo = self.first + at
            sums = [s + self._block(l, *block, wts[..., lo:lo + self.block])
                    for s, (_, l, wts, _) in zip(sums, routed)]
        up = self._take(params["moe_latent_up"], row)
        shared = [self._take(params["moe_shared_" + k], row) for k in ("up", "down")]
        out = [x + self._matmul(s, up) + self._relu2(u, *shared)
               for x, s, (u, _, _, _) in zip(xs, sums, routed)]
        return out, [np.asarray(idx[0]) for _, _, _, idx in routed]

    # -- what the comparison calls -------------------------------------------

    def forward_rows(self, params, rows, last, kv_rows=()) -> dict:
        """Full forward pass over rows of different lengths (1-D token
        arrays). Returns the logits of each row's ``last`` positions, and for
        the rows named in ``kv_rows`` the keys and values of the attention
        blocks, [L*, T, KV, D] each. ``choices`` holds, for each ``E`` block
        and row, the experts each token chose [T, k] (of all the router's)."""
        rows = [np.asarray(r)[None] for r in rows]
        xs = [params["embed"][r].astype(jnp.float32) for r in rows]
        kv = {i: ([], []) for i in kv_rows}
        choices = []
        n = {"M": 0, "E": 0, "*": 0}  # blocks of each kind so far
        for kind in self.pattern:
            mixer = n["M"] + n["*"]  # a mixer's norm: its row among the mixers
            if kind == "M":
                w = {"norm": self._take(params["attn_norm"], mixer),
                     **{k: self._take(params[k], n["M"]) for k in SSM_LEAVES}}
                xs = [self._ssm(x, w) for x in xs]
            elif kind == "*":
                w = {"norm": self._take(params["attn_norm"], mixer),
                     "wq": self._take(params["wq_full"], n["*"]),
                     "wo": self._take(params["wo_full"], n["*"]),
                     "wk": self._take(params["wk"], n["*"]), "wv": self._take(params["wv"], n["*"])}
                for i in range(len(xs)):
                    xs[i], k, v = self._attn(xs[i], w)
                    if i in kv:
                        kv[i][0].append(np.asarray(k[0]))
                        kv[i][1].append(np.asarray(v[0]))
            else:
                xs, chosen = self._experts(params, n["E"], xs)
                choices.append(chosen)
            n[kind] += 1
        norm = params["final_norm"].astype(jnp.float32)
        unembed = (params["embed"].T if self.config["tie_word_embeddings"]
                   else params["unembed"]).astype(jnp.float32)
        logits = [np.asarray(self._logits(x[:, -last:], norm, unembed))[0] for x in xs]
        return {"logits": logits, "choices": choices,
                "kv": {i: (np.stack(k), np.stack(v)) for i, (k, v) in kv.items()}}
