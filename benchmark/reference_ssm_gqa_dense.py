"""The plain reference of family ``ssm_gqa_dense``: a pre-norm decoder whose
every layer is a mixer and then a dense SwiGLU feed-forward, the mixer a
Mamba-2 state-space mixer (``mamba``) or grouped-query attention without any
position signal (``attention``) by ``layer_types``, under the four Granite
scalars. Written from the equations of ISSUE 44 and the catalog row of
ibm-granite/granite-4.0-h-micro (``config.json``, ``model_type:
granitemoehybrid``: ``layer_types``, ``mamba_n_heads``, ``mamba_d_head``,
``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``,
``shared_intermediate_size``, ``embedding_multiplier``,
``residual_multiplier``, ``attention_multiplier``, ``logits_scaling``,
``position_embedding_type: nope``, ``tie_word_embeddings``), in ``jax.numpy``
and float32 under ``jax.default_matmul_precision("highest")``. A layer at a
time, the recurrence a token at a time (``lax.scan`` over the tokens: no
chunked form, no cache, no kernel); nothing from ``ray_tpu`` is imported.

    x <- embedding_multiplier * E[token]
    each layer:  x <- x + residual_multiplier * mix(rmsnorm(x))
                 x <- x + residual_multiplier * (silu(u W_gate) * (u W_up)) W_down,  u = rmsnorm(x)
    logits = rmsnorm(x) E^T / logits_scaling          (the table tied)

    attention:  q, k, v = u Wq, u Wk, u Wv (no rotation);
                o = softmax(attention_multiplier * q k^T + causal) v;  mix = o Wo
    mamba:      [z | xBC | dt] = u W_in                 widths inner | inner + 2 G N | H
                xBC_t = silu(b + sum_j w_j xBC_{t - (K-1) + j})   (depthwise, causal, K taps;
                                                 zeros before the row's first token)
                x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t);  head h reads group h // (H / G)
                dt_t = softplus(dt_t + dt_bias);  a = -exp(A_log)                (a head each)
                S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t     S_0 = 0
                y_t = rmsnorm_grouped(y_t * silu(z_t)) * norm     (G groups; the gate before the norm)
                mix = y W_out

The convolution's weight lies [taps, channels] (the published [channels, 1,
taps] with the channels last). ``kv`` of ``forward_rows`` gives what a cache
holds of the attention layers (they alone have keys and values): [L*, T, KV,
D] each, ``L*`` the number of attention layers, which is the shape
``benchmark/compare.py engine_probe`` reads. What the configuration leaves
open is in the configuration file's ``assumed``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
SSM_LEAVES = ("ssm_w_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log", "ssm_d",
              "ssm_norm", "ssm_w_out")
FFN_LEAVES = ("w_gate", "w_up", "w_down")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mamba_mix(x, w, *, heads, head_dim, groups, state, eps, state_dtype=jnp.float32):
    """``mix`` of a mamba layer on x [1, T, E] -> [1, T, E]. ``state_dtype``
    is the type the state is held in and the recurrence's products and sums
    are made in: float32 is the reference; bfloat16 is the control of the
    configuration's ``assumed.ssm_precision``
    (``benchmark/tools/state_precision.py``)."""
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
    return (y @ w["ssm_w_out"])[None]


def attention_mix(x, w, *, kv_heads, scale, eps):
    """``mix`` of an attention layer on x [B, T, E]: causal grouped-query
    attention, no rotation, the scores times ``scale``. Returns the branch and
    the keys and values [B, T, KV, D]."""
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
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg[:, rows], k) * scale
        allowed = at[rows, None] >= at[None, :]
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bkgqs,bskd->bqkgd", probs, v))
    attn = jnp.concatenate(out, axis=1).reshape(B, T, H, D)
    return jnp.einsum("bthd,hde->bte", attn, w["wo"]), k, v


def feed_forward(x, w, *, eps):
    u = rmsnorm(x, w["norm"], eps)
    return (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]


class Reference:
    """Holds the jitted pieces for one configuration, on one device."""

    def __init__(self, config: dict, devices=None, state_dtype=jnp.float32):
        self.config = c = config
        devices = list(devices or jax.local_devices())
        if len(devices) != 1:
            raise ValueError("this reference runs on one device")
        self.device = devices[0]
        eps = float(c["rms_norm_eps"])
        self.kinds = list(c["layer_types"])
        if len(self.kinds) != c["num_hidden_layers"] or set(self.kinds) - {"mamba", "attention"}:
            raise ValueError("layer_types: mamba or attention, one a layer")
        self.embed_mult = float(c["embedding_multiplier"])
        self.res_mult = float(c["residual_multiplier"])
        logits_scaling = float(c["logits_scaling"])
        self._mamba = jax.jit(_highest(functools.partial(
            mamba_mix, heads=c["mamba_n_heads"], head_dim=c["mamba_d_head"],
            groups=c["mamba_n_groups"], state=c["mamba_d_state"], eps=eps,
            state_dtype=state_dtype)))
        self._attn = jax.jit(_highest(functools.partial(
            attention_mix, kv_heads=c["num_key_value_heads"],
            scale=float(c["attention_multiplier"]), eps=eps)))
        self._ffn = jax.jit(_highest(functools.partial(feed_forward, eps=eps)))
        self._logits = jax.jit(_highest(
            lambda x, norm, unembed: rmsnorm(x, norm, eps) @ unembed / logits_scaling))
        self._take = jax.jit(
            lambda leaf, i: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
            .astype(jnp.float32))

    def forward_rows(self, params, rows, last, kv_rows=()) -> dict:
        """Full forward pass over rows of different lengths (1-D token
        arrays). Returns the logits of each row's ``last`` positions, and for
        the rows named in ``kv_rows`` the keys and values of the attention
        layers, [L*, T, KV, D] each."""
        rows = [np.asarray(r)[None] for r in rows]
        xs = [self.embed_mult * params["embed"][r].astype(jnp.float32) for r in rows]
        kv = {i: ([], []) for i in kv_rows}
        n = {"mamba": 0, "attention": 0}  # layers of each kind so far
        for layer, kind in enumerate(self.kinds):
            norm = self._take(params["attn_norm"], layer)
            if kind == "mamba":
                w = {"norm": norm, **{k: self._take(params[k], n[kind]) for k in SSM_LEAVES}}
                xs = [x + self.res_mult * self._mamba(x, w) for x in xs]
            else:
                w = {"norm": norm,
                     "wq": self._take(params["wq_full"], n[kind]),
                     "wo": self._take(params["wo_full"], n[kind]),
                     "wk": self._take(params["wk"], n[kind]), "wv": self._take(params["wv"], n[kind])}
                for i in range(len(xs)):
                    branch, k, v = self._attn(xs[i], w)
                    xs[i] = xs[i] + self.res_mult * branch
                    if i in kv:
                        kv[i][0].append(np.asarray(k[0]))
                        kv[i][1].append(np.asarray(v[0]))
            n[kind] += 1
            w = {"norm": self._take(params["mlp_norm"], layer),
                 **{k: self._take(params[k], layer) for k in FFN_LEAVES}}
            xs = [x + self.res_mult * self._ffn(x, w) for x in xs]
        norm = params["final_norm"].astype(jnp.float32)
        unembed = (params["embed"].T if self.config["tie_word_embeddings"]
                   else params["unembed"]).astype(jnp.float32)
        logits = [np.asarray(self._logits(x[:, -last:], norm, unembed))[0] for x in xs]
        return {"logits": logits,
                "kv": {i: (np.stack(k), np.stack(v)) for i, (k, v) in kv.items()}}
