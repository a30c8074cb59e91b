"""The plain reference of family ``sparse_latent``: a pre-norm decoder whose
layers are latent attention of two kinds, each with a query latent and a gate
a head on the heads' outputs. A *full* layer attends the ``index_topk``
positions a learned indexer picks (DeepSeek-V3.2's), a *sliding* one the last
``sliding_window_size`` positions over a latent of its own sizes; layer 0 has a
dense SwiGLU, the others sigmoid-routed experts with a selection bias beside a
shared one, of which this chip holds the first ``n_routed_experts``. Written
from the equations of ISSUE 51 and the catalog row of dots-studio
dots3-note-prev (``config.json``, ``model_type: dots3_note``), in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.
The *expanded* form of the attention only: every head's keys and values are
expanded from the latents and attended to under a mask. A layer at a time, no
kernel, no cache, no absorbed projection, no gather, no sorting of tokens;
nothing from ``ray_tpu`` is imported.

For the normed input h of a token at position t (sizes of the layer's kind):

    c_q = s_q rmsnorm(h Wqa, q_norm)          s_q = sqrt(hidden / q_lora_rank)
    q_i = c_q Wq_i                            split q_nope_i (nope), q_pe_i (rope)
    [c_raw ; k_raw] = h Wkv_a                 c = s_kv rmsnorm(c_raw, kv_norm)
    q_pe_i, k_pe = rope(q_pe_i, t), rope(k_raw, t)     pairs (2j, 2j+1), one k_pe for all heads
    k_nope_i = c Wuk_i^T ;  v_i = c Wuv_i
    full:    q_I = c_q W_Iq (Hi heads of Di), k_I = layernorm(h W_Ik) (Di), w = h W_Iw (Hi);
             the first ``rope`` numbers of each q_I head and of k_I rotated by halves
             I[t, s] = sum_j w[t, j] Hi^-1/2 Di^-1/2 relu(q_I[t, j] . k_I[s])
             allowed(t) = the index_topk positions s <= t of largest I[t, s], ties to the lower s
    sliding: allowed(t) = { s : 0 <= t - s < window }
    p_i = softmax over allowed(t) of (q_nope_i . k_nope_i + q_pe_i . k_pe) / sqrt(nope + rope)
    g   = sigmoid(h Wg)                       a number a head
    x   = x + concat_i(g_i sum_s p_is v_is) Wo
    h2  = rmsnorm(x, mlp_norm)
    dense:   x = x + (silu(h2 Wgate) * (h2 Wup)) Wdown
    sparse:  s = sigmoid(h2 Wr) over all published experts; idx = top_k(s + b);
             w = s[idx] / sum(s[idx]);  x = x + shared(h2) + scale * sum over the chosen
             experts *held here* of w_k expert_{idx_k}(h2)

What the program's cache holds of a token in a full layer is ``(k_pe, c)``
(and the index key, which ``benchmark/compare.py`` does not read); ``kv`` of
``forward_rows`` gives that pair for the full layers alone, [L_full, T, 1, D]
each, the rotated key at the front of a row of whole ``KEY_TILE``-lane tiles,
which is the shape ``engine_probe`` reads out of ``k`` and ``v``.

Attention goes a block of queries at a time and the routed sum a block of
experts at a time (weights arrive in the type they are served in and are
upcast by the block), so that a 4,500-token row fits beside a resident
engine. What the configuration leaves open is in its file's ``assumed``."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 4
QUERY_BLOCK = 128
KEY_TILE = 128  # lanes of a tile on the chip
MOE_LEAVES = ("moe_w_gate", "moe_w_up", "moe_w_down")
LAYER_KINDS = {"full_attention": "latent", "sliding_attention": "latent_sliding"}


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layernorm(x, w, b, eps):
    x = x - x.mean(axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


def _angles(positions, d: int, theta: float):
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = positions[..., None].astype(jnp.float32) * inv  # [B, T, d/2]
    return jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]


def rope_pairs(x, positions, theta: float):
    """x [B, T, H, D], all of D rotated: neighbours (2j, 2j+1) by the angle
    ``t / theta ** (2j / D)``."""
    cos, sin = _angles(positions, x.shape[-1], theta)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def rope_halves_front(x, positions, theta: float, rot: int):
    """x [B, T, H, D]: the first ``rot`` numbers rotated, (j, j + rot/2) by
    ``t / theta ** (2j / rot)``; the rest pass."""
    cos, sin = _angles(positions, rot, theta)
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def chosen(scores, allowed, k: int):
    """Of each row of ``scores`` [.., S] the ``k`` largest among ``allowed``,
    ties to the lower position, as a mask: a stable sort by falling score,
    each position's rank, ranks under ``k``."""
    order = jnp.argsort(jnp.where(allowed, -scores, jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return allowed & (rank < k)


def index_parts(h, c_q, w, positions, *, theta, rope, eps, index_heads, index_dim):
    """The indexer's queries [B, T, Hi, Di], key [B, T, Di] and scaled weight a
    head [B, T, Hi] of a full layer's normed input h and query latent c_q."""
    B, T, _ = h.shape
    q_i = rope_halves_front((c_q @ w["index_wq"]).reshape(B, T, index_heads, index_dim),
                            positions, theta, rope)
    k_i = rope_halves_front(
        layernorm(h @ w["index_wk"], w["index_k_norm"], w["index_k_bias"], eps)[:, :, None, :],
        positions, theta, rope)[:, :, 0]
    return q_i, k_i, (h @ w["index_ww"]) * (index_heads ** -0.5 * index_dim ** -0.5)


def index_scores(q_i, k_i, w_i):
    """I[t, s] of a block of queries against all keys: [B, q, S]."""
    return jnp.einsum("bqh,bqhk->bqk", w_i, jax.nn.relu(jnp.einsum("bqhd,bkd->bqhk", q_i, k_i)))


def attention_part(x, w, positions, *, kind, rank, nope, rope, theta, eps, s_q, s_kv,
                   window, index_heads, index_dim, index_topk):
    """The attention half of a layer on x [B, T, E], expanded form. Returns x
    after the residual, the rotated shared key [B, T, 1, rope] and the normed
    latent [B, T, 1, rank]."""
    h = rmsnorm(x, w["attn_norm"], eps)
    c_q = s_q * rmsnorm(h @ w["wqa"], w["q_norm"], eps)
    q = jnp.einsum("btr,rhd->bthd", c_q, w["wq"])
    q_nope, q_pe = q[..., :nope], rope_pairs(q[..., nope:], positions, theta)
    kv = h @ w["wkv_a"]
    c = s_kv * rmsnorm(kv[..., :rank], w["kv_norm"], eps)
    k_pe = rope_pairs(kv[:, :, None, rank:], positions, theta)
    k_nope = jnp.einsum("bsr,hnr->bshn", c, w["wuk"])
    v = jnp.einsum("bsr,hrv->bshv", c, w["wuv"])
    if kind == "latent":
        q_i, k_i, w_i = index_parts(h, c_q, w, positions, theta=theta, rope=rope, eps=eps,
                                    index_heads=index_heads, index_dim=index_dim)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = []
    for at in range(0, x.shape[1], QUERY_BLOCK):
        rows = slice(at, at + QUERY_BLOCK)
        back = positions[:, rows, None] - positions[:, None, :]  # query - key [B, q, k]
        allowed = back >= 0
        if kind == "latent":
            allowed = chosen(index_scores(q_i[:, rows], k_i, w_i[:, rows]), allowed, index_topk)
        else:
            allowed = allowed & (back < window)
        scores = (jnp.einsum("bqhn,bkhn->bhqk", q_nope[:, rows], k_nope)
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe[:, rows], k_pe[:, :, 0])) * scale
        probs = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhv->bqhv", probs, v))
    attn = jnp.concatenate(out, axis=1) * jax.nn.sigmoid(h @ w["wg"])[..., None]
    return x + jnp.einsum("bthv,hve->bte", attn, w["wo"]), k_pe, c[:, :, None, :]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def dense_part(x, w, *, eps):
    return x + swiglu(rmsnorm(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"], w["w_down"])


def route(x, w, *, top_k, eps):
    """Normed input of the expert layer, each token's weight for every expert
    the router scores [B, T, E] (its score over the sum of its chosen scores
    where the expert is one of the ``top_k`` by score plus bias, zero
    elsewhere), and the chosen experts."""
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
        e = c["hidden_size"]
        self.n_layers = c["num_hidden_layers"]
        self.kinds = [LAYER_KINDS[t] for t in c["layer_types"][: self.n_layers]]
        self.dense_lead = min(c["first_k_dense_replace"], self.n_layers)
        self.held = c["n_routed_experts"]
        self.first = int(c.get("run", {}).get("experts_first", 0))
        rescale = bool(c["apply_mla_qkv_lora_rescale"])
        common = dict(eps=eps, window=c["sliding_window_size"], index_heads=c["index_n_heads"],
                      index_dim=c["index_head_dim"], index_topk=c["index_topk"])

        def sizes(pre):
            q_rank, rank = c[pre + "q_lora_rank"], c[pre + "kv_lora_rank"]
            return dict(rank=rank, nope=c[pre + "qk_nope_head_dim"],
                        rope=c[pre + "qk_rope_head_dim"],
                        theta=float(c["swa_rope_theta" if pre else "rope_theta"]),
                        s_q=math.sqrt(e / q_rank) if rescale else 1.0,
                        s_kv=math.sqrt(e / rank) if rescale else 1.0)

        self._attn = {
            kind: jax.jit(_highest(functools.partial(
                attention_part, kind=kind, **sizes(pre), **common)))
            for kind, pre in (("latent", ""), ("latent_sliding", "swa_"))
        }
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
        self.block = math.gcd(self.held, EXPERT_BLOCK)
        self._take_block = jax.jit(
            lambda leaf, i, at: jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False), at, self.block, 0)
            .astype(jnp.float32))

    # -- the served weights, a layer (or a block of experts) at a time --------

    def _attn_weights(self, params, l):
        """Layer ``l``'s attention leaves: its row in the stacks of its kind."""
        kind = self.kinds[l]
        row = self.kinds[:l].count(kind)
        names = {k: f"{k}_{kind}" for k in
                 ("wqa", "q_norm", "wq", "wkv_a", "kv_norm", "wuk", "wuv", "wo", "wg")}
        if kind == "latent":
            names.update({k: k for k in
                          ("index_wq", "index_wk", "index_k_norm", "index_k_bias", "index_ww")})
        w = {k: self._take(params[leaf], row) for k, leaf in names.items()}
        w["attn_norm"] = self._take(params["attn_norm"], l)
        return w

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
        for at in range(0, self.held, self.block):  # the experts held here, of the router's
            block = [self._take_block(params[k], row, at) for k in MOE_LEAVES]
            lo = self.first + at
            sums = [s + scale * self._block(h, *block, wts[..., lo:lo + self.block])
                    for s, (h, wts, _) in zip(sums, routed)]
        return [x + s for x, s in zip(xs, sums)], [np.asarray(idx[0]) for _, _, idx in routed]

    # -- what the comparison calls -------------------------------------------

    def forward_rows(self, params, rows, last, kv_rows=()) -> dict:
        """Full forward pass over rows of different lengths (1-D token
        arrays). Returns the logits of each row's ``last`` positions, and for
        the rows named in ``kv_rows`` what the program's ``k`` and ``v`` hold
        of every *full* layer: the pair (rotated shared key at the front of a
        row of whole tiles, normed latent), [L_full, T, 1, D] each. ``choices``
        holds, for each expert layer and row, the experts each token chose
        [T, k]."""
        rows = [np.asarray(r)[None] for r in rows]
        xs = [params["embed"][r].astype(jnp.float32) for r in rows]
        pos = [jnp.broadcast_to(jnp.arange(r.shape[1], dtype=jnp.int32), r.shape) for r in rows]
        kv = {i: ([], []) for i in kv_rows}
        choices = []
        for l in range(self.n_layers):
            w = self._attn_weights(params, l)
            kind = self.kinds[l]
            for i, p in enumerate(pos):
                xs[i], k_pe, c = self._attn[kind](xs[i], w, p)
                if i in kv and kind == "latent":
                    k_pe = np.asarray(k_pe[0])
                    kv[i][0].append(np.pad(k_pe, ((0, 0), (0, 0), (0, -k_pe.shape[-1] % KEY_TILE))))
                    kv[i][1].append(np.asarray(c[0]))
            del w
            xs, picked = self._feed_forward(params, l, xs)
            if picked is not None:
                choices.append(picked)
        norm = params["final_norm"].astype(jnp.float32)
        unembed = (params["embed"].T if self.config["tie_word_embeddings"]
                   else params["unembed"]).astype(jnp.float32)
        logits = [np.asarray(self._logits(x[:, -last:], norm, unembed))[0] for x in xs]
        return {"logits": logits, "choices": choices,
                "kv": {i: (np.stack(k), np.stack(v)) for i, (k, v) in kv.items()}}
