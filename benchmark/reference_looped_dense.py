"""The plain reference of family ``looped_dense``: a decoder whose one stack
of layers runs ``total_ut_steps`` times over a token with the same weights
(ByteDance Ouro, ``model_type: ouro``; "Scaling Latent Reasoning via Looped
Language Models", arXiv 2510.25741), in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``. No cache, no kernel, no batching,
no loop primitive; nothing from ``ray_tpu`` is imported.

    x = E[tokens]
    for t in 0 .. T-1:
      for l in 0 .. L-1:
        a = rms(x; g1[l]);  q, k, v = a Wq[l], a Wk[l], a Wv[l]     # no bias
        q, k = rope(q), rope(k)                                     # halves (i, i + D/2)
        row (t * L + l) of the keys and values <- k, v              # a row a pass and layer
        o = softmax(q k^T / sqrt(D) + causal) v                     # over this pass's own k, v
        x = x + rms(o Wo[l]; g2[l])                                 # a norm on the branch's way out
        m = rms(x; g3[l]);  f = (silu(m Wgate[l]) * (m Wup[l])) Wdown[l]
        x = x + rms(f; g4[l])
      x = rms(x; g_final)                  # inside the loop: pass t + 1 starts from the normed stream
      H[t] = x;  lam[t] = sigmoid(x . w_exit + b_exit)
    p[t] = lam[t] * prod_{s<t}(1 - lam[s]) for t < T-1;  p[T-1] = prod_{s<T-1}(1 - lam[s])
    exit = the first t with p[0] + .. + p[t] >= early_exit_threshold, else T-1    # a position's own
    logits = H[exit] W_head

It reads the published keys of the configuration file and a parameter tree of
the layout in ``benchmark/families/looped_dense.py`` (the program's own names:
``attn_norm`` g1, ``attn_out_norm`` g2, ``mlp_norm`` g3, ``mlp_out_norm`` g4,
``exit_w``, ``exit_b``). Weights arrive in the type they are served in and are
upcast a layer at a time, once a pass, for all rows: what is resident beside
an engine that fills the chip is one layer in float32 (205 MB at the published
sizes) and the rows' streams."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("wq_full", "wk", "wv", "wo_full", "attn_norm", "attn_out_norm", "mlp_norm",
                "mlp_out_norm", "w_gate", "w_up", "w_down")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [T, H, D], positions [T]; the rotate-half form of the published code."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer_parts(x, w, heads, kv_heads, theta, eps):
    """One layer of one pass on one sequence x [T, E], causal over T. Returns
    the layer's output and its keys (rotated) and values [T, KV, D]: what a
    serving program keeps in this pass's row of its cache."""
    positions = jnp.arange(x.shape[0])
    a = rmsnorm(x, w["attn_norm"], eps)
    q = rope(jnp.einsum("te,ehd->thd", a, w["wq_full"]), positions, theta)
    keys = rope(jnp.einsum("te,ehd->thd", a, w["wk"]), positions, theta)
    values = jnp.einsum("te,ehd->thd", a, w["wv"])
    k = jnp.repeat(keys, heads // kv_heads, axis=1)
    v = jnp.repeat(values, heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    causal = positions[None, :, None] >= positions[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v)
    x = x + rmsnorm(jnp.einsum("thd,hde->te", o, w["wo_full"]), w["attn_out_norm"], eps)
    m = rmsnorm(x, w["mlp_norm"], eps)
    f = (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]
    return x + rmsnorm(f, w["mlp_out_norm"], eps), keys, values


def pass_end(x, final_norm, exit_w, exit_b, eps):
    """The end of a pass: the stream under the final norm, and the gate's
    chance that a position stops here."""
    x = rmsnorm(x, final_norm, eps)
    return x, jax.nn.sigmoid(x @ exit_w + exit_b[0])


def exit_rule(lam, threshold: float):
    """lam [T, ..] float32 -> (the exit distribution p [T, ..], the pass each
    position's head reads [..]): ``p[t] = lam[t] prod(1 - lam[s], s < t)``,
    the last pass taking what is left, summed in float32 pass by pass; the
    first pass at which the sum reaches ``threshold``, else the last."""
    lam = np.asarray(lam, np.float32)
    T = lam.shape[0]
    alive, cum = np.ones(lam.shape[1:], np.float32), np.zeros(lam.shape[1:], np.float32)
    pdf, chosen = [], np.full(lam.shape[1:], T - 1, np.int32)
    done = np.zeros(lam.shape[1:], bool)
    for t in range(T):
        share = alive if t == T - 1 else lam[t] * alive
        cum = cum + share
        here = ~done & (cum >= np.float32(threshold))
        chosen = np.where(here, t, chosen)
        done |= here
        alive = alive * (np.float32(1.0) - lam[t])
        pdf.append(share)
    return np.stack(pdf), chosen


class Reference:
    """Holds the jitted pieces for one configuration, on one device."""

    def __init__(self, config: dict, devices=None):
        self.config = config
        self.device = list(devices or jax.local_devices())[0]
        self.passes = int(config["total_ut_steps"])
        self.threshold = float(config["early_exit_threshold"])
        eps = float(config["rms_norm_eps"])
        kw = dict(heads=config["num_attention_heads"], kv_heads=config["num_key_value_heads"],
                  theta=float(config["rope_theta"]), eps=eps)
        self._layer_parts = jax.jit(_highest(functools.partial(layer_parts, **kw)))
        self._pass_end = jax.jit(_highest(functools.partial(pass_end, eps=eps)))
        self._head = jax.jit(_highest(lambda x, unembed: x @ unembed))
        self._upcast = jax.jit(lambda leaf: leaf.astype(jnp.float32))
        self._take = jax.jit(
            lambda leaf, i: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
            .astype(jnp.float32))

    def _layer_weights(self, params, index):
        return {k: self._take(params[k], index) for k in LAYER_LEAVES}

    def forward_rows(self, params, rows, last=None, kv_rows=()) -> dict:
        """The whole pass over rows of any lengths (1-D token arrays), every
        layer's weights upcast once a pass for all of them. Returns, a row:
        ``logits`` of its ``last`` positions (all of them where None)
        [last, V], every pass's normed stream ``hidden`` [T, last, E], the exit
        distribution ``pdf`` [T, last] and the pass picked ``exit`` [last]; and
        for the rows named in ``kv_rows`` the keys (rotated) and values of
        every pass and layer, pass-major [T * L, positions, KV, D]: the order
        of the engine's cache rows."""
        with jax.default_device(self.device):
            return self._forward_rows(params, rows, last, kv_rows)

    def _forward_rows(self, params, rows, last, kv_rows) -> dict:
        rows = [np.asarray(r) for r in rows]
        table = self._upcast(params["embed"])
        xs = [table[jnp.asarray(r)] for r in rows]
        del table
        n_layers = self.config["num_hidden_layers"]
        final_norm, exit_w, exit_b = (self._upcast(params[k]) for k in (
            "final_norm", "exit_w", "exit_b"))
        kv = {i: ([], []) for i in kv_rows}
        hidden, lam = [[] for _ in rows], [[] for _ in rows]
        for _ in range(self.passes):
            for index in range(n_layers):
                w = self._layer_weights(params, index)
                for i in range(len(rows)):
                    xs[i], k, v = self._layer_parts(xs[i], w)
                    if i in kv:
                        kv[i][0].append(np.asarray(k))
                        kv[i][1].append(np.asarray(v))
            for i in range(len(rows)):
                xs[i], gate = self._pass_end(xs[i], final_norm, exit_w, exit_b)
                at = slice(None) if last is None else slice(-last, None)
                hidden[i].append(np.asarray(xs[i][at]))
                lam[i].append(np.asarray(gate[at]))
        unembed = self._upcast(params["unembed"])
        out = {"logits": [], "hidden": [], "pdf": [], "exit": [],
               "kv": {i: (np.stack(k), np.stack(v)) for i, (k, v) in kv.items()}}
        for h, g in zip(hidden, lam):
            h = np.stack(h)  # [T, last, E]
            pdf, chosen = exit_rule(np.stack(g), self.threshold)
            picked = np.take_along_axis(h, chosen[None, :, None], axis=0)[0]
            out["logits"].append(np.asarray(self._head(jnp.asarray(picked), unembed)))
            out["hidden"].append(h)
            out["pdf"].append(pdf)
            out["exit"].append(chosen)
        return out


def rel_rms(got, want) -> float:
    """Root-mean-square of the difference over that of the reference."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))
