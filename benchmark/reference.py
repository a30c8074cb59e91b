"""The plain reference: a pre-norm decoder with grouped-query attention,
rotary positions and a SwiGLU feed-forward, as the Mistral-7B-v0.3 model card
and its ``modeling_mistral.py`` describe it, in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``. No kernels, no cache, no scan, no
remat, no fused loss; nothing from ``ray_tpu`` is imported.

It reads the published keys of the configuration file and a parameter tree of
the layout in ``benchmark/weights.py``. Weights arrive in the type they are
served in and are upcast one layer at a time (3.76 B parameters in float32
would be 15 GB). Gradients are taken layer by layer with ``jax.vjp`` and only
their running sum of squares is kept.

Departures from the published code: none in the mathematics. The sliding
window is ``null`` in v0.3 and absent here. Sequences are spread over the
local devices a batch row at a time (plain data parallelism: a four-chip host
holds four rows at once), which changes no result beyond summation order."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "attn_norm", "mlp_norm", "w_gate", "w_up", "w_down")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [B, T, H, D], positions [B, T]; the rotate-half form of the
    published code."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer_parts(x, w, positions, heads, kv_heads, theta, eps):
    """One decoder layer on x [B, T, E] with causal attention over T. Returns
    the layer's output and its keys (rotated) and values [B, T, KV, D], which
    are what a serving program keeps in its cache."""
    h = rmsnorm(x, w["attn_norm"], eps)
    q = rope(jnp.einsum("bte,ehd->bthd", h, w["wq"]), positions, theta)
    keys = rope(jnp.einsum("bte,ehd->bthd", h, w["wk"]), positions, theta)
    values = jnp.einsum("bte,ehd->bthd", h, w["wv"])
    k = jnp.repeat(keys, heads // kv_heads, axis=2)
    v = jnp.repeat(values, heads // kv_heads, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    causal = positions[:, None, :, None] >= positions[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    x = x + jnp.einsum("bthd,hde->bte", attn, w["wo"])
    h = rmsnorm(x, w["mlp_norm"], eps)
    ff = jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])
    return x + ff @ w["w_down"], keys, values


def layer(x, w, positions, **kw):
    return layer_parts(x, w, positions, **kw)[0]


def head_logits(x, final_norm, unembed, eps):
    return rmsnorm(x, final_norm, eps) @ unembed


def head_loss_sum(x, final_norm, unembed, labels, eps):
    logp = jax.nn.log_softmax(head_logits(x, final_norm, unembed, eps), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).sum()


class Reference:
    """Holds the jitted pieces for one configuration and set of devices."""

    def __init__(self, config: dict, devices=None):
        self.config = config
        devices = list(devices or jax.local_devices())
        self.n_dev = len(devices)
        mesh = Mesh(np.array(devices), ("d",))
        self.rows = NamedSharding(mesh, P("d"))
        self.whole = NamedSharding(mesh, P())
        kw = dict(
            heads=config["num_attention_heads"], kv_heads=config["num_key_value_heads"],
            theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        )
        eps = kw["eps"]
        layer_fn = functools.partial(layer, **kw)
        self._layer = jax.jit(_highest(layer_fn))
        self._layer_parts = jax.jit(_highest(functools.partial(layer_parts, **kw)))

        def layer_back(x, w, positions, dy):
            _, vjp = jax.vjp(lambda x, w: layer_fn(x, w, positions), x, w)
            return vjp(dy)

        self._layer_back = jax.jit(_highest(layer_back))
        self._logits = jax.jit(_highest(functools.partial(head_logits, eps=eps)))
        self._head = jax.jit(_highest(jax.value_and_grad(
            functools.partial(head_loss_sum, eps=eps), argnums=(0, 1, 2))))
        self._sumsq = jax.jit(lambda tree: sum(
            jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
        # weights sharded over the chips are gathered by a compiled program
        # (over the chips' links), not resharded through the host: the latter
        # took 85 s a seed for 16 layers on four chips
        self._upcast = jax.jit(lambda leaf: leaf.astype(jnp.float32), out_shardings=self.whole)
        self._take = jax.jit(
            lambda leaf, i: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
            .astype(jnp.float32),
            out_shardings=self.whole,
        )

    # -- moving the served weights in, a layer at a time ---------------------

    def _f32(self, leaf):
        return self._upcast(leaf)

    def _layer_weights(self, params, index):
        return {k: self._take(params[k], index) for k in LAYER_LEAVES}

    def _unembed(self, params):
        if self.config["tie_word_embeddings"]:
            return self._f32(params["embed"]).T
        return self._f32(params["unembed"])

    def _positions(self, tokens):
        pos = np.broadcast_to(np.arange(tokens.shape[1], dtype=np.int32), tokens.shape)
        return jax.device_put(pos, self.rows)

    def _embed(self, table, tokens):
        return jax.device_put(table[tokens], self.rows)

    def _groups(self, tokens):
        """Rows of the batch, ``n_dev`` at a time (padded rows are dropped
        by the caller: the batch sizes in use divide evenly)."""
        rows = tokens.shape[0]
        if rows % self.n_dev:
            raise ValueError(f"{rows} rows over {self.n_dev} devices")
        return [tokens[i:i + self.n_dev] for i in range(0, rows, self.n_dev)]

    # -- what the comparison calls -------------------------------------------

    def logits(self, params, tokens, last=None) -> np.ndarray:
        """Full forward pass: tokens [B, T] -> logits [B, T, V] float32, or
        those of the ``last`` positions only (every position still goes
        through every layer)."""
        tokens = np.asarray(tokens)
        n_layers = self.config["num_hidden_layers"]
        out = []
        groups = self._groups(tokens)
        table = self._f32(params["embed"])
        xs = [self._embed(table, g) for g in groups]
        del table
        pos = [self._positions(g) for g in groups]
        for index in range(n_layers):
            w = self._layer_weights(params, index)
            xs = [self._layer(x, w, p) for x, p in zip(xs, pos)]
        final_norm, unembed = self._f32(params["final_norm"]), self._unembed(params)
        for x in xs:
            x = x if last is None else x[:, -last:]
            out.append(np.asarray(self._logits(x, final_norm, unembed)))
        return np.concatenate(out, axis=0)

    def forward_rows(self, params, rows, last, kv_rows=()) -> dict:
        """Full forward pass over rows of different lengths (1-D token
        arrays), every layer's weights upcast once for all of them. Returns
        the logits of each row's ``last`` positions, and for the rows named
        in ``kv_rows`` every layer's keys (rotated) and values
        [L, T, KV, D]."""
        if self.n_dev != 1:
            raise ValueError("rows of different lengths go through one device")
        rows = [np.asarray(r)[None] for r in rows]
        table = self._f32(params["embed"])
        xs = [self._embed(table, r) for r in rows]
        del table
        pos = [self._positions(r) for r in rows]
        kv = {i: ([], []) for i in kv_rows}
        for index in range(self.config["num_hidden_layers"]):
            w = self._layer_weights(params, index)
            for i, p in enumerate(pos):
                xs[i], k, v = self._layer_parts(xs[i], w, p)
                if i in kv:
                    kv[i][0].append(np.asarray(k[0]))
                    kv[i][1].append(np.asarray(v[0]))
        final_norm, unembed = self._f32(params["final_norm"]), self._unembed(params)
        logits = [np.asarray(self._logits(x[:, -last:], final_norm, unembed))[0] for x in xs]
        return {"logits": logits, "kv": {i: (np.stack(k), np.stack(v)) for i, (k, v) in kv.items()}}

    def loss_and_grad_norm(self, params, tokens, visit=None):
        """Mean next-token loss over tokens [B, T+1] and the norm of its
        gradient over every parameter. ``visit(name, layer, grad)`` is
        handed each parameter's float32 gradient on the device as it is
        made (``layer`` is the index into a stacked leaf, or None), for a
        caller that compares gradients and not only their norm."""
        visit = visit or (lambda name, layer, grad: None)
        tokens = np.asarray(tokens)
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        n_layers = self.config["num_hidden_layers"]
        n_tokens = labels.size
        groups = self._groups(inputs)
        label_groups = [jax.device_put(g, self.rows) for g in self._groups(labels)]
        pos = [self._positions(g) for g in groups]

        table = self._f32(params["embed"])
        acts = [[self._embed(table, g) for g in groups]]
        del table
        for index in range(n_layers):
            w = self._layer_weights(params, index)
            acts.append([self._layer(x, w, p) for x, p in zip(acts[-1], pos)])

        final_norm, unembed = self._f32(params["final_norm"]), self._unembed(params)
        loss_sum, dys, head_grads = 0.0, [], None
        for x, lab in zip(acts[-1], label_groups):
            lsum, (dx, dnorm, dunembed) = self._head(x, final_norm, unembed, lab)
            loss_sum += float(lsum)
            dys.append(dx / n_tokens)
            g = (dnorm / n_tokens, dunembed / n_tokens)
            head_grads = g if head_grads is None else self._add(head_grads, g)
        sumsq = 0.0 if self.config["tie_word_embeddings"] else float(self._sumsq(head_grads[1]))
        sumsq += float(self._sumsq(head_grads[0]))
        visit("final_norm", None, head_grads[0])
        if not self.config["tie_word_embeddings"]:
            visit("unembed", None, head_grads[1])
        tied_dunembed = head_grads[1] if self.config["tie_word_embeddings"] else None
        del head_grads, unembed

        for index in reversed(range(n_layers)):
            w = self._layer_weights(params, index)
            dw_total, new_dys = None, []
            for x, p, dy in zip(acts[index], pos, dys):
                dx, dw = self._layer_back(x, w, p, dy)
                new_dys.append(dx)
                dw_total = dw if dw_total is None else self._add(dw_total, dw)
            sumsq += float(self._sumsq(dw_total))
            for name in LAYER_LEAVES:
                visit(name, index, dw_total[name])
            dys = new_dys
            acts.pop()
            del dw_total

        # the embedding: each row's gradient is the sum over its occurrences
        v, e = self.config["vocab_size"], self.config["hidden_size"]
        dembed = jnp.zeros((v, e), jnp.float32)
        for g, dx in zip(groups, dys):
            dembed = dembed.at[jnp.asarray(g)].add(jax.device_put(dx, self.whole))
        if tied_dunembed is not None:
            dembed = dembed + tied_dunembed.T
        sumsq += float(self._sumsq(dembed))
        visit("embed", None, dembed)
        return loss_sum / n_tokens, float(np.sqrt(sumsq))


def rel_rms(got, want) -> float:
    """Root-mean-square of the difference over that of the reference."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))
