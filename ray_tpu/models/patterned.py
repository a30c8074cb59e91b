"""Decoders whose layers are not alike: full and sliding-window attention
layers with their own query-head counts and rotary settings, a per-head gate
on the attention output, and dense and routed-expert feed-forwards, mixed by a
per-layer pattern (``LlamaConfig.layer_types``, ``heads_per_layer``,
``mlp_types``; poolside Laguna-XS.2 is the published instance,
``LlamaConfig.laguna_xs2``).

``models/llama.py`` stays the entry point: its ``forward``, ``prefill``,
``decode_step`` and ``init_kv_cache`` hand on to this module whenever
``cfg.layer_types`` is set, so the engine and everything else that serves or
checks a model calls the same four functions for both.

Parameters are one flat ``name -> array`` dict, as ``models/llama.py`` has
it. Leaves whose shape every layer shares (``wk``, ``wv``, the two norms) are
stacked over all layers; the others by the group their shape follows:
``wq_full`` / ``wq_sliding`` (and ``wo_``, ``wg_``) by attention kind,
``w_gate`` .. over the dense feed-forward layers, ``moe_*`` over the expert
layers. The layer stack is traced as its leading layers, then one body of a
whole period under a ``fori_loop`` (the published 40 layers: layer 0, nine
times [sliding, sliding, sliding, full], three more sliding), never one body
a layer.

The cache is ``init_kv_cache``'s: ``[L, B, K, S, D]``, every layer a whole
stripe. A sliding layer reads only its window from it (a slice of at most
``window + T`` positions a row, rounded to the tiling), so the window saves
bandwidth now and memory only once a layer may own a shorter stripe."""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ray_tpu.models.llama import (
    MOE_STATS,
    LlamaConfig,
    _cache_writer,
    _dense_ffn,
    _embed_lookup,
    _grouped_attention,
    _moe_decode_ffn,
    _moe_shapes,
    _project_logits,
    _ride_stats,
    _rmsnorm,
    scope,
)

# a window's first position in the stripe is rounded down to a multiple of
# this, so that the slice starts on a tile of the cache's position axis
_WINDOW_ALIGN = 128
_SCOPE_OF_KIND = {"full": "global", "sliding": "window"}


# ------------------------------------------------------------------ the plan


@dataclasses.dataclass(frozen=True)
class Plan:
    """How the layer stack is traced: ``lead`` layers one by one, ``reps``
    times a period of ``period`` layers in one loop body, then the rest.
    ``attn_index[l]`` / ``mlp_index[l]``: layer l's row in the stack of its
    attention kind / feed-forward kind."""

    lead: int
    period: int
    reps: int
    attn_index: tuple
    mlp_index: tuple

    @property
    def tail_from(self) -> int:
        return self.lead + self.period * self.reps


@functools.lru_cache(maxsize=None)
def plan(cfg: LlamaConfig) -> Plan:
    L = cfg.n_layers
    kinds = list(zip(cfg.layer_types, cfg.heads_per_layer, cfg.mlp_types))
    for t, h, m in kinds:
        if t not in _SCOPE_OF_KIND or m not in ("dense", "sparse"):
            raise ValueError(f"unknown layer kind ({t!r}, {m!r})")
        if h % cfg.n_kv_heads:
            raise ValueError(f"{h} query heads over {cfg.n_kv_heads} key-value heads")
    for t in _SCOPE_OF_KIND:
        if len({h for kt, h, _ in kinds if kt == t}) > 1:
            raise ValueError(f"{t} attention layers differ in their query heads")
    if "sliding" in cfg.layer_types and cfg.sliding_window <= 0:
        raise ValueError("sliding layers need sliding_window")
    if "sparse" in cfg.mlp_types and not cfg.moe_experts:
        raise ValueError("sparse layers need moe_experts")
    # the split that traces the fewest layer bodies
    best = None
    for lead in range(L):
        for period in range(1, L - lead + 1):
            rest = kinds[lead:]
            if any(rest[i] != rest[i % period] for i in range(len(rest))):
                continue
            reps = len(rest) // period
            bodies = lead + period + len(rest) % period
            if best is None or bodies < best[0]:
                best = (bodies, lead, period, reps)
    _, lead, period, reps = best
    if reps == 1:  # nothing repeats: every layer its own body, no loop
        lead, period, reps = L, 1, 0
    seen: dict = {}
    attn_index, mlp_index = [], []
    for t, _, m in kinds:
        attn_index.append(seen.setdefault(t, 0))
        mlp_index.append(seen.setdefault(m, 0))
        seen[t] += 1
        seen[m] += 1
    return Plan(lead, period, reps, tuple(attn_index), tuple(mlp_index))


def param_shapes(cfg: LlamaConfig) -> dict[str, tuple]:
    plan(cfg)  # validates the pattern
    e, v, L = cfg.d_model, cfg.vocab_size, cfg.n_layers
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    shapes = {
        "embed": (v, e),
        "final_norm": (e,),
        "wk": (L, e, kv, hd),
        "wv": (L, e, kv, hd),
        "attn_norm": (L, e),
        "mlp_norm": (L, e),
    }
    for kind in _SCOPE_OF_KIND:
        n = cfg.layer_types.count(kind)
        if not n:
            continue
        h = cfg.heads_per_layer[cfg.layer_types.index(kind)]
        shapes["wq_" + kind] = (n, e, h, hd)
        shapes["wo_" + kind] = (n, h, hd, e)
        if cfg.attn_gate:
            shapes["wg_" + kind] = (n, e, h)
    n_dense = cfg.mlp_types.count("dense")
    if n_dense:
        f = cfg.d_ff
        shapes.update({"w_gate": (n_dense, e, f), "w_up": (n_dense, e, f),
                       "w_down": (n_dense, f, e)})
    if n_dense < L:
        shapes.update(_moe_shapes(cfg, L - n_dense))
    if not cfg.tie_embeddings:
        shapes["unembed"] = (e, v)
    return shapes


# --------------------------------------------------------------------- rope


def rope_inv_freq(cfg: LlamaConfig, kind: str):
    """(inverse frequencies float32 [rotated / 2], factor on cos and sin) of
    one attention kind. A sliding layer rotates the whole head at
    ``rope_theta_sliding``; a full layer the first ``rope_partial`` of it at
    ``rope_theta``, with YaRN's blend of interpolated and extrapolated
    frequencies where ``yarn_factor`` is set (as transformers'
    ``_compute_yarn_parameters`` computes them over the rotated dims)."""
    if kind == "sliding":
        rot, theta = cfg.head_dim, cfg.rope_theta_sliding
    else:
        rot, theta = int(cfg.head_dim * cfg.rope_partial), cfg.rope_theta
    inv = (1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot)).astype(np.float32)
    if kind == "sliding" or not cfg.yarn_factor:
        return inv, 1.0

    def correction_dim(rotations):
        return (rot * math.log(cfg.yarn_original_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    extrapolated = 1.0 - ramp  # share of the unscaled frequency, by dim
    inv = inv / np.float32(cfg.yarn_factor) * (1 - extrapolated) + inv * extrapolated
    return inv.astype(np.float32), cfg.yarn_attention_factor


def _rope(x, positions, inv_freq, factor):
    """x: [B, T, H, D], positions: [B, T]. Rotates the first
    ``2 * len(inv_freq)`` dims of each head (halves paired, as
    ``models/llama.py _rope``) and passes the rest through."""
    rot = 2 * len(inv_freq)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, rot/2]
    cos = (jnp.cos(angles) * factor)[:, :, None, :]
    sin = (jnp.sin(angles) * factor)[:, :, None, :]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], axis=-1)


# ------------------------------------------------------------- layer pieces


class _Layer:
    """One layer's static kind and its (static or traced) indices."""

    def __init__(self, cfg: LlamaConfig, l_static: int, l, attn_i, mlp_i):
        self.kind = cfg.layer_types[l_static]
        self.sparse = cfg.mlp_types[l_static] == "sparse"
        self.l, self.attn_i, self.mlp_i = l, attn_i, mlp_i


def _qkv(params, lay: _Layer, h, positions, cfg: LlamaConfig):
    inv_freq, factor = rope_inv_freq(cfg, lay.kind)
    with scope("attn_qkv"):
        q = jnp.einsum("bte,ehd->bthd", h, params["wq_" + lay.kind][lay.attn_i])
        k = jnp.einsum("bte,ehd->bthd", h, params["wk"][lay.l])
        v = jnp.einsum("bte,ehd->bthd", h, params["wv"][lay.l])
        q = _rope(q, positions, inv_freq, factor)
        k = _rope(k, positions, inv_freq, factor)
    return q, k, v


def _attn_out(params, lay: _Layer, x, h, attn, cfg: LlamaConfig):
    with scope("attn_out"):
        if cfg.attn_gate:
            with scope("gate"):
                gate = jax.nn.sigmoid(jnp.einsum(
                    "bte,eh->bth", h, params["wg_" + lay.kind][lay.attn_i],
                    preferred_element_type=jnp.float32,
                ))
                attn = (attn * gate[..., None]).astype(attn.dtype)
        return x + jnp.einsum("bthd,hde->bte", attn, params["wo_" + lay.kind][lay.attn_i])


def _feed_forward(params, lay: _Layer, x, cfg: LlamaConfig):
    """x + feed-forward(norm(x)), and the layer's routing counts (zeros for a
    dense layer)."""
    h = _rmsnorm(x, params["mlp_norm"][lay.l], cfg.rms_eps, cfg.fused_rmsnorm)
    if lay.sparse:
        with scope("moe_ffn"):
            y, stats = _moe_decode_ffn(params, lay.mlp_i, h, cfg)
            return x + y, stats
    with scope("ffn"):
        x = x + _dense_ffn(h, lambda name: params[name][lay.mlp_i])
    return x, jnp.zeros((len(MOE_STATS),), jnp.int32)


def _run_layers(cfg: LlamaConfig, layer_fn, carry):
    """``carry = layer_fn(lay, carry)`` over the stack as ``plan`` splits it."""
    pl = plan(cfg)

    def static(l):
        return _Layer(cfg, l, l, pl.attn_index[l], pl.mlp_index[l])

    for l in range(pl.lead):
        carry = layer_fn(static(l), carry)
    if pl.reps:
        first = [pl.lead + j for j in range(pl.period)]
        # a kind's rows advance by its count in one period
        step_attn = [sum(cfg.layer_types[m] == cfg.layer_types[l] for m in first) for l in first]
        step_mlp = [sum(cfg.mlp_types[m] == cfg.mlp_types[l] for m in first) for l in first]

        def body(i, carry):
            for j, l in enumerate(first):
                lay = _Layer(
                    cfg, l, l + i * pl.period,
                    pl.attn_index[l] + i * step_attn[j],
                    pl.mlp_index[l] + i * step_mlp[j],
                )
                carry = layer_fn(lay, carry)
            return carry

        carry = jax.lax.fori_loop(0, pl.reps, body, carry)
    for l in range(pl.tail_from, cfg.n_layers):
        carry = layer_fn(static(l), carry)
    return carry


# ------------------------------------------------------------ whole sequence


def forward_hidden(params, tokens, cfg: LlamaConfig, mesh: Optional[Mesh] = None,
                   positions=None):
    """tokens: [B, T] -> final hidden states [B, T, d_model], every expert
    layer dropless on this device (``_moe_decode_ffn``). One device: a mesh
    with an axis over 1 is refused (training this model over ``ep`` is not
    here yet)."""
    if mesh is not None and any(s > 1 for s in mesh.shape.values()):
        raise NotImplementedError("models/patterned.py runs on one device")
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    x = _embed_lookup(params["embed"], tokens, cfg, None)
    back = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]  # query - key
    masks = {
        "full": jnp.broadcast_to(back >= 0, (B, T, T)),
        "sliding": jnp.broadcast_to((back >= 0) & (back < cfg.sliding_window), (B, T, T)),
    }

    def layer(lay: _Layer, x):
        h = _rmsnorm(x, params["attn_norm"][lay.l], cfg.rms_eps, cfg.fused_rmsnorm)
        q, k, v = _qkv(params, lay, h, positions, cfg)
        with scope("attn_core"), scope(_SCOPE_OF_KIND[lay.kind]):
            attn = _grouped_attention(
                q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), masks[lay.kind]
            )
        x = _attn_out(params, lay, x, h, attn, cfg)
        return _feed_forward(params, lay, x, cfg)[0]

    if cfg.remat:
        plain = layer
        layer = lambda lay, x: jax.checkpoint(lambda y: plain(lay, y))(x)  # noqa: E731
    x = _run_layers(cfg, layer, x)
    return _rmsnorm(x, params["final_norm"], cfg.rms_eps, cfg.fused_rmsnorm)


# ------------------------------------------------------------ through a cache


def _window_slice(c_all, l, first, width: int):
    """Row b's positions ``[first[b], first[b] + width)`` of layer ``l``:
    [B, K, width, D], one gather over the rows."""
    _, B, K, _, D = c_all.shape

    def row(b, at):
        return jax.lax.dynamic_slice(c_all, (l, b, 0, at, 0), (1, 1, K, width, D))[0, 0]

    return jax.vmap(row)(jnp.arange(B), first)


def decode_forward(params, cache, tokens, positions, cfg: LlamaConfig, valid=None,
                   loras=None, with_logits: bool = True, logits_at=None, start_pos=None):
    """``models/llama.py _decode_forward`` for a patterned model: the same
    arguments and results, the same cache write. Row b's positions are
    consecutive from ``positions[b, 0]`` (``prefill`` and ``decode_step``
    make no others), which is what lets a sliding layer cut its window out
    of the stripe: the ``window + T - 1`` positions its queries can see,
    from a start rounded down to ``_WINDOW_ALIGN``; where that is the whole
    stripe, the stripe under the window's mask."""
    if loras is not None:
        raise NotImplementedError("LoRA adapters over layers that are not alike")
    B, T = tokens.shape
    S = cache["k"].shape[3]
    W, A = cfg.sliding_window, _WINDOW_ALIGN
    with scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    write = _cache_writer(cfg, S, positions, valid, start_pos)

    qpos = positions[:, :, None]  # [B, T, 1]
    slot = jnp.arange(S)[None, None, :]
    span = -(-(W + T - 1 + A - 1) // A) * A  # covers the window from an aligned start
    whole = {"full": True, "sliding": span >= S}
    if not whole["sliding"]:
        first = jnp.clip((positions[:, 0] - W + 1) // A * A, 0, S - span)  # [B]
        wslot = first[:, None, None] + jnp.arange(span)[None, None, :]
        window_mask = (wslot <= qpos) & (qpos - wslot < W)
    masks = {"full": slot <= qpos, "sliding": (slot <= qpos) & (qpos - slot < W)}

    def layer(lay: _Layer, carry):
        x, ck_all, cv_all, stats = carry
        h = _rmsnorm(x, params["attn_norm"][lay.l], cfg.rms_eps, cfg.fused_rmsnorm)
        q, k, v = _qkv(params, lay, h, positions, cfg)
        with scope("kv_write"):
            ck_all = write(ck_all, k.transpose(0, 2, 1, 3), lay.l)
            cv_all = write(cv_all, v.transpose(0, 2, 1, 3), lay.l)
        with scope("attn_core"), scope(_SCOPE_OF_KIND[lay.kind]):
            if whole[lay.kind]:
                attn = _grouped_attention(q, ck_all[lay.l], cv_all[lay.l], masks[lay.kind])
            else:
                attn = _grouped_attention(
                    q, _window_slice(ck_all, lay.l, first, span),
                    _window_slice(cv_all, lay.l, first, span), window_mask,
                )
        x = _attn_out(params, lay, x, h, attn, cfg)
        x, layer_stats = _feed_forward(params, lay, x, cfg)
        return x, ck_all, cv_all, stats + layer_stats

    stats0 = jnp.zeros((len(MOE_STATS),), jnp.int32)
    x, new_k, new_v, stats = _run_layers(cfg, layer, (x, cache["k"], cache["v"], stats0))
    new_cache = {"k": new_k, "v": new_v, "length": cache["length"] + T}
    _ride_stats(cache, new_cache, [stats] if cfg.moe_experts else [])
    if not with_logits:
        return None, new_cache
    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    x = _rmsnorm(x, params["final_norm"], cfg.rms_eps, cfg.fused_rmsnorm)
    return _project_logits(x, params, cfg, None), new_cache
