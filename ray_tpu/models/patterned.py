"""A decoder as a pattern of layers, and the one body that carries tokens
through the cache for every model the engine serves.

``plan(cfg)`` reads a config as ``lead`` layers, then ``reps`` times a period
of ``period`` layers, then the rest. A decoder whose layers are alike
(``cfg.layer_types == ()``) is the period-1 pattern: no lead, one layer
``n_layers`` times, full attention with ``n_heads`` query heads, dense or (with
``moe_experts``) routed-expert feed-forwards, its projections in ``wq`` /
``wo``. One whose layers are not alike mixes full and sliding-window attention
layers with their own query-head counts and rotary settings, a per-head gate
on the attention output, and dense and expert feed-forwards, by
``cfg.layer_types``, ``heads_per_layer`` and ``mlp_types`` (poolside
Laguna-XS.2 is the published instance, ``LlamaConfig.laguna_xs2``). A third
attention kind, ``latent`` (DeepSeek-V3's; kakaocorp Kanana-2-30B-A3B is the
published instance, ``LlamaConfig.kanana2_30b_a3b``), caches one normed
latent and one rotated key a token for all heads; it does not mix with the
other two, because the cache has one shape (``init_kv_cache``). Two kinds of
mixer keep a state a sequence and no keys, whatever its length
(``STATE_MIXERS``): ``ssm`` (Mamba-2; NVIDIA Nemotron-3-Super, whose blocks
are a mixer or a feed-forward alone: kinds ``none``) and ``kda`` (the delta
rule with a decay a channel; upstage Solar-Open2, ``ops/kda.py``). A kind may
hold both a stripe and state leaves: ``cca`` (compressed convolutional
attention; Zyphra ZAYA1-8B, ``LlamaConfig.zaya1_8b``) is grouped-query
attention over stripes like a full layer's, whose queries and keys pass two
short convolutions over the sequence and half of whose value heads are the
token before's, so a slot carries the convolutions' last inputs and the last
shifted value half beside its stripes (``STRIPE_STATE``, ``_cca_qkv``). That
model's expert layers are routed by a small MLP with a stream of its own
through the depth (``moe_router_hidden``, ``_mlp_route``) and its branches
join the stream under learned scales (``residual_scales``, ``_joined``).

``models/llama.py`` is the entry point and imports this module, never the
other way round: its ``prefill`` and ``decode_step`` call ``decode_forward``
here for every config, its ``forward_hidden`` hands a model whose layers are
not alike to ``forward_hidden`` here (one device; the whole-sequence path of a
uniform model, with its scan, remat policy, pipeline and mesh constraints,
stays there), and the pieces both need (``_rmsnorm``, ``_project_logits``,
``_embed_lookup``, ``_shared_expert``, the parameter shapes) live here.

Parameters are one flat ``name -> array`` dict. Leaves whose shape every
layer shares (``wk``, ``wv``, the two norms) are stacked over all layers; the
others by the group their shape follows: the query and output projections by
attention kind (``wq_full`` / ``wq_sliding``, ``wo_``, ``wg_``; plain ``wq`` /
``wo`` where the layers are alike), ``w_gate`` .. over the dense feed-forward
layers, ``moe_*`` over the expert layers. The stack is traced as its leading
layers, then one body of a whole period under a ``fori_loop`` (a uniform
model: one layer body; the published Laguna's 40 layers: layer 0, nine times
[sliding, sliding, sliding, full], three more sliding), never one body a
layer.

The cache is ``init_kv_cache``'s: ``[L, B, K, S, D]``, every layer a whole
stripe. A decode step reads a row's stripe between the row's own bounds, whole
blocks of it (``ops/decode_attention.py``); a prompt's chunk reads the whole
stripe, and in a sliding layer only its window (a slice of at most
``window + T`` positions a row, rounded to the tiling). So the window saves
bandwidth now and memory only once a layer may own a shorter stripe."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ray_tpu.ops import latent_chunk_attention as chunk_kernel
from ray_tpu.ops.cache_write import rows_in_stripe, takes_cache, write_rows_in_place
from ray_tpu.ops.decode_attention import (
    decode_attention,
    latent_decode_attention,
    sparse_latent_decode_attention,
    takes_heads_of,
    takes_stripe,
)
from ray_tpu.parallel.mesh import with_sharding

# ``jax.named_scope`` names, one vocabulary for the train step and the
# engine's programs (which add ``kv_write``, ``sampling``, ``prefix_seed``):
# embed, norm, attn_qkv (projections and rope), attn_core (the kernel; in
# decode, attention over the cache), attn_out, ffn, moe_ffn, lm_head, loss,
# optimizer, grad_norm (inside ``attn_qkv`` and ``attn_core`` an indexed latent
# layer names its indexer's work ``attn_index`` and ``attn_select``; inside
# known names, because a trace reader books an operation to the outermost name
# it knows). They sit inside the layer body, so every layer's work
# pools under one name, and are metadata only: each lands in the ``op_name``
# of the operations traced under it, which is what a device trace is
# attributed by. The backward pass needs none of its own: JAX writes
# ``jvp(..)`` and ``transpose(jvp(..))`` around the forward scope.
scope = jax.named_scope

# a window's first position in the stripe is rounded down to a multiple of
# this, so that the slice starts on a tile of the cache's position axis
_WINDOW_ALIGN = 128
# the name under ``attn_core`` of a layer whose attention kind is named
_SCOPE_OF_KIND = {"full": "global", "sliding": "window", "latent": "latent", "cca": "global",
                  "latent_sliding": "latent_window"}
# the latent kinds, and the stripes each keeps its rotated key and its latent
# in (``stripe_cache_shapes``); a layer of another kind keeps ``k`` and ``v``
_LATENT_KINDS = ("latent", "latent_sliding")
_STRIPES_OF_KIND = {"latent": ("k", "v"), "latent_sliding": ("k_sliding", "v_sliding")}
_LANES = 128  # the minor axis of a tile on the chip
# the kinds whose queries see every earlier position of their stripe, read as
# a full layer reads its own (``_cache_reader``)
_FULL_KINDS = ("full", "cca")
# key positions a block when a prompt's chunk reads a latent cache in plain
# XLA (what the kernel is not given, ``chunk_walks``: a tiny cache, a chunk of
# no whole query tiles, small scores, a mesh; since PR 52 these two bound that
# walk only): the
# largest of these that divides the stripe (else the stripe whole). A block's
# float32 scores are [B, heads, T, block] in HBM: 34 MB at 32 heads and a
# 256-token chunk
_LATENT_KEY_BLOCKS = (1024, 512, 256, 128)
# ... of those whose float32 scores stay under this many bytes, where any does
# (128 heads of a 4,544-token chunk at two rows are 4.7 MB a key position)
_LATENT_SCORES_MAX_BYTES = 1 << 30
# ... and that walk stays where a row's float32 scores are under this many
# bytes a key position (heads x T x 4: 64 KB is 128 heads of 128 queries, 64
# heads of 256), whatever else the kernel would take. Under it the walk's
# scores are small enough that it runs at 59% of its roofline (Kanana's 32
# heads: 32 KB at most) and the kernel won 0.6 ms of a 13.6 ms chunk, nothing
# end to end, while two of the kernel's five windows in that cell read 4% and
# 7% under the range of the parent's four and I could not say why (PERF.md
# section 6, PR 52; 7 ck): such a chunk's program stays the one it was
_CHUNK_KERNEL_MIN_SCORE_BYTES = 1 << 16
# A prompt's chunk over a full layer's stripe scores every position of the
# stripe at once (``_grouped_attention``: float32 [B, heads, T, S]) while that
# is at most this many bytes, which holds every cell but one at the form its
# programs were measured in (Laguna's four rows of 256 tokens over 4,096
# positions and Nemotron's four of 1,024 over 2,048: 1.07 GB); past it, blocks
# of ``_FULL_KEY_BLOCK`` key positions up to the furthest row's last query
# with a running maximum, sum and context (``_cache_reader``): 64 heads of a
# 1,024-token chunk over an 8,192-position stripe are 2.1 GB a row, 8.6 GB at
# four rows of a launch, on a 16 GB chip (PERF.md section 6, PR 42)
_STRIPE_SCORES_MAX_BYTES = 3 << 29
_FULL_KEY_BLOCK = 512
# queries and keys of a delta-rule layer: x / sqrt(sum x^2 + this)
_L2_EPS = 1e-6


# ------------------------------------------------------------------ the plan


@dataclasses.dataclass(frozen=True)
class Plan:
    """How the layer stack is traced: ``lead`` layers one by one, ``reps``
    times a period of ``period`` layers in one loop body, then the rest.
    ``kinds[l]``: layer l's (attention kind, query heads, feed-forward kind).
    ``attn_index[l]`` / ``mlp_index[l]``: its row in the stack of its
    attention kind / feed-forward kind. ``by_kind``: the layers are not
    alike, so the projections' leaves and the scope under ``attn_core``
    carry the attention kind's name."""

    lead: int
    period: int
    reps: int
    kinds: tuple
    attn_index: tuple
    mlp_index: tuple
    by_kind: bool
    # a layer's row among the layers that have attention (its keys' and
    # values' row in ``wk``, ``wv`` and the cache), any mixer (``attn_norm``)
    # and a feed-forward (``mlp_norm``): the layer's own number in a model
    # whose blocks all have attention and a feed-forward (``whole``)
    kv_index: tuple = ()
    mixer_index: tuple = ()
    ffn_index: tuple = ()
    # the 'latent' layers attend the positions an indexer picks (``index_topk``)
    indexed: bool = False

    @property
    def tail_from(self) -> int:
        return self.lead + self.period * self.reps

    @property
    def n_attention(self) -> int:
        return sum(t in _SCOPE_OF_KIND for t, _, _ in self.kinds)

    @property
    def n_ssm(self) -> int:
        return sum(t == "ssm" for t, _, _ in self.kinds)

    @property
    def n_kda(self) -> int:
        return sum(t == "kda" for t, _, _ in self.kinds)

    @property
    def n_cca(self) -> int:
        return sum(t == "cca" for t, _, _ in self.kinds)

    @property
    def n_mixer(self) -> int:
        return sum(t != "none" for t, _, _ in self.kinds)

    @property
    def n_ffn(self) -> int:
        return sum(m != "none" for _, _, m in self.kinds)

    @property
    def whole(self) -> bool:
        return self.n_attention == self.n_ffn == len(self.kinds)

    @property
    def bodies(self) -> int:
        """Layer bodies a pass through the stack is traced as: what a start
        pays in tracing and lowering for every program that walks it."""
        return self.lead + (self.period if self.reps else 0) + len(self.kinds) - self.tail_from

    def leaf(self, name: str, kind: str) -> str:
        """The leaf that holds projection ``name`` of attention kind ``kind``."""
        return f"{name}_{kind}" if self.by_kind else name


@functools.lru_cache(maxsize=None)
def plan(cfg) -> Plan:
    L = cfg.n_layers
    if cfg.layer_types:
        kinds = list(zip(cfg.layer_types, cfg.heads_per_layer, cfg.mlp_types))
    else:
        if (cfg.attn_gate or cfg.yarn_factor or cfg.rope_partial != 1.0
                or cfg.kv_latent_rank or cfg.moe_scoring != "softmax"):
            # the whole-sequence path of a uniform model knows none of them
            raise ValueError(
                "attn_gate, yarn_factor, rope_partial, kv_latent_rank and "
                "moe_scoring need layer_types"
            )
        kinds = [("full", cfg.n_heads, "sparse" if cfg.moe_experts else "dense")] * L
    for t, h, m in kinds:
        if t not in (*_SCOPE_OF_KIND, "ssm", "kda", "none") or m not in ("dense", "sparse", "none"):
            raise ValueError(f"unknown layer kind ({t!r}, {m!r})")
        if t == m == "none":
            raise ValueError("a block with neither a mixer nor a feed-forward")
        if h % cfg.n_kv_heads:
            raise ValueError(f"{h} query heads over {cfg.n_kv_heads} key-value heads")
    if any(t == "ssm" for t, _, _ in kinds) and not (
        cfg.ssm_heads and cfg.ssm_head_dim and cfg.ssm_state
        and cfg.ssm_heads % cfg.ssm_groups == 0
    ):
        raise ValueError("ssm layers need ssm_heads (a multiple of ssm_groups), ssm_head_dim "
                         "and ssm_state")
    if any(t == "kda" for t, _, _ in kinds) and not (
        cfg.kda_heads and cfg.kda_head_dim and cfg.kda_chunk % 4 == 0
    ):
        raise ValueError("kda layers need kda_heads, kda_head_dim and a kda_chunk that is a "
                         "multiple of 4")
    if any(t == "cca" for t, _, _ in kinds) and not (
        cfg.n_kv_heads % 2 == 0 and len(cfg.cca_taps) == 2 and min(cfg.cca_taps) >= 2
        and not cfg.attn_gate
    ):
        raise ValueError("cca layers need an even n_kv_heads (half of the value heads are the "
                         "token before's), two cca_taps of at least 2 and no attn_gate")
    if cfg.moe_router_hidden and (cfg.moe_scoring != "softmax" or cfg.moe_latent_dim):
        raise ValueError("moe_router_hidden: a softmax router over experts of the model's width")
    for t in _SCOPE_OF_KIND:
        if len({h for kt, h, _ in kinds if kt == t}) > 1:
            raise ValueError(f"{t} attention layers differ in their query heads")
    if any(t in ("sliding", "latent_sliding") for t, _, _ in kinds) and cfg.sliding_window <= 0:
        raise ValueError("sliding layers need sliding_window")
    if any(t in _LATENT_KINDS for t, _, _ in kinds) != bool(cfg.kv_latent_rank) or (
        cfg.kv_latent_rank and (
            any(t not in _LATENT_KINDS for t, _, _ in kinds) or cfg.n_kv_heads != 1
            or cfg.attn_gate == "channel"
            or not (cfg.qk_nope_dim and cfg.qk_rope_dim and cfg.v_head_dim)
        )
    ):
        raise ValueError(
            "latent layers need kv_latent_rank, qk_nope_dim, qk_rope_dim and v_head_dim, "
            "n_kv_heads 1 and a gate a head or none, and do not mix with full or sliding layers"
        )
    if any(t == "latent_sliding" for t, _, _ in kinds) and not (
        any(t == "latent" for t, _, _ in kinds) and cfg.kv_latent_rank_sliding
        and cfg.qk_nope_dim_sliding and cfg.qk_rope_dim_sliding and cfg.v_head_dim_sliding
    ):
        raise ValueError(
            "latent_sliding layers need kv_latent_rank_sliding, qk_nope_dim_sliding, "
            "qk_rope_dim_sliding and v_head_dim_sliding, beside at least one latent layer "
            "(the cache's ``k`` and ``v`` are the latent layers')"
        )
    if cfg.index_topk and not (
        cfg.kv_latent_rank and cfg.q_latent_rank and cfg.index_heads
        and cfg.index_head_dim >= cfg.qk_rope_dim
    ):
        raise ValueError(
            "index_topk: an indexer over latent layers needs q_latent_rank (its queries come "
            "from the query latent), index_heads and an index_head_dim of at least qk_rope_dim"
        )
    if any(m == "sparse" for _, _, m in kinds) and not cfg.moe_experts:
        raise ValueError("sparse layers need moe_experts")
    if (cfg.qk_norm or cfg.block_length) and any(t != "full" for t, _, _ in kinds):
        raise ValueError("qk_norm and block_length: over full attention layers alone (the "
                         "per-head norms and the block mask are the full kind's)")
    if cfg.loop_passes != 1 and not (
        cfg.loop_passes > 1 and all(k == ("full", cfg.n_heads, "dense") for k in kinds)
        and not (cfg.block_length or cfg.residual_scales) and 0.0 <= cfg.exit_threshold <= 1.0
    ):
        raise ValueError("loop_passes: a stack of full attention layers alike over dense "
                         "feed-forwards, run 2 or more times, with an exit_threshold in [0, 1]")
    if cfg.moe_experts_held and not (
        0 <= cfg.moe_experts_first <= cfg.moe_experts - cfg.moe_experts_held
    ):
        raise ValueError("moe_experts_first .. + moe_experts_held lie outside the router's experts")
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
    # a layer's row in each stack it has leaves in: counted by the key's kind
    seen: dict = {}
    rows = {name: [] for name in ("attn", "mlp", "kv", "mixer", "ffn")}
    for t, _, m in kinds:
        keys = {"attn": ("attn", t), "mlp": ("mlp", m), "kv": t in _SCOPE_OF_KIND,
                "mixer": t != "none", "ffn": m != "none"}
        for name, key in keys.items():
            key = (name, key)
            rows[name].append(seen.setdefault(key, 0))
            seen[key] += 1
    return Plan(lead, period, reps, tuple(kinds), tuple(rows["attn"]), tuple(rows["mlp"]),
                bool(cfg.layer_types), tuple(rows["kv"]), tuple(rows["mixer"]),
                tuple(rows["ffn"]), bool(cfg.index_topk))


def _moe_shapes(cfg, n: int) -> dict[str, tuple]:
    """The leaves of ``n`` stacked expert layers."""
    e, E, f = cfg.d_model, cfg.moe_experts, cfg.moe_d_ff or cfg.d_ff
    # the router scores every expert; the banks hold this device's, in the
    # width the experts work in (the latent's where there is one). A relu^2
    # expert has no gate matrix
    held, w = cfg.moe_experts_held or E, cfg.moe_latent_dim or e
    gated = cfg.moe_activation == "swiglu"
    shapes = {
        **(_mlp_router_shapes(cfg, n) if cfg.moe_router_hidden else {"moe_router": (n, e, E)}),
        **({"moe_w_gate": (n, held, w, f)} if gated else {}),
        "moe_w_up": (n, held, w, f),
        "moe_w_down": (n, held, f, w),
    }
    if cfg.moe_scoring == "sigmoid":
        shapes["moe_router_bias"] = (n, E)
    if cfg.moe_latent_dim:
        shapes.update({"moe_latent_down": (n, e, w), "moe_latent_up": (n, w, e)})
    if cfg.moe_shared_d_ff:
        fs = cfg.moe_shared_d_ff
        shapes.update({
            **({"moe_shared_gate": (n, e, fs)} if gated else {}),
            "moe_shared_up": (n, e, fs),
            "moe_shared_down": (n, fs, e),
        })
    return shapes


def _mlp_router_shapes(cfg, n: int) -> dict[str, tuple]:
    """The leaves of ``n`` stacked routers that are an MLP
    (``moe_router_hidden``): the projection of the stream to the router's
    width, the multiple a channel of the router's vector of the expert layer
    before, a norm, three matrices with their biases, and the selection bias."""
    e, E, R = cfg.d_model, cfg.moe_experts, cfg.moe_router_hidden
    return {
        "moe_router_down": (n, e, R), "moe_router_gamma": (n, R), "moe_router_norm": (n, R),
        "moe_router_w1": (n, R, R), "moe_router_b1": (n, R),
        "moe_router_w2": (n, R, R), "moe_router_b2": (n, R),
        "moe_router_w3": (n, R, E), "moe_router_b3": (n, E),
        "moe_router_bias": (n, E),
    }


def ssm_dims(cfg) -> dict:
    """The widths of a state-space mixer: ``inner`` (heads x head width, what
    the gate ``z`` and the input ``x`` are wide), ``bc`` (one of B and C: groups
    x state), ``conv`` (what the convolution runs over: x, B and C), ``proj``
    (the input projection: z, then x B C, then a step a head)."""
    inner, bc = cfg.ssm_heads * cfg.ssm_head_dim, cfg.ssm_groups * cfg.ssm_state
    return {"inner": inner, "bc": bc, "conv": inner + 2 * bc,
            "proj": 2 * inner + 2 * bc + cfg.ssm_heads}


def _ssm_shapes(cfg, n: int) -> dict[str, tuple]:
    """The leaves of ``n`` stacked state-space mixers. The convolution's
    weight lies [taps, channels] (the published [channels, 1, taps] with the
    channels on the lanes)."""
    d, e, H = ssm_dims(cfg), cfg.d_model, cfg.ssm_heads
    return {
        "ssm_w_in": (n, e, d["proj"]),
        "ssm_conv_w": (n, cfg.ssm_conv, d["conv"]),
        "ssm_conv_b": (n, d["conv"]),
        "ssm_dt_bias": (n, H),
        "ssm_a_log": (n, H),
        "ssm_d": (n, H),
        "ssm_norm": (n, d["inner"]),
        "ssm_w_out": (n, d["inner"], e),
    }


def kda_dims(cfg) -> dict:
    """The widths of a delta-rule mixer: ``inner`` (heads x head width: each
    of query, key and value), ``rank`` (the low rank the decay and the output
    gate come through: the head's width), ``conv`` (what the three
    convolutions run over: q, k, v), ``proj`` (the input projection: q k v,
    the two low ranks, then a writing strength a head)."""
    inner, rank = cfg.kda_heads * cfg.kda_head_dim, cfg.kda_head_dim
    return {"inner": inner, "rank": rank, "conv": 3 * inner,
            "proj": 3 * inner + 2 * rank + cfg.kda_heads}


def _kda_shapes(cfg, n: int) -> dict[str, tuple]:
    """The leaves of ``n`` stacked delta-rule mixers. The three projections of
    query, key and value, the two low ranks' first halves and the writing
    strength are one matrix (``kda_w_in``: one read of the input); the three
    convolutions one weight [taps, channels], no bias anywhere."""
    d, e, H = kda_dims(cfg), cfg.d_model, cfg.kda_heads
    return {
        "kda_w_in": (n, e, d["proj"]),
        "kda_conv_w": (n, cfg.kda_conv, d["conv"]),
        "kda_w_decay": (n, d["rank"], d["inner"]),
        "kda_dt_bias": (n, d["inner"]),
        "kda_a_log": (n, H),
        "kda_w_gate": (n, d["rank"], d["inner"]),
        "kda_norm": (n, cfg.kda_head_dim),
        "kda_w_out": (n, d["inner"], e),
    }


def cca_dims(cfg) -> dict:
    """The widths of a compressed-convolutional-attention layer: ``heads``
    (query and key heads side by side: what both convolutions run over),
    ``qk`` (their channels), ``vprev`` (the value heads that are the token
    before's: the second half of them), and the parts of a slot's tail in
    order (``tail``: the last ``taps - 1`` inputs of each convolution, the
    taps side by side on the lanes, then the last token's shifted values)."""
    heads = max(h for t, h, _ in plan(cfg).kinds if t == "cca") + cfg.n_kv_heads
    qk, vprev = heads * cfg.head_dim, cfg.n_kv_heads // 2 * cfg.head_dim
    parts = ((cfg.cca_taps[0] - 1) * qk, (cfg.cca_taps[1] - 1) * qk, vprev)
    return {"heads": heads, "qk": qk, "vprev": vprev, "parts": parts, "tail": sum(parts)}


def _cca_shapes(cfg, n: int, h: int) -> dict[str, tuple]:
    """The leaves of ``n`` stacked compressed-convolutional-attention layers
    of ``h`` query heads beside ``wq_cca``, ``wo_cca`` and their rows of
    ``wk`` and ``wv`` (``wv``'s first half of heads the token's own values,
    its second half the token before's): the depthwise convolution [taps,
    channels] (the published [channels, 1, taps]), the one that mixes each
    head's channels [heads, taps x head width, head width] (the published
    [channels, head width, taps] with groups = heads: row ``tap * width + i``
    of a head's matrix multiplies channel ``i`` of that tap), their biases,
    and a temperature a key head."""
    d, hd = cca_dims(cfg), cfg.head_dim
    return {
        "cca_conv0_w": (n, cfg.cca_taps[0], d["qk"]),
        "cca_conv0_b": (n, d["qk"]),
        "cca_conv1_w": (n, d["heads"], cfg.cca_taps[1] * hd, hd),
        "cca_conv1_b": (n, d["qk"]),
        "cca_temp": (n, cfg.n_kv_heads),
    }


class LatentDims(NamedTuple):
    """The sizes of one latent kind: the key-value latent's and the query
    latent's rank (0: queries come from the input), a head's query and key in
    its two parts, its value, the rotary base, and what both normed latents
    are multiplied by (1 without ``latent_rescale``)."""

    rank: int
    q_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    q_scale: float
    kv_scale: float


def latent_dims(cfg, kind: str = "latent") -> LatentDims:
    sliding = kind == "latent_sliding"
    rank, q_rank, nope, rope, v, theta = (
        (cfg.kv_latent_rank_sliding, cfg.q_latent_rank_sliding, cfg.qk_nope_dim_sliding,
         cfg.qk_rope_dim_sliding, cfg.v_head_dim_sliding, cfg.rope_theta_sliding) if sliding
        else (cfg.kv_latent_rank, cfg.q_latent_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
              cfg.v_head_dim, cfg.rope_theta))
    rescaled = cfg.latent_rescale
    return LatentDims(
        rank, q_rank, nope, rope, v, theta,
        (cfg.d_model / q_rank) ** 0.5 if rescaled and q_rank else 1.0,
        (cfg.d_model / rank) ** 0.5 if rescaled and rank else 1.0)


def stripe_cache_shapes(cfg, batch_size: int, max_len: int) -> dict:
    """name -> shape of every stripe a model's cache holds, each ``[layers of
    its kind, B, K, S, D]``: ``k`` and ``v`` of the attention layers (a
    latent model's: the latent layers' shared rotated key, at the front of a
    row of whole lane tiles, and their normed latent), and where the model has
    them the sliding latent layers' two (``k_sliding``, ``v_sliding``) and the
    indexer's key a token and latent layer (``k_index``). A model whose stack
    runs ``cfg.loop_passes`` times a token keeps every pass's keys and values:
    row ``t * layers + l`` is layer ``l``'s in pass ``t``."""
    pl = plan(cfg)

    def lanes(n):
        return -(-n // _LANES) * _LANES

    if not cfg.kv_latent_rank:  # (a looped model: a row a pass and layer, pass-major)
        lead = (pl.n_attention * cfg.loop_passes, batch_size, cfg.n_kv_heads, max_len)
        return {"k": lead + (cfg.head_dim,), "v": lead + (cfg.head_dim,)}
    shapes = {}
    for kind, (k, v) in _STRIPES_OF_KIND.items():
        n = sum(t == kind for t, _, _ in pl.kinds)
        if n:
            d = latent_dims(cfg, kind)
            shapes[k] = (n, batch_size, 1, max_len, lanes(d.rope))
            shapes[v] = (n, batch_size, 1, max_len, d.rank)
    if cfg.index_topk:
        shapes["k_index"] = shapes["k"][:-1] + (lanes(cfg.index_head_dim),)
    return shapes


def state_cache_shapes(cfg, batch_size: int) -> dict:
    """name -> (shape, dtype) of the ``STATE_LEAVES`` a model's cache holds, a
    row a layer and slot: a state-space layer's state and the last
    ``ssm_conv - 1`` inputs of its convolution, a delta-rule layer's state a
    head and the last ``kda_conv - 1`` inputs of its three convolutions (the
    states float32: they sum a sequence's steps; the tails in the served type,
    channels on the lanes), a compressed-convolutional-attention layer's tail
    (``cca_dims``; its keys and values are stripes). Empty for a model whose
    slots are stripes alone."""
    pl, shapes = plan(cfg), {}
    if pl.n_ssm:
        shapes["ssm_state"] = ((pl.n_ssm, batch_size, cfg.ssm_heads, cfg.ssm_head_dim,
                                cfg.ssm_state), jnp.float32)
        shapes["ssm_conv"] = ((pl.n_ssm, batch_size, cfg.ssm_conv - 1, ssm_dims(cfg)["conv"]),
                              cfg.dtype)
    if pl.n_kda:
        shapes["kda_state"] = ((pl.n_kda, batch_size, cfg.kda_heads, cfg.kda_head_dim,
                                cfg.kda_head_dim), jnp.float32)
        shapes["kda_conv"] = ((pl.n_kda, batch_size, cfg.kda_conv - 1, kda_dims(cfg)["conv"]),
                              cfg.dtype)
    if pl.n_cca:
        shapes["cca_tail"] = ((pl.n_cca, batch_size, 1, cca_dims(cfg)["tail"]), cfg.dtype)
    return shapes


def _latent_shapes(cfg, kind: str, n: int, h: int) -> dict[str, tuple]:
    """The leaves of ``n`` stacked latent layers of ``h`` heads, each named
    with its kind behind it: the query projection (from the input, or with a
    query latent the down-projection, its norm and the up-projection), the
    key-value latent's down-projection and norm, a head's two halves of the
    up-projection, the output projection, the gate a head, and for the
    indexed kind the indexer's query projection from the query latent (a
    matrix: heads x width side by side), its key projection with a LayerNorm's
    scale and bias, and its weight a head."""
    e, d = cfg.d_model, latent_dims(cfg, kind)
    shapes = {
        f"wq_{kind}": (n, d.q_rank or e, h, d.nope + d.rope),
        f"wkv_a_{kind}": (n, e, d.rank + d.rope),
        f"kv_norm_{kind}": (n, d.rank),
        f"wuk_{kind}": (n, h, d.nope, d.rank),
        f"wuv_{kind}": (n, h, d.rank, d.v),
        f"wo_{kind}": (n, h, d.v, e),
    }
    if d.q_rank:
        shapes.update({f"wqa_{kind}": (n, e, d.q_rank), f"q_norm_{kind}": (n, d.q_rank)})
    if cfg.attn_gate:
        shapes[f"wg_{kind}"] = (n, e, h)
    if cfg.index_topk and kind == "latent":
        Hi, Di = cfg.index_heads, cfg.index_head_dim
        shapes.update({
            "index_wq": (n, d.q_rank, Hi * Di), "index_wk": (n, e, Di),
            "index_k_norm": (n, Di), "index_k_bias": (n, Di), "index_ww": (n, e, Hi),
        })
    return shapes


def _param_shapes(cfg) -> dict[str, tuple]:
    pl = plan(cfg)  # validates the pattern
    e, v, L = cfg.d_model, cfg.vocab_size, cfg.n_layers
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    shapes = {
        "embed": (v, e),
        "final_norm": (e,),
        "attn_norm": (pl.n_mixer, e),
        "mlp_norm": (pl.n_ffn, e),
    }
    if not cfg.kv_latent_rank:
        shapes.update({"wk": (pl.n_attention, e, kv, hd), "wv": (pl.n_attention, e, kv, hd)})
    if cfg.qk_norm:  # a scale a channel of a head, for the queries and for the keys
        shapes.update({"q_head_norm": (pl.n_attention, hd), "k_head_norm": (pl.n_attention, hd)})
    if pl.n_ssm:
        shapes.update(_ssm_shapes(cfg, pl.n_ssm))
    if pl.n_kda:
        shapes.update(_kda_shapes(cfg, pl.n_kda))
    for kind, h in {t: h for t, h, _ in pl.kinds if t in _SCOPE_OF_KIND}.items():
        n = sum(t == kind for t, _, _ in pl.kinds)
        if kind in _LATENT_KINDS:
            shapes.update(_latent_shapes(cfg, kind, n, h))
            continue
        shapes[pl.leaf("wq", kind)] = (n, e, h, hd)
        shapes[pl.leaf("wo", kind)] = (n, h, hd, e)
        if kind == "cca":
            shapes.update(_cca_shapes(cfg, n, h))
        if cfg.attn_gate:  # a value a head, or a channel of each head
            shapes[pl.leaf("wg", kind)] = (n, e, h * hd if cfg.attn_gate == "channel" else h)
    n_dense = sum(m == "dense" for _, _, m in pl.kinds)
    if n_dense:
        f = cfg.d_ff
        shapes.update({"w_gate": (n_dense, e, f), "w_up": (n_dense, e, f),
                       "w_down": (n_dense, f, e)})
    n_sparse = sum(m == "sparse" for _, _, m in pl.kinds)
    if n_sparse:
        shapes.update(_moe_shapes(cfg, n_sparse))
    if cfg.residual_scales:  # [0] on the stream, [1] on the branch
        shapes.update({"attn_scale": (pl.n_mixer, 2, e), "mlp_scale": (pl.n_ffn, 2, e)})
    if cfg.branch_norm:  # a norm on each branch's way out (``_joined``)
        shapes.update({"attn_out_norm": (pl.n_mixer, e), "mlp_out_norm": (pl.n_ffn, e)})
    if cfg.loop_passes > 1:  # the exit gate: Linear(d_model, 1) with a bias
        shapes.update({"exit_w": (e,), "exit_b": (1,)})
    if not cfg.tie_embeddings:
        shapes["unembed"] = (e, v)
    return shapes


# ------------------------------------------- pieces every path shares


@scope("norm")
def _rmsnorm(x, w, eps, fused: bool = False):
    if fused:
        from ray_tpu.ops import rmsnorm as _fused_rmsnorm

        # one VMEM pass; output dtype = x.dtype (model weights share cfg.dtype)
        return _fused_rmsnorm(x, w, eps)
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * w


def _times(x, m: float):
    """``x * m`` in ``x``'s own type, and ``x`` itself where ``m`` is 1: a
    model without the scalar keeps its graph. (The Granite multipliers:
    ``LlamaConfig.embedding_multiplier`` on the looked-up rows,
    ``residual_multiplier`` on a branch before it joins the stream.)"""
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


def _joined(params, leaf: str, out_norm: str, i, x, branch, cfg):
    """The stream after a branch joins it: ``x + branch`` (the branch times
    ``cfg.residual_multiplier``), or under learned scales (``residual_scales``;
    ``leaf`` row ``i``: a vector on the stream and one on the branch)
    ``a * x + b * branch``. With ``branch_norm`` the branch passes a norm of
    its own first (``out_norm`` row ``i``)."""
    if cfg.branch_norm:
        branch = _rmsnorm(branch, params[out_norm][i], cfg.rms_eps, cfg.fused_rmsnorm)
    if cfg.residual_scales:
        a, b = params[leaf][i]
        return a * x + b * branch
    return x + _times(branch, cfg.residual_multiplier)


@scope("embed")
def _embed_lookup(table, tokens, cfg, mesh: Optional[Mesh]):
    """Token embedding. On a sharded mesh the row-gather is replaced by a
    one-hot matmul: SPMD cannot partition a gather from a table sharded on
    vocab (tp) and embed (fsdp) — it replicates the output ("involuntary
    full rematerialization") — while a matmul contracts the sharded vocab
    dim with a psum and lands directly in activation sharding. The backward
    pass likewise becomes a matmul instead of a scatter-add."""
    sharded = mesh is not None and any(s > 1 for s in mesh.shape.values())
    if not sharded:
        return _times(table[tokens].astype(cfg.dtype), cfg.embedding_multiplier)
    onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=cfg.dtype)
    return _times(
        jnp.einsum("btv,ve->bte", onehot, table.astype(cfg.dtype)), cfg.embedding_multiplier
    )



@scope("lm_head")
def _project_logits(x, params, cfg, mesh: Optional[Mesh]):
    """Vocab projection shared by forward() and the training loss.

    bf16 operands + fp32 accumulation: the MXU's native mode. Casting the
    OPERANDS to fp32 would quarter matmul throughput on the vocab
    projection (~20% of total train FLOPs) for no meaningful precision
    gain — accumulation is fp32 either way."""
    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum(
        "bte,ev->btv", x, unembed.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    if mesh is not None:
        logits = with_sharding(mesh, logits, "batch", "seq", "vocab")
    return logits if cfg.logits_scaling == 1.0 else logits / cfg.logits_scaling



def _shared_expert(p, h):
    """The expert every token passes through, ungated. h: [..., e]."""
    with scope("shared_expert"):
        if "moe_shared_gate" not in p:  # a relu^2 expert: two matrices
            return jnp.square(jax.nn.relu(h @ p["moe_shared_up"])) @ p["moe_shared_down"]
        ff = jax.nn.silu(h @ p["moe_shared_gate"]) * (h @ p["moe_shared_up"])
        return ff @ p["moe_shared_down"]



# --------------------------------- pieces of the path through a cache


# Rows of one call's routing counts (``_moe_decode_ffn``; summed over expert
# layers by the caller): expert layers run, (token, expert) assignments,
# experts that got at least one token, and the fullest expert's tokens.
MOE_STATS = ("layer_steps", "assignments", "experts_touched", "max_expert_load")


def moe_stats_names(cfg) -> tuple:
    """``MOE_STATS``, and for a model that holds a share of its experts
    (``moe_experts_held``) two more: the assignments that fell on the held
    ones (``assignments`` counts all the router made, ``experts_touched`` and
    ``max_expert_load`` the held experts') and the blocks of sorted rows the
    layer worked through (``passes``: one a layer run unless more than a
    block's rows fell on the held experts, ``held_block``)."""
    return MOE_STATS + (("assignments_held", "passes") if cfg.moe_experts_held else ())


def held_block(assignments: int, held: int, experts: int) -> int:
    """Rows of the block an expert layer that holds ``held`` of the router's
    ``experts`` works on, of the ``assignments`` (tokens x k) a call's router
    makes: twice the rows expected to fall on the held experts, in whole row
    tiles of the grouped matmul, at least one tile and at most all. From the
    call's own shapes and the config's held share and nothing else; a layer
    run on which more fell takes a second block (``_moe_decode_ffn``)."""
    from ray_tpu.ops.grouped_matmul import TILING

    tile = TILING[0]
    twice = -(-2 * assignments * held // experts)
    return min(assignments, max(tile, -(-twice // tile) * tile))


def _mlp_route(params, row, g, r_prev, cfg):
    """A router that is an MLP with a stream of its own through the depth
    (``moe_router_hidden``; ZAYA1's). g [G, e] the expert layer's normed
    input, ``r_prev`` [G, R] the router's vector of the expert layer before
    (zeros at the first). ``r = g W_d + gamma * r_prev``; the logits
    ``W_3 gelu(W_2 gelu(W_1 rmsnorm(r)))`` with biases, in float32 (the
    choice is an argmax: in bfloat16 near ties swap on rounding alone);
    probabilities their softmax; the k experts chosen by probability plus the
    selection bias and weighted by their probabilities themselves, which at
    k = 1 are not renormalised (a renormalised single weight is 1: the layer
    would lose the router's confidence, and its gradient). Returns
    (gate_vals [G, k] f32, gate_idx [G, k], r [G, R] in ``g``'s type: what the
    next expert layer's router adds, whatever was chosen)."""
    f32 = jnp.float32

    def leaf(name):
        return params["moe_router_" + name][row]

    r = g @ leaf("down") + leaf("gamma") * r_prev
    u = _rmsnorm(r.astype(f32), leaf("norm").astype(f32), cfg.rms_eps)
    for j in ("1", "2"):
        u = jax.nn.gelu(u @ leaf("w" + j).astype(f32) + leaf("b" + j).astype(f32))
    probs = jax.nn.softmax(u @ leaf("w3").astype(f32) + leaf("b3").astype(f32), axis=-1)
    _, gate_idx = jax.lax.top_k(probs + leaf("bias").astype(f32), cfg.moe_top_k)
    gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
    if cfg.moe_top_k > 1:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return gate_vals, gate_idx, r


def _moe_decode_ffn(params, row, h, cfg, r_prev=None):
    """Dropless routed expert FFN for the serving path, and for ``forward``
    of a model whose layers are not alike. ``params`` holds the stacked
    ``moe_*`` leaves, ``row`` (static or traced) is this layer's row in them.
    h: [B, T, e] -> ([B, T, e], routing counts int32 [4], ``MOE_STATS``; [6]
    for a share of the experts, ``moe_stats_names``).

    Inference must never drop tokens (a capacity overflow at prefill would
    silently corrupt the prompt — the reference's serving engine is likewise
    dropless), so instead of the training path's capacity buffers
    (``parallel/moe.py``) every token goes through exactly its top-k experts,
    mixed with the renormalized gate weights (``topk_gates`` on float32
    logits: softmax, or sigmoid with the selection bias), times ``cfg.moe_routed_scale``, plus the shared expert where
    ``cfg.moe_shared_d_ff`` is set.

    One form at every size: the B*T*k assignments are sorted by expert and go
    through three grouped matmuls (``ops/grouped_matmul.py``), so each expert
    multiplies its own tokens only and only a touched expert's weights are
    read. The form this replaced below 65 tokens, every expert over every
    token as one batched einsum, streams all the weights whatever the routing:
    on a v5e at 256 experts of 2048 x 512, 8 a token, a layer took 2.19 ms at
    any batch against 0.57, 1.50, 1.98 and 2.46 ms grouped at 8, 32, 64 and
    256 tokens (57, 165, 219 and 256 experts touched; PERF.md section 6, PR
    28). The calls take the whole stacked bank as ``[layers * E, ..]`` with
    this layer's group sizes at its own offset and zeros elsewhere: a layer's
    slice of the bank handed to a kernel is a copy of it on the chip (1.6 GB
    a layer at those widths).

    Numerically identical to ``moe_dense`` whenever its capacity does not
    overflow, which is what the decode-vs-forward exactness test pins.

    Three more forms of the same layer, by the config: an expert of two
    matrices with a squared ReLU (``moe_activation``), the routed experts in a
    latent between a down- and an up-projection (``moe_latent_dim``; scope
    ``moe_latent_proj``), and a share of the experts held here
    (``moe_experts_held``; two more counts, ``moe_stats_names``).

    A layer that holds a share works on the assignments that fell on its
    experts only. The sort puts those first, expert by expert, and the absent
    ones behind them, so everything after the sort (the gather of each
    assignment's token, the three grouped matmuls, the weighting and the sum
    into the tokens' rows) runs on a block of the first ``held_block`` sorted
    rows, a quarter of all at an eighth held, and on the next block only
    while assignments on held experts are left: a loop of ``ceil(n_held /
    block)`` turns, one on nearly every run, all ``G*k / block`` of them where
    every assignment fell here, so nothing is dropped whatever the routing.
    (The grouped matmul itself never spent a tile on the rows no group owns;
    what the block saves is the gather, the select and the combine over all
    ``G*k`` rows of ``width`` in float32, which were 5 of the 12.5 ms of a
    1,024-token chunk's expert layers at 40 of 320 held: PERF.md section 6,
    PR 45.) A model that holds all its experts keeps the one pass over all
    rows, operation for operation.

    ``r_prev`` [B, T, R]: the model's router is an MLP (``_mlp_route``) and
    this is its vector of the expert layer before; the result then ends with
    this layer's, ``(y, stats, r)``."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul
    from ray_tpu.parallel.moe import topk_gates

    B, T, e = h.shape
    E, k = cfg.moe_experts, cfg.moe_top_k
    # a device's share of the experts (``moe_experts_held`` of the router's
    # E, from ``moe_experts_first``): the router chooses over all E, an
    # assignment to an absent expert is sorted behind the held ones, belongs
    # to no group and adds nothing
    held, first = cfg.moe_experts_held or E, cfg.moe_experts_first
    g = h.reshape(B * T, e)
    G = g.shape[0]
    with scope("router"):
        # float32 logits: in the model's own bf16 the 8th and 9th of 256
        # experts swap for some tokens on rounding alone
        if r_prev is not None:
            gate_vals, gate_idx, r = _mlp_route(
                params, row, g, r_prev.reshape(G, r_prev.shape[-1]), cfg)
        else:
            router = {"router": params["moe_router"][row].astype(jnp.float32)}
            if cfg.moe_scoring == "sigmoid":
                router["bias"] = params["moe_router_bias"][row].astype(jnp.float32)
            _, gate_vals, gate_idx = topk_gates(router, g.astype(jnp.float32), k)
        # tokens an expert: a one-hot sum (a scatter-add is slow on the chip)
        load = jax.nn.one_hot(gate_idx.reshape(-1), E, dtype=jnp.int32).sum(axis=0)
        chosen = gate_idx.reshape(-1)
        if cfg.moe_experts_held:
            load = load[first:first + held]
            chosen = jnp.where((chosen >= first) & (chosen < first + held), chosen - first, held)
        n_held = load.sum()  # assignments that fell on the experts held here
        stats = [jnp.int32(1), jnp.int32(G * k), (load > 0).sum(dtype=jnp.int32), load.max()]
        if cfg.moe_experts_held:
            block = held_block(G * k, held, E)
            passes = -(-n_held // block)  # blocks that hold any of them
            stats += [n_held, jnp.maximum(passes, 1)]
        stats = jnp.stack(stats)
    if cfg.moe_latent_dim:
        with scope("moe_latent_proj"):
            src = g @ params["moe_latent_down"][row]
    else:
        src = g
    with scope("experts"):
        order = jnp.argsort(chosen)  # assignments by expert
        n = params["moe_w_up"].shape[0]

        def bank(name):
            w = params[name]
            return w.reshape((n * held,) + w.shape[2:])

        def experts(rows, load):
            """Each row through its expert: ``rows`` sorted by expert,
            ``load`` of them each of this layer's experts. float32."""
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n * held,), jnp.int32), load, (row * held,)
            )
            if cfg.moe_activation == "relu2":
                act = jnp.square(jax.nn.relu(grouped_matmul(rows, bank("moe_w_up"), sizes)))
            else:
                gate = grouped_matmul(rows, bank("moe_w_gate"), sizes)
                up = grouped_matmul(rows, bank("moe_w_up"), sizes)
                act = jax.nn.silu(gate) * up
            return grouped_matmul(act, bank("moe_w_down"), sizes, jnp.float32)

        if cfg.moe_experts_held:
            ends = jnp.cumsum(load)  # where each expert's sorted rows end
            # whole blocks, so that the last window reads no row twice
            blocks = jnp.pad(order, (0, -(G * k) % block))
            weights = gate_vals.reshape(-1)

            def one_block(p, y):
                at = p * block
                picked = jax.lax.dynamic_slice(blocks, (at,), (block,))
                token = picked // k
                out = experts(src[token], jnp.diff(jnp.clip(ends, at, at + block), prepend=at))
                # the kernel leaves the rows no group owns unwritten
                owned = at + jnp.arange(block) < n_held
                out = jnp.where(owned[:, None], out * weights[picked][:, None], 0.0)
                # each row onto its token's: a scatter-add of ``block`` rows (of
                # the forms timed on a v5e at 1,024 tokens, a block of 2,048
                # rows of 4,096: 0.42 ms, a one-hot product at the highest
                # precision 0.29 but 4.3 against 1.6 at four such rows, a
                # gather of [G, k] positions 0.53; PERF.md section 6, PR 45)
                return y.at[token].add(out)

            y = jax.lax.fori_loop(
                0, passes, one_block, jnp.zeros((G, src.shape[-1]), jnp.float32)
            )
        else:
            out = experts(src[order // k], load)  # [G*k, width]: each assignment's token
            # back to token order: a gather, not a scatter-add
            out = out[jnp.argsort(order)].reshape(G, k, src.shape[-1])
            y = jnp.einsum("gkd,gk->gd", out, gate_vals)
        y = (y * cfg.moe_routed_scale).astype(g.dtype)
    if cfg.moe_latent_dim:
        with scope("moe_latent_proj"):
            y = y @ params["moe_latent_up"][row]
    if cfg.moe_shared_d_ff:
        y = y + _shared_expert(
            {n: params[n][row] for n in params if n.startswith("moe_shared_")}, g
        )
    if r_prev is not None:
        return y.reshape(B, T, e), stats, r.reshape(r_prev.shape)
    return y.reshape(B, T, e), stats



# Widest batch whose rows ``decode_forward`` writes as one contiguous
# block each. On a v5e (PERF.md section 6, PR 27; a tensor and layer) a block
# costs about 2 us and 5 ns for each of the row's K*T cache rows (a window
# read, a select, an in-place ``dynamic_update_slice``: 11 us for a 256-token
# chunk), the scatter 73-100 ns a cache row (150 us for the same chunk), both
# linear in B. So the block wins wherever a row brings more than ~32 cache
# rows, as every prompt chunk does, and loses at decode's T = 1 (B = 32: 69 us
# against 23). The blocks are unrolled into the layer loop's body and the cap
# only bounds that program: the engine's scratch stripe has B = 1, the
# benchmark's probe B = 2; a wider gang batch (``llm/spmd.py``) is scattered.
# A decode step's rows are neither: ``writes_rows`` hands them to the write
# kernel (``ops/cache_write.py``), one call a layer for keys and values
# together. Us a layer, the two scatters against the kernel, at the serving
# cells' shapes (``tools/cache_write_sweep.py``, a v5e; PERF.md section 6,
# PR 58): 12 slots of 16 heads (Ouro) 37.6 / 6.4; 32 of 8 (Mistral, Laguna)
# 46.1 / 8.4-8.8; 64 of 8 (Solar) 83.7 / 18.3; 64 of 2 (Nemotron, ZAYA1) 22.6-25.7
# / 12.7-14.4; 64 of 4 46.5 / 13.0: the scatter by the (slot, head) pair, the
# kernel some 4 us and 0.15-0.2 us a slot, so no served shape keeps the scatter
# for its cost and the gate asks only what the kernel's copies can take.
_BLOCK_WRITE_MAX_BATCH = 8


def _write_block(c_all, new, l, b, start, ok):
    """Write row ``b``'s new keys or values ``new`` [K, T, D], whose
    positions are ``start + arange(T)``, into layer ``l`` of the carried
    cache ``c_all`` [L, B, K, S, D] as ONE contiguous block, leaving exactly
    the bytes the ``mode="drop"`` scatter leaves.

    The block is the window ``[w, w + T)`` with ``w = min(start, S - T)``
    computed here: ``dynamic_update_slice`` would clamp a start that runs
    past the axis and silently shift every row, so the shift is made
    explicit (``new`` rolled right by ``start - w``) and never left to the
    clamp. The window's old bytes are read first and kept wherever the
    scatter wrote nothing: padding (``ok`` [T] false), positions at or past
    ``S``, and the slots before ``start`` that a shifted window covers."""
    K, T, D = new.shape
    S = c_all.shape[3]
    w = jnp.clip(start, 0, S - T)
    shift = start - w
    at = (l, b, 0, w, 0)
    old = jax.lax.dynamic_slice(c_all, at, (1, 1, K, T, D))
    keep_new = (jnp.arange(T) >= shift) & jnp.roll(ok, shift)
    block = jnp.where(
        keep_new[None, :, None], jnp.roll(new, shift, axis=1), old[0, 0]
    )
    return jax.lax.dynamic_update_slice(c_all, block[None, None], at)


def _ride_stats(cache, new_cache, stats) -> None:
    """A cache that comes in with a ``moe_stats`` leaf (int32 [4],
    ``MOE_STATS``) goes out with this call's routing counts added to it: how
    the engine's programs get them out without a fetch of their own, and how
    a prompt's chunks add theirs up on the device. Any other cache is left
    as ``init_kv_cache`` made it."""
    if stats and "moe_stats" in cache:
        new_cache["moe_stats"] = cache["moe_stats"] + stats[0]


def _cache_writer(cfg, cache, params, positions, valid, start_pos):
    """``write(l, caches, news)`` for ``decode_forward``: each of ``news``
    [B, T, K, D] (new keys, new values) into layer ``l`` of the carried cache
    [L, B, K, S, D] beside it in ``caches`` -> the caches, as blocks, through
    the kernel or as the scatter (see ``decode_forward``)."""
    B, T = positions.shape
    S = cache["k"].shape[3]
    if writes_rows(T, start_pos is not None, cache["k"], *jax.tree.leaves(params),
                   latent=bool(cfg.kv_latent_rank)):
        # which rows write, and where: once, outside the layer loop
        at, ok = rows_in_stripe(positions[:, 0], None if valid is None else valid[:, 0], S)

        def write(l, caches, news):  # keys and values: one call for both
            ck_all, cv_all = caches
            return tuple(write_rows_in_place(
                ck_all, cv_all, l, *(new[:, 0] for new in news), at, ok))

        return write
    as_blocks = (
        start_pos is not None and B <= _BLOCK_WRITE_MAX_BATCH and T <= S
    )
    if as_blocks:
        ok = jnp.ones((B, T), bool) if valid is None else valid

        def one(c_all, new, l):
            for b in range(B):
                c_all = _write_block(c_all, new[b], l, b, start_pos[b], ok[b])
            return c_all
    else:
        if valid is not None:
            # out-of-range index -> dropped by scatter mode='drop'
            write_pos = jnp.where(valid, positions, S)
        else:
            write_pos = positions
        bi = jnp.arange(B)[:, None, None]
        ki = jnp.arange(cfg.n_kv_heads)[None, :, None]
        pi = write_pos[:, None, :]  # [B, 1, T]

        def one(c_all, new, l):
            return c_all.at[l, bi, ki, pi].set(new, mode="drop")

    def write(l, caches, news):
        # the cache is head-major: the new [B, T, K, D] rows go in as [B, K, T, D]
        return tuple(one(c_all, new.transpose(0, 2, 1, 3), l) for c_all, new in zip(caches, news))

    return write


def _grouped_attention(q, k, v, mask):
    """GQA over the keys the mask allows, without materializing repeated
    K/V. q: [B, T, H, D]; k, v: [B, K, S, D] (head-major, as the cache keeps
    them); mask: [B, T, S]."""
    B, T, H, D = q.shape
    K = k.shape[1]
    qg = q.reshape(B, T, K, H // K, D)
    s = jnp.einsum("btkgd,bksd->bktgs", qg, k) * D**-0.5
    s = jnp.where(mask[:, None, :, None, :], s, -1e30)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bktgs,bksd->btkgd", w, v).reshape(B, T, H, D)


def _dense_ffn(h, p):
    """SwiGLU of h [B, T, e]; ``p(name)`` hands out this layer's ``w_gate``,
    ``w_up``, ``w_down`` when asked (a layer's slice of a stacked weight is a
    copy on the chip: it is taken where it is used)."""
    ff = jax.nn.silu(
        jnp.einsum("bte,ef->btf", h, p("w_gate"))
    ) * jnp.einsum("bte,ef->btf", h, p("w_up"))
    return jnp.einsum("btf,fe->bte", ff, p("w_down"))


# --------------------------------------------------------------------- rope


def rope_inv_freq(cfg, kind: str):
    """(inverse frequencies float32 [rotated / 2], factor on cos and sin) of
    one attention kind. A sliding layer rotates the whole head at
    ``rope_theta_sliding``; a full layer the first ``rope_partial`` of it at
    ``rope_theta``, with YaRN's blend of interpolated and extrapolated
    frequencies where ``yarn_factor`` is set (as transformers'
    ``_compute_yarn_parameters`` computes them over the rotated dims). A
    uniform model's layers are full ones with neither: the whole head at
    ``rope_theta``, factor 1. A latent layer rotates ``qk_rope_dim`` numbers
    at ``rope_theta``."""
    if kind == "sliding":
        rot, theta = cfg.head_dim, cfg.rope_theta_sliding
    elif kind in _LATENT_KINDS:  # the rotated part of a query, and the shared key
        d = latent_dims(cfg, kind)
        rot, theta = d.rope, d.theta
    else:
        rot, theta = int(cfg.head_dim * cfg.rope_partial), cfg.rope_theta
    inv = (1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot)).astype(np.float32)
    if kind != "full" or not cfg.yarn_factor:
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


def _rope(x, positions, inv_freq, factor, interleave: bool = False):
    """x: [B, T, H, D], positions: [B, T]. Rotates the first
    ``2 * len(inv_freq)`` dims of each head and passes the rest through. The
    rotated dims are paired by halves, ``(i, i + rot/2)``, as ``models/llama.py
    _rope``, or with ``interleave`` as neighbours, ``(2i, 2i + 1)``."""
    rot = 2 * len(inv_freq)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, rot/2]
    cos = (jnp.cos(angles) * factor)[:, :, None, :]
    sin = (jnp.sin(angles) * factor)[:, :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    if interleave:
        pairs = xr.reshape(xr.shape[:-1] + (rot // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(xr.shape)
    else:
        x1, x2 = jnp.split(xr, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], axis=-1)


# ------------------------------------------------------------- layer pieces


class _Layer:
    """One layer's static kind and its (static or traced) indices."""

    def __init__(self, pl: Plan, l_static: int, l, attn_i, mlp_i, rows=None, row0=None):
        self.kind, _, self.mlp = pl.kinds[l_static]
        self.sparse = self.mlp == "sparse"
        self.l, self.attn_i, self.mlp_i = l, attn_i, mlp_i
        # the layer's row among the layers with attention, with a mixer, with
        # a feed-forward: its own number where every block has all of them
        self.kv_i, self.mixer_i, self.ffn_i = (l, l, l) if pl.whole else rows
        # its keys' and values' row in the cache: ``kv_i``, and in a stack run
        # several times a token ``row0``, the pass's first row, further
        self.cache_i = self.kv_i if row0 is None else row0 + self.kv_i
        self.wq, self.wo, self.wg = (pl.leaf(n, self.kind) for n in ("wq", "wo", "wg"))
        self.by_kind = pl.by_kind
        self.latent = self.kind in _LATENT_KINDS
        # a latent layer whose queries attend what an indexer picks
        self.indexed = pl.indexed and self.kind == "latent"
        # the stack's last layer, traced on its own (not a pass of the loop)
        self.last = l_static == len(pl.kinds) - 1 and isinstance(l, int)

    def inner_scope(self):
        """The name under ``attn_core``: ``global`` or ``window`` where the
        model has kinds to tell apart, none where its layers are alike."""
        if not self.by_kind:
            return contextlib.nullcontext()
        return scope("latent_sparse" if self.indexed else _SCOPE_OF_KIND[self.kind])


def _score_rescale(cfg) -> float:
    """What the queries are multiplied by so that every form of the read
    (``_grouped_attention``, the block walk, the decode kernel), each of which
    scales its scores by ``head_dim ** -0.5``, scores by
    ``cfg.attention_multiplier`` instead: their ratio, 1 where the field is 0.
    Granite's 1/64 over 64 ** -0.5 is 1/8, exact in any float type."""
    return cfg.attention_multiplier * cfg.head_dim ** 0.5 if cfg.attention_multiplier else 1.0


def _qkv(params, lay: _Layer, h, positions, cfg, loras=None, adapter_ids=None):
    """Rotated queries and keys, and values, of h [B, T, e]. ``loras`` (a
    uniform model's, ``init_lora_stack``): each row's adapter
    ``adapter_ids[b]`` adds its low-rank delta to q and v, W x + B (A x)."""
    inv_freq, factor = rope_inv_freq(cfg, lay.kind)
    with scope("attn_qkv"):
        q = jnp.einsum("bte,ehd->bthd", h, params[lay.wq][lay.attn_i])
        k = jnp.einsum("bte,ehd->bthd", h, params["wk"][lay.kv_i])
        v = jnp.einsum("bte,ehd->bthd", h, params["wv"][lay.kv_i])
        if loras is not None:
            lp = {n: loras[n][lay.l] for n in ("wq_a", "wq_b", "wv_a", "wv_b")}
            q = q + jnp.einsum(
                "btr,brhd->bthd",
                jnp.einsum("bte,ber->btr", h, lp["wq_a"][adapter_ids]),
                lp["wq_b"][adapter_ids],
            )
            v = v + jnp.einsum(
                "btr,brhd->bthd",
                jnp.einsum("bte,ber->btr", h, lp["wv_a"][adapter_ids]),
                lp["wv_b"][adapter_ids],
            )
        if cfg.qk_norm:  # each head's own width, before the rotation
            q = _rmsnorm(q, params["q_head_norm"][lay.kv_i], cfg.rms_eps)
            k = _rmsnorm(k, params["k_head_norm"][lay.kv_i], cfg.rms_eps)
        if cfg.attn_rope:
            q = _rope(q, positions, inv_freq, factor)
            k = _rope(k, positions, inv_freq, factor)
    return _times(q, _score_rescale(cfg)), k, v


def _cca_project(params, lay: _Layer, h):
    """The projections of a compressed-convolutional-attention layer of h
    [B, T, e], which multiply by a weight and so run on every set's rows as
    one: queries and keys before their convolutions side by side
    [B, T, heads, D] (the query heads first), and the values [B, T, K, D]
    (the second half of the heads belong to the token after)."""
    with scope("attn_qkv"):
        q = jnp.einsum("bte,ehd->bthd", h, params[lay.wq][lay.attn_i])
        k = jnp.einsum("bte,ehd->bthd", h, params["wk"][lay.kv_i])
        v = jnp.einsum("bte,ehd->bthd", h, params["wv"][lay.kv_i])
    return jnp.concatenate([q, k], axis=2), v


def _cca_qkv(params, lay: _Layer, qk, v, tail, positions, valid, cfg):
    """Queries, keys and values of one set of rows of a compressed-
    convolutional-attention layer (Figliolia et al., arXiv 2510.04476), from
    ``_cca_project``'s ``qk`` [B, T, heads, D] and ``v`` [B, T, K, D] and the
    rows' ``tail`` [B, 1, ``cca_dims``' tail] (zeros for a new sequence), and
    the rows' next tail.

    ``qk`` passes a depthwise causal convolution over the sequence and then
    one that mixes the channels of each head (``cca_taps``, no activation);
    the mean of each query head and its group's key before the convolutions
    (and that mean's mean over the group, for the key) is added back; each
    head is brought to length ``sqrt(D)`` in float32, a key head times its
    temperature; the first ``rope_partial`` of each head rotated. The value
    heads' first half are the token's own, the second half the token
    before's. ``valid`` [B, T] (or None: all) marks a row's real tokens, a
    prefix of it: the next tail is cut where the row ends, and a row of no
    real token keeps the one it came with. The first convolution's output is
    rounded to the served type before the second reads it, as the tail holds
    it: a token's numbers do not depend on where a chunk ended."""
    from ray_tpu.ops.ssm import causal_conv

    i, d, f32 = lay.attn_i, cca_dims(cfg), jnp.float32
    B, T, heads, D = qk.shape
    K = cfg.n_kv_heads
    H, taps = heads - K, cfg.cca_taps
    inv_freq, factor = rope_inv_freq(cfg, lay.kind)
    with scope("attn_qkv"), scope("cca_conv"):
        t0, t1, tv = (part.reshape(B, -1, width) for part, width in zip(
            jnp.split(tail, np.cumsum(d["parts"])[:-1], axis=-1), (d["qk"], d["qk"], d["vprev"])))
        flat = qk.reshape(B, T, d["qk"])
        u, seen0 = causal_conv(t0, flat, params["cca_conv0_w"][i], params["cca_conv0_b"][i])
        seen1 = jnp.concatenate([t1.astype(flat.dtype), u.astype(flat.dtype)], axis=1)
        by_head = seen1.reshape(B, -1, heads, D)
        # the taps of a head side by side: one product a head
        w = jnp.einsum(
            "bthc,hcd->bthd",
            jnp.concatenate([by_head[:, j:j + T] for j in range(taps[1])], axis=-1),
            params["cca_conv1_w"][i], preferred_element_type=f32,
        ) + params["cca_conv1_b"][i].astype(f32).reshape(heads, D)
        qk32 = qk.astype(f32)
        mean_q = 0.5 * (qk32[:, :, :H] + jnp.repeat(qk32[:, :, H:], H // K, axis=2))
        mean_k = mean_q.reshape(B, T, K, H // K, D).mean(axis=3)
        q, k = w[:, :, :H] + mean_q, w[:, :, H:] + mean_k
        q, k = (t * jax.lax.rsqrt((t * t).sum(axis=-1, keepdims=True) + _L2_EPS) * D ** 0.5
                for t in (q, k))
        k = k * params["cca_temp"][i].astype(f32)[:, None]
        q = _rope(q, positions, inv_freq, factor).astype(qk.dtype)
        k = _rope(k, positions, inv_freq, factor).astype(qk.dtype)
        half = K // 2
        seen_v = jnp.concatenate(
            [tv.astype(v.dtype), v[:, :, half:].reshape(B, T, d["vprev"])], axis=1)
        v = jnp.concatenate([v[:, :, :half], seen_v[:, :T].reshape(B, T, half, D)], axis=2)
        tail = jnp.concatenate([
            _next_tail(seen, n - 1, valid).reshape(B, 1, -1)
            for seen, n in ((seen0, taps[0]), (seen1, taps[1]), (seen_v, 2))], axis=-1)
    return _times(q, _score_rescale(cfg)), k, v, tail.astype(cfg.dtype)


def _latent_qkv(params, lay: _Layer, h, positions, cfg):
    """A latent layer's projections of h [B, T, e]: each head's query in its
    two parts, (q_nope [B, T, H, nope], q_rope [B, T, H, rope], rotated), what
    the cache holds of a token: the rotated key all heads share
    [B, T, 1, rope] and the normed latent [B, T, 1, rank], and the normed
    query latent [B, T, q_rank] (None where the queries come from the input
    itself). Both normed latents times the kind's rescale (``LatentDims``)."""
    d = latent_dims(cfg, lay.kind)
    inv_freq, factor = rope_inv_freq(cfg, lay.kind)
    r, nope = d.rank, d.nope
    i = lay.attn_i

    def leaf(name):
        return params[f"{name}_{lay.kind}"][i]

    with scope("attn_qkv"):
        cq = None
        if d.q_rank:
            cq = _times(_rmsnorm(jnp.einsum("bte,er->btr", h, leaf("wqa")), leaf("q_norm"),
                                 cfg.rms_eps, cfg.fused_rmsnorm), d.q_scale)
            q = jnp.einsum("btr,rhd->bthd", cq, leaf("wq"))
        else:
            q = jnp.einsum("bte,ehd->bthd", h, leaf("wq"))
        kv = jnp.einsum("bte,er->btr", h, leaf("wkv_a"))
        c = _times(_rmsnorm(kv[..., :r], leaf("kv_norm"), cfg.rms_eps, cfg.fused_rmsnorm),
                   d.kv_scale)
        q_rope = _rope(q[..., nope:], positions, inv_freq, factor, cfg.rope_interleave)
        k_rope = _rope(kv[:, :, None, r:], positions, inv_freq, factor, cfg.rope_interleave)
    return (q[..., :nope], q_rope), k_rope, c[:, :, None, :], cq


def _index_qkw(params, lay: _Layer, h, cq, positions, cfg):
    """The indexer's projections (DeepSeek-V3.2's) of a latent layer's normed
    input h [B, T, e] and normed query latent cq [B, T, q_rank]: its queries
    [B, T, Hi, Di], the weight a query head float32 [B, T, Hi], times
    ``Hi ** -0.5 * Di ** -0.5``, and the token's index key [B, T, 1, Di] (a
    LayerNorm with bias over the key); the first ``qk_rope_dim`` numbers of a
    query and of the key rotated by halves at the layer's own base."""
    Hi, Di = cfg.index_heads, cfg.index_head_dim
    inv_freq, factor = rope_inv_freq(cfg, "latent")
    i, f32 = lay.attn_i, jnp.float32
    B, T, _ = h.shape
    with scope("attn_qkv"), scope("attn_index"):
        q = jnp.einsum("btr,rf->btf", cq, params["index_wq"][i]).reshape(B, T, Hi, Di)
        k = jnp.einsum("bte,ed->btd", h, params["index_wk"][i], preferred_element_type=f32)
        k = k - k.mean(axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + cfg.rms_eps)
        k = (k * params["index_k_norm"][i].astype(f32)
             + params["index_k_bias"][i].astype(f32)).astype(h.dtype)
        q = _rope(q, positions, inv_freq, factor)
        k = _rope(k[:, :, None, :], positions, inv_freq, factor)
        w = jnp.einsum("bte,eh->bth", h, params["index_ww"][i], preferred_element_type=f32)
    return q, w * (Hi ** -0.5 * Di ** -0.5), k


def _index_scores(q, w, keys):
    """``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`` in float32. q
    [B, T, Hi, Di], w [B, T, Hi] (scaled: ``_index_qkw``), keys [B, S, Di']
    (zeros behind Di) -> [B, T, S]."""
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, keys.shape[-1] - q.shape[-1]),))
    s = jnp.einsum("bthd,bsd->bths", q, keys, preferred_element_type=jnp.float32)
    return jnp.einsum("bths,bth->bts", jax.nn.relu(s), w)


def _kept(scores, k: int):
    """Which of each row's ``scores`` [..., S] (float32) are among its ``k``
    largest, ties to the lower position (``lax.top_k``'s order): everything
    above the ``k``-th largest value, and of those equal to it the first that
    the count leaves room for. The ``k``-th value is found without a sort, by
    descent over the 32 bits of the scores' order-preserving integer image (a
    pass of compares and a count a bit; on a v5e 0.36 ms for 256 rows of
    24,576 where ``lax.top_k`` of 2,048 takes 4.67: PERF.md section 6, PR 51).
    A position masked to ``-inf`` is kept only where fewer than ``k`` are not,
    and is none the caller's mask allows."""
    u32 = jnp.uint32
    bits = jax.lax.bitcast_convert_type(jnp.where(scores == 0, 0.0, scores), u32)  # no -0.0
    image = jnp.where(bits >> 31 == 1, ~bits, bits | u32(1 << 31))

    def bit(i, kth):
        raised = kth | (u32(1 << 31) >> i.astype(u32))
        enough = (image >= raised[..., None]).sum(axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, raised, kth)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1], u32))[..., None]
    above, level = image > kth, image == kth
    room = k - above.sum(axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (level & (jnp.cumsum(level, axis=-1, dtype=jnp.int32) <= room))


def _latent_scale(cfg, kind: str = "latent") -> float:
    d = latent_dims(cfg, kind)
    return (d.nope + d.rope) ** -0.5


def _latent_expand(params, lay: _Layer, c):
    """Every head's keys (the part that is not rotated) and values of the
    latents c [B, S, rank] -> ([B, S, H, nope], [B, S, H, v])."""
    return (jnp.einsum("bsr,hnr->bshn", c, params[f"wuk_{lay.kind}"][lay.attn_i]),
            jnp.einsum("bsr,hrv->bshv", c, params[f"wuv_{lay.kind}"][lay.attn_i]))


def _latent_expanded(params, lay: _Layer, q, k_rope, c, mask, cfg):
    """Latent attention in its expanded form over keys that are all at hand:
    every head's keys and values expanded from the latents. q: ``_latent_qkv``'s
    pair; k_rope [B, S, rope], c [B, S, rank]; mask [B, T, S] -> [B, T, H, v]."""
    q_nope, q_rope = q
    k_nope, v = _latent_expand(params, lay, c)
    s = (
        jnp.einsum("bthn,bshn->bhts", q_nope, k_nope, preferred_element_type=jnp.float32)
        + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope, preferred_element_type=jnp.float32)
    ) * _latent_scale(cfg, lay.kind)
    s = jnp.where(mask[:, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshv->bthv", w, v)


def _latent_absorb(params, lay: _Layer, q_nope):
    """Each head's query through its half of the key up-projection: scores
    against the latents themselves. [B, T, H, nope] -> [B, T, H, rank]."""
    return jnp.einsum("bthn,hnr->bthr", q_nope, params[f"wuk_{lay.kind}"][lay.attn_i])


def _attn_out(params, lay: _Layer, x, h, attn, cfg, from_latent: bool = False):
    """x + the heads' outputs through ``wo``. ``from_latent``: ``attn`` is the
    absorbed form's context in the latent's space [B, T, H, rank] and goes
    through each head's half of the value up-projection first."""
    with scope("attn_out"):
        if from_latent:
            attn = jnp.einsum("bthr,hrv->bthv", attn, params[f"wuv_{lay.kind}"][lay.attn_i])
        if cfg.attn_gate:
            with scope("gate"):
                # [B, T, H] a head, [B, T, H * D] a channel
                gate = jax.nn.sigmoid(jnp.einsum(
                    "bte,eh->bth", h, params[lay.wg][lay.attn_i],
                    preferred_element_type=jnp.float32,
                ))
                gate = gate.reshape(attn.shape) if cfg.attn_gate == "channel" else gate[..., None]
                attn = (attn * gate).astype(attn.dtype)
        out = jnp.einsum("bthd,hde->bte", attn, params[lay.wo][lay.attn_i])
        return _joined(params, "attn_scale", "attn_out_norm", lay.mixer_i, x, out, cfg)


def _ssm_in(params, lay: _Layer, h, valid, cfg):
    """A state-space mixer's input projection of the normed h [B, T, e]: the
    what the mixing takes a set of rows at a time (what the convolution runs
    over: x, B and C; the float32 step a head, 0 where ``valid`` [B, T] (or
    None: all) says a token is none), and the gate ``z``. Rows are independent
    here, so several sets of them go through as one (``decode_forward``). The
    three parts of a mixer (this, ``_ssm_mix``, ``_ssm_out``) keep their
    scopes inside the attention's three, by what the work is, under
    ``ssm_mixer``; ``ssm_conv`` and ``ssm_scan`` or ``ssm_step`` inside
    ``attn_core/ssm_mixer``."""
    i = lay.attn_i  # the row among the state-space layers
    d = ssm_dims(cfg)
    f32 = jnp.float32
    with scope("attn_qkv"), scope("ssm_mixer"):
        proj = jnp.einsum("bte,ef->btf", h, params["ssm_w_in"][i])
        z = proj[..., : d["inner"]]
        xbc = proj[..., d["inner"]: d["inner"] + d["conv"]]
        dt = jax.nn.softplus(
            proj[..., -cfg.ssm_heads:].astype(f32) + params["ssm_dt_bias"][i].astype(f32))
        if valid is not None:
            dt = jnp.where(valid[..., None], dt, 0.0)
    return (xbc, dt), z


def _conv_through_tail(conv_all, i, x, w, b, valid):
    """The short causal convolution of one set of rows ``x`` [B, T, channels]
    behind the tails ``conv_all`` [n, B, taps - 1, channels] carries at row
    ``i``, then SiLU, and the leaf with the next tail: the taps - 1 inputs up
    to each row's last real token. Every token real (``valid`` None: a decode
    step): the last ones, a plain slice; a padded row's end is its own, which
    makes it a gather."""
    from ray_tpu.ops.ssm import causal_conv

    conv, seen = causal_conv(conv_all[i], x, w, b)
    tail = _next_tail(seen, w.shape[0] - 1, valid)
    return jax.nn.silu(conv), jax.lax.dynamic_update_index_in_dim(
        conv_all, tail.astype(conv_all.dtype), i, 0)


def _next_tail(seen, taps: int, valid):
    """The ``taps`` inputs up to each row's last real token, of ``seen``
    [B, taps + T, channels]: the row's tail and its T new inputs behind it.
    Every token real (``valid`` None): the last ones, a plain slice; a padded
    row's end is its own, which makes it a gather, and a row of no real token
    keeps the tail it came with."""
    if valid is None:
        return seen[:, seen.shape[1] - taps:]
    return jax.vmap(
        lambda row, at: jax.lax.dynamic_slice(row, (at, 0), (taps, row.shape[1]))
    )(seen, valid.sum(axis=1, dtype=jnp.int32))


def _ssm_mix(params, lay: _Layer, xbc, dt, state_all, conv_all, valid, cfg):
    """The mixing itself for one set of rows, from and into its carried state
    ``state_all`` [n, B, H, P, N] and convolution tails ``conv_all``
    [n, B, taps - 1, channels] at this layer's row: the convolution over
    ``xbc`` [B, T, channels], then the recurrence with the steps ``dt``
    [B, T, H]. ``valid`` [B, T] (or None: all) marks a row's real tokens, a
    prefix of it: a token that is none has a step of 0 (``_ssm_in``), which
    leaves the state where the row's last real token put it, and the tail is
    cut where the row ends. One token a row takes the recurrence's own line on
    the leaf where it lies (``ops/ssm.py ssm_step_in_place``: a kernel where
    the state tiles, ``ssm_step`` where not), more the chunked scan
    (``ssm_scan``): the same state either way. Returns (y float32
    [B, H, P] or [B, T, H, P], the two leaves)."""
    from ray_tpu.ops.ssm import ssm_scan, ssm_step_in_place

    i = lay.attn_i
    d = ssm_dims(cfg)
    Bsz, T, _ = xbc.shape
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    f32 = jnp.float32
    with scope("attn_core"), scope("ssm_mixer"):
        with scope("ssm_conv"):
            xbc, conv_all = _conv_through_tail(
                conv_all, i, xbc, params["ssm_conv_w"][i], params["ssm_conv_b"][i], valid)
        x = xbc[..., : d["inner"]].reshape(Bsz, T, H, P)
        b_in = xbc[..., d["inner"]: d["inner"] + d["bc"]].reshape(Bsz, T, G, N)
        c_in = xbc[..., d["inner"] + d["bc"]:].reshape(Bsz, T, G, N)
        a = -jnp.exp(params["ssm_a_log"][i].astype(f32))
        skip = params["ssm_d"][i]
        if T == 1:  # a row handed out of the leaf would be a copy out and a copy in
            with scope("ssm_step"):
                y, state_all = ssm_step_in_place(
                    state_all, i, x[:, 0], dt[:, 0], a, b_in[:, 0], c_in[:, 0], skip)
        else:
            state = jax.lax.dynamic_index_in_dim(state_all, i, 0, keepdims=False)
            with scope("ssm_scan"):
                y, state = ssm_scan(state, x, dt, a, b_in, c_in, skip, cfg.ssm_chunk)
            state_all = jax.lax.dynamic_update_index_in_dim(state_all, state, i, 0)
    return y, state_all, conv_all


def _ssm_out(params, lay: _Layer, y, z, cfg):
    """Gate, grouped norm and output projection of the mixing's ``y`` (any
    shape of ``z``'s [B, T, inner] numbers) -> [B, T, e]: row by row again."""
    i = lay.attn_i
    d = ssm_dims(cfg)
    Bsz, T, _ = z.shape
    G = cfg.ssm_groups
    with scope("attn_out"), scope("ssm_mixer"):
        # gate, then a norm a group of heads (the gate before the norm)
        y = y.reshape(Bsz, T, G, d["inner"] // G) * jax.nn.silu(z.astype(jnp.float32)).reshape(
            Bsz, T, G, d["inner"] // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps)
        y = y.reshape(Bsz, T, d["inner"]).astype(z.dtype) * params["ssm_norm"][i]
        return jnp.einsum("btf,fe->bte", y, params["ssm_w_out"][i])


def _kda_in(params, lay: _Layer, h, valid, cfg):
    """A delta-rule mixer's input projection of the normed h [B, T, e]: what
    the mixing takes a set of rows at a time (what the three convolutions run
    over: q, k and v; the float32 log-decay a head and key channel ``g`` =
    -exp(A_log) softplus(low rank + dt_bias) and the writing strength a head
    ``beta`` = 2 sigmoid(.), in (0, 2): a state's eigenvalues in (-1, 1]),
    and the output gate's low rank. A token that is none (``valid`` [B, T]
    false) has ``beta`` 0 and ``g`` 0 (a decay of 1): it leaves the state
    where the row's last real token put it. Scopes as ``_ssm_in``'s, under
    ``kda_mixer``: ``kda_conv`` and ``kda_scan`` or ``kda_step`` inside
    ``attn_core/kda_mixer``."""
    i = lay.attn_i  # the row among the delta-rule layers
    d = kda_dims(cfg)
    f32 = jnp.float32
    with scope("attn_qkv"), scope("kda_mixer"):
        proj = jnp.einsum("bte,ef->btf", h, params["kda_w_in"][i])
        qkv = proj[..., : d["conv"]]
        low = proj[..., d["conv"]: d["conv"] + d["rank"]]
        gate_low = proj[..., d["conv"] + d["rank"]: d["conv"] + 2 * d["rank"]]
        step = jax.nn.softplus(
            jnp.einsum("btr,rf->btf", low, params["kda_w_decay"][i],
                       preferred_element_type=f32) + params["kda_dt_bias"][i].astype(f32))
        g = -jnp.exp(params["kda_a_log"][i].astype(f32))[:, None] * step.reshape(
            step.shape[:2] + (cfg.kda_heads, cfg.kda_head_dim))
        beta = 2.0 * jax.nn.sigmoid(proj[..., -cfg.kda_heads:].astype(f32))
        if valid is not None:
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
    return (qkv, g, beta), gate_low


def _kda_mix(params, lay: _Layer, qkv, g, beta, state_all, conv_all, valid, cfg):
    """The mixing itself for one set of rows, from and into its carried state
    ``state_all`` [n, B, H, K, V] and convolution tails ``conv_all``
    [n, B, taps - 1, channels] at this layer's row: the three convolutions
    over ``qkv`` [B, T, channels], queries and keys to unit length a head (the
    query times K ** -0.5), then the delta rule with ``g`` [B, T, H, K] and
    ``beta`` [B, T, H]. One token a row takes the rule's own line on the leaf
    where it lies (``ops/kda.py kda_step_in_place``: a kernel where the state
    tiles, ``kda_step`` where not), more the chunked form (``kda_scan``, a kernel
    or ``kda_scan_plain`` likewise): the same state either way. Returns (o
    float32 [B, H, V] or [B, T, H, V], the two leaves)."""
    from ray_tpu.ops.kda import kda_scan, kda_step_in_place

    i = lay.attn_i
    Bsz, T, _ = qkv.shape
    H, D = cfg.kda_heads, cfg.kda_head_dim
    with scope("attn_core"), scope("kda_mixer"):
        with scope("kda_conv"):
            qkv, conv_all = _conv_through_tail(
                conv_all, i, qkv, params["kda_conv_w"][i], jnp.zeros((), jnp.float32), valid)
            q, k, v = (qkv[..., j * H * D:(j + 1) * H * D].reshape(Bsz, T, H, D) for j in range(3))
            q, k = (t * jax.lax.rsqrt((t * t).sum(axis=-1, keepdims=True) + _L2_EPS) for t in (q, k))
            q = q * D ** -0.5
        if T == 1:  # a row handed out of the leaf would be a copy out and a copy in
            with scope("kda_step"):
                o, state_all = kda_step_in_place(
                    state_all, i, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        else:
            state = jax.lax.dynamic_index_in_dim(state_all, i, 0, keepdims=False)
            with scope("kda_scan"):
                o, state = kda_scan(state, q, k, v, g, beta, cfg.kda_chunk)
            state_all = jax.lax.dynamic_update_index_in_dim(state_all, state, i, 0)
    return o, state_all, conv_all


def _kda_out(params, lay: _Layer, o, gate_low, cfg):
    """Norm a head, gate a channel and output projection of the mixing's ``o``
    (any shape of [B, T, inner] numbers) -> [B, T, e]: row by row again."""
    i = lay.attn_i
    Bsz, T, _ = gate_low.shape
    H, D = cfg.kda_heads, cfg.kda_head_dim
    f32 = jnp.float32
    with scope("attn_out"), scope("kda_mixer"):
        o = o.reshape(Bsz, T, H, D)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps)
        gate = jax.nn.sigmoid(jnp.einsum(
            "btr,rf->btf", gate_low, params["kda_w_gate"][i], preferred_element_type=f32))
        o = o * params["kda_norm"][i].astype(f32) * gate.reshape(Bsz, T, H, D)
        return jnp.einsum("btf,fe->bte", o.reshape(Bsz, T, H * D).astype(gate_low.dtype),
                          params["kda_w_out"][i])


# The mixers that keep a state a sequence and no keys, by layer kind (a kind
# that holds a stripe and state leaves both is in ``STRIPE_STATE``, below):
# the three parts
# ``decode_forward`` calls (the input projection of every set's rows as one,
# ``-> (what the mixing takes a set at a time, what the output takes)``; the
# mixing of one set, ``-> (y, *leaves)``; gate, norm and output projection of
# all rows as one) and the ``STATE_LEAVES`` the mixing carries.
STATE_MIXERS = {
    "ssm": (_ssm_in, _ssm_mix, _ssm_out, ("ssm_state", "ssm_conv")),
    "kda": (_kda_in, _kda_mix, _kda_out, ("kda_state", "kda_conv")),
}
# What a slot holds whatever its length, beside its stripes of keys and
# values: every such leaf of every kind of mixer that keeps a state, [layers
# of the kind, slots, ..] with the slot on axis 1 (``state_cache_shapes``).
# The one list the engine's pool (``state_bytes_per_slot``, ``stateful``), its
# chunk programs (what is stacked, unstacked, zeroed and copied into a slot
# with the stripes), ``init_kv_cache``, ``decode_forward`` and
# ``llm/config.py refuse_stateful`` read: a further kind adds a row above and
# its shapes, and nothing asks which kind.
# An attention kind may hold a state leaf beside its stripe, what its layers
# carry from token to token that is no key and no value: the projections of
# every set's rows as one, ``-> parts``; queries, keys and values of one set
# from its parts and its rows of the leaf, ``-> (q, k, v, the rows' next)``;
# and the leaf.
STRIPE_STATE = {"cca": (_cca_project, _cca_qkv, "cca_tail")}
STATE_LEAVES = tuple(name for *_, names in STATE_MIXERS.values() for name in names) + tuple(
    name for *_, name in STRIPE_STATE.values())


def _router_stream(cfg, x) -> tuple:
    """What goes round the layer loop beside ``x`` [B, T, e] for the expert
    layers' routers: nothing, or for a router that is an MLP
    (``moe_router_hidden``) its vector of the expert layer before, zeros in
    front of the first."""
    if not cfg.moe_router_hidden:
        return ()
    return (jnp.zeros(x.shape[:-1] + (cfg.moe_router_hidden,), x.dtype),)


def _feed_forward(params, lay: _Layer, x, cfg, route=()):
    """x + feed-forward(norm(x)), the layer's routing counts (zeros for a
    dense layer of a model that has expert layers, None in a model with none,
    which carries no counts) and ``route`` (``_router_stream``) as an expert
    layer's router left it."""
    h = _rmsnorm(x, params["mlp_norm"][lay.ffn_i], cfg.rms_eps, cfg.fused_rmsnorm)
    if lay.sparse:
        with scope("moe_ffn"):
            y, stats, *route = _moe_decode_ffn(params, lay.mlp_i, h, cfg, *route)
            x = _joined(params, "mlp_scale", "mlp_out_norm", lay.ffn_i, x, y, cfg)
            return x, stats, tuple(route)
    with scope("ffn"):
        # a layer's slice of a stacked weight is taken where it is used
        y = _dense_ffn(h, lambda name: params[name][lay.mlp_i])
        x = _joined(params, "mlp_scale", "mlp_out_norm", lay.ffn_i, x, y, cfg)
    return x, (jnp.zeros((len(moe_stats_names(cfg)),), jnp.int32) if cfg.moe_experts else None), route


def _run_layers(cfg, layer_fn, carry, row0=None):
    """``carry = layer_fn(lay, carry)`` over the stack as ``plan`` splits it:
    the only loop over layers on the cache path. The repeated period is one
    ``fori_loop`` with the whole carry (for ``decode_forward`` the whole
    cache) going round: the per-layer cache writes alias in place (donated
    buffers), where a ``lax.scan`` carrying per-layer cache slices as ys
    re-materializes the whole cache every step (decode measured 1.6x slower
    from those copies alone at 3B/B=16 on v5e). ``row0``: the cache row of
    the stack's first layer where it is not 0 (``_run_passes``)."""
    pl = plan(cfg)

    tables = (pl.kv_index, pl.mixer_index, pl.ffn_index)

    def static(l):
        return _Layer(pl, l, l, pl.attn_index[l], pl.mlp_index[l], [t[l] for t in tables], row0)

    for l in range(pl.lead):
        carry = layer_fn(static(l), carry)
    if pl.reps:
        first = [pl.lead + j for j in range(pl.period)]
        # a kind's rows advance by its count in one period
        step_attn = [sum(pl.kinds[m][0] == pl.kinds[l][0] for m in first) for l in first]
        step_mlp = [sum(pl.kinds[m][2] == pl.kinds[l][2] for m in first) for l in first]
        # rows among the layers with attention, a mixer, a feed-forward
        step_rows = [sum(pl.kinds[m][0] in _SCOPE_OF_KIND for m in first),
                     sum(pl.kinds[m][0] != "none" for m in first),
                     sum(pl.kinds[m][2] != "none" for m in first)]

        def body(i, carry):
            for j, l in enumerate(first):
                lay = _Layer(
                    pl, l, l + i * pl.period,
                    pl.attn_index[l] + i * step_attn[j],
                    pl.mlp_index[l] + i * step_mlp[j],
                    [t[l] + i * step for t, step in zip(tables, step_rows)],
                    row0,
                )
                carry = layer_fn(lay, carry)
            return carry

        carry = jax.lax.fori_loop(0, pl.reps, body, carry)
    for l in range(pl.tail_from, cfg.n_layers):
        carry = layer_fn(static(l), carry)
    return carry


# ----------------------------------------- a stack run several times a token


def _run_passes(cfg, layer_fn, end_pass, carry, ex):
    """A stack run ``cfg.loop_passes`` times a token: ``_run_layers`` under one
    more ``fori_loop``, the carry going round both. Pass ``t`` is the stack
    with the same weights on the cache's rows from ``t * layers``
    (``_Layer.cache_i``), and ``carry, ex = end_pass(t, carry, ex)`` behind its
    last layer (``_loop_exit``; ``ex``: what the exit rule carries from pass to
    pass, which no layer sees). -> ``(carry, ex)``"""
    rows = plan(cfg).n_attention
    return jax.lax.fori_loop(
        0, cfg.loop_passes,
        lambda t, both: end_pass(t, _run_layers(cfg, layer_fn, both[0], t * rows), both[1]),
        (carry, ex))


# what ``loop_stats`` counts (int32 [2 + passes]): forwards, the passes their
# stacks ran, then the rows whose head read pass 0, 1, ..
LOOP_STATS = ("forwards", "passes")


def _loop_exit_start(cfg, like, keep: bool = False) -> dict:
    """What goes round the passes beside the stream for the rows ``like``
    [.., e] whose next token is asked for (``_loop_exit``): the hidden state
    the exit rule has chosen so far, the pass it is from and whether it has
    chosen, the shares added up, the chance that no earlier pass stopped, and
    the passes run. ``keep``: every pass's normed stream and share as well
    (the whole-sequence path hands them out)."""
    lead, f32 = like.shape[:-1], jnp.float32
    ex = {"h": jnp.zeros(like.shape, like.dtype), "at": jnp.zeros(lead, jnp.int32),
          "done": jnp.zeros(lead, bool), "cum": jnp.zeros(lead, f32),
          "alive": jnp.ones(lead, f32), "passes": jnp.zeros((), jnp.int32)}
    if keep:
        ex.update(hidden=jnp.zeros((cfg.loop_passes,) + like.shape, like.dtype),
                  pdf=jnp.zeros((cfg.loop_passes,) + lead, f32))
    return ex


def _loop_exit(params, cfg, t, x, ex: dict, rows=lambda x: x):
    """The end of pass ``t`` of a stack run ``cfg.loop_passes`` times: the
    stream under the model's final norm (what pass ``t + 1`` starts from and
    what the head reads), and for ``rows(x)`` the exit rule: the gate's
    chance ``lam`` that a position stops here, its share ``lam * prod(1 -
    lam[s], s < t)`` of the exit distribution (the last pass takes what is
    left), and the first pass at which the shares add up to
    ``cfg.exit_threshold`` (else the last) as the one the head reads. All in
    float32, a position its own. Named ``loop_exit`` inside ``norm``: a
    reader that knows no such name books it all to the norm it mostly is."""
    with scope("norm"), scope("loop_exit"):
        x = _rmsnorm(x, params["final_norm"], cfg.rms_eps, cfg.fused_rmsnorm)
        ex = dict(ex, passes=ex["passes"] + 1)
        if "at" not in ex:  # no row's next token is asked for
            return x, ex
        h = rows(x)
        lam = jax.nn.sigmoid(
            jnp.einsum("...e,e->...", h, params["exit_w"].astype(h.dtype),
                       preferred_element_type=jnp.float32)
            + params["exit_b"].astype(jnp.float32)[0])
        last = t == cfg.loop_passes - 1
        share = jnp.where(last, ex["alive"], lam * ex["alive"])
        cum = ex["cum"] + share
        # (the shares of all passes add up to 1 but for rounding: the last takes the rest)
        here = ~ex["done"] & ((cum >= cfg.exit_threshold) | last)
        ex = dict(ex, h=jnp.where(here[..., None], h, ex["h"]), at=jnp.where(here, t, ex["at"]),
                  done=ex["done"] | here, cum=cum, alive=ex["alive"] * (1.0 - lam))
        if "hidden" in ex:
            ex.update(hidden=jax.lax.dynamic_update_index_in_dim(ex["hidden"], h, t, 0),
                      pdf=jax.lax.dynamic_update_index_in_dim(ex["pdf"], share, t, 0))
    return x, ex


# ------------------------------------------------------------ whole sequence


def forward_hidden(params, tokens, cfg, mesh: Optional[Mesh] = None, positions=None,
                   passes: bool = False):
    """tokens: [B, T] -> final hidden states [B, T, d_model] of a model whose
    layers are not alike, every expert layer dropless on this device
    (``_moe_decode_ffn``). One device: a mesh with an axis over 1 is refused
    (training this model over ``ep`` is not here yet). A uniform model's
    whole-sequence path is ``models/llama.py forward_hidden``.

    Of a stack run several times a token (``cfg.loop_passes``) the hidden
    state is each position's own pass's, as its exit rule picks (``_loop_exit``),
    and with ``passes`` the result is ``(that, {"hidden": every pass's normed
    stream [passes, B, T, e], "pdf": the exit distribution [passes, B, T],
    "exit": the pass picked [B, T]})``."""
    if mesh is not None and any(s > 1 for s in mesh.shape.values()):
        raise NotImplementedError("models/patterned.py runs on one device")
    if not plan(cfg).whole:
        raise NotImplementedError(
            "models/patterned.py forward_hidden: blocks that are a mixer or a feed-forward "
            "alone, and mixers that keep a state, run through the cache only (prefill, "
            "decode_step)"
        )
    if cfg.index_topk or any(t == "latent_sliding" for t, _, _ in plan(cfg).kinds):
        raise NotImplementedError(
            "models/patterned.py forward_hidden: latent layers under an indexer or a window "
            "(index_topk, latent_sliding) run through the cache only (prefill, decode_step): "
            "no whole-sequence path, and so no training, selects or windows a latent"
        )
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    x = _embed_lookup(params["embed"], tokens, cfg, None)
    back = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]  # query - key
    if cfg.block_length:  # causal across blocks, both ways inside one
        back = positions[:, :, None] // cfg.block_length - positions[:, None, :] // cfg.block_length
    masks = {
        "full": jnp.broadcast_to(back >= 0, (B, T, T)),
        "sliding": jnp.broadcast_to((back >= 0) & (back < cfg.sliding_window), (B, T, T)),
    }
    masks["latent"] = masks["cca"] = masks["full"]

    def layer(lay: _Layer, carry):
        x, route = carry
        h = _rmsnorm(x, params["attn_norm"][lay.l], cfg.rms_eps, cfg.fused_rmsnorm)
        if lay.latent:
            q, k, v, _ = _latent_qkv(params, lay, h, positions, cfg)
        elif lay.kind in STRIPE_STATE:  # the whole sequence: nothing came before it
            project, qkv, leaf = STRIPE_STATE[lay.kind]
            shape, dtype = state_cache_shapes(cfg, B)[leaf]
            q, k, v, _ = qkv(params, lay, *project(params, lay, h), jnp.zeros(shape[1:], dtype),
                             positions, None, cfg)
        else:
            q, k, v = _qkv(params, lay, h, positions, cfg)
        with scope("attn_core"), lay.inner_scope():
            if lay.latent:  # the whole sequence is at hand: the expanded form
                attn = _latent_expanded(params, lay, q, k[:, :, 0], v[:, :, 0], masks["latent"], cfg)
            else:
                attn = _grouped_attention(
                    q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), masks[lay.kind]
                )
        x = _attn_out(params, lay, x, h, attn, cfg)
        x, _, route = _feed_forward(params, lay, x, cfg, route)
        return x, route

    if cfg.remat:
        plain = layer
        layer = lambda lay, x: jax.checkpoint(lambda y: plain(lay, y))(x)  # noqa: E731
    if cfg.loop_passes > 1:
        def end_pass(t, carry, ex):
            x, ex = _loop_exit(params, cfg, t, carry[0], ex)
            return (x, carry[1]), ex

        _, ex = _run_passes(cfg, layer, end_pass, (x, _router_stream(cfg, x)),
                            _loop_exit_start(cfg, x, keep=passes))
        if passes:
            return ex["h"], {"hidden": ex["hidden"], "pdf": ex["pdf"], "exit": ex["at"]}
        return ex["h"]
    x, _ = _run_layers(cfg, layer, (x, _router_stream(cfg, x)))
    return _rmsnorm(x, params["final_norm"], cfg.rms_eps, cfg.fused_rmsnorm)


# ------------------------------------------------------------ through a cache


def _window_slice(c_all, l, first, width: int):
    """Row b's positions ``[first[b], first[b] + width)`` of layer ``l``:
    [B, K, width, D], one gather over the rows."""
    _, B, K, _, D = c_all.shape

    def row(b, at):
        return jax.lax.dynamic_slice(c_all, (l, b, 0, at, 0), (1, 1, K, width, D))[0, 0]

    return jax.vmap(row)(jnp.arange(B), first)


def reads_blocks(stripe: int, *arrays, latent: bool = False) -> bool:
    """Whether a decode step (one new token a row) over a cache of ``stripe``
    positions a slot reads through the kernel (``ops/decode_attention.py``)
    or keeps the einsum: the one place that decides it. ``arrays`` are what
    the step runs on, the cache and the parameters, as the caller holds them
    or as a trace sees them: the engine asks once a pool with its arrays
    (``llm/engine.py _Pool.reads_blocks``, for its counter), ``_cache_reader``
    with the tracers of the same arrays, and both get the same answer.

    The kernel wants a stripe of whole blocks (``takes_stripe``: of the
    shortest; how long a block then is follows from what a position of the
    cache holds, ``ops/decode_attention.py block_size``), heads as wide as its
    copies take (the first of ``arrays`` is the cache's keys ``[.., D]``; a
    latent cache's rows are whole lane tiles by ``init_kv_cache``) and
    everything on one device.
    An argument committed to a ``NamedSharding`` carries its mesh in its
    type, inside a trace too (``llm/spmd.py`` and ``llm/gang.py`` jit over a
    mesh with the key-value heads sharded over ``tp``; an engine under
    ``tensor_parallel_degree`` shards its parameters); a mesh of one device
    is one device. What a type does not carry cannot be seen here: an
    uncommitted argument that only a ``jit``'s ``in_shardings`` spreads over
    a mesh reads as one device (no caller in this repo places its arrays
    so; ``tests/test_patterned_stack.py`` traces the ways they do)."""
    if not takes_stripe(stripe):
        return False
    if not latent and not takes_heads_of(arrays[0]):
        return False
    return _on_one_device(arrays)


def writes_rows(T: int, from_start: bool, *arrays, latent: bool = False) -> bool:
    """Whether a set of rows of ``T`` new tokens each writes its keys and
    values through the kernel (``ops/cache_write.py``: one call a layer for
    both tensors and every row) or keeps the form it has (``_write_block`` or
    the scatter): the one place that decides it, asked as ``reads_blocks`` is,
    by ``_cache_writer`` with the tracers of the arrays the rows run on and by
    the engine once a pool with the arrays themselves (``llm/engine.py
    _Pool.writes_rows``, for ``get_stats()["pools"][i]["decode_write"]``).

    The kernel is a decode step's: one new token a row at a position of the
    row's own (``T == 1`` and no ``from_start``, the caller's word that a
    row's positions are consecutive: a prompt's one-token chunk is a block
    like any other), into a stripe cache (the first of ``arrays``, the cache's
    keys ``[L, B, K, S, D]``; not ``latent``: a latent pool's write of one
    256-lane row a slot is 0.03-0.04 ms a step as the scatter) whose heads are
    whole lane tiles and whose stripe is whole tiles of positions
    (``ops/cache_write.py takes_cache``: a tiny model's narrow heads keep the
    scatter on the CPU as on the chip), with everything on one device
    (``reads_blocks`` says how a mesh is seen)."""
    if T != 1 or from_start or latent or not takes_cache(arrays[0]):
        return False
    return _on_one_device(arrays)


def _on_one_device(arrays) -> bool:
    """No argument (an array or its tracer) carries a mesh of several devices
    in its type (``reads_blocks`` says what a type does not carry)."""
    return all(jax.typeof(x).sharding.mesh.size <= 1 for x in arrays)


def _chunk_expands(cfg, T: int, kind: str = "latent") -> bool:
    """Which form ``T`` new tokens a row take over a latent cache: whether
    each block's keys and values are expanded from its latents first (2 *
    rank * heads * (nope + v) operations a key position, then 2 * heads *
    (nope + rope + v) a query and key position), or the queries are absorbed
    through ``wuk`` and meet the latents themselves (2 * heads * (2 * rank +
    rope) a pair, nothing a key position): the form that needs fewer
    operations at this width. At 32 heads of 128 + 64 and 128 on a rank of
    512 that is the expanded one from 171 tokens up. On a v5e a whole final
    chunk of 256 tokens (5 layers, 4 of them with 128 experts) behind 12,288
    / 20,480 cached tokens took 17.8 / 22.6 ms absorbed and 16.1 / 20.0 ms
    expanded (``benchmark/tools/latent_chunk_forms.py``; PERF.md section 6,
    PR 33): 13.6 against 17.8 M operations a key position."""
    d = latent_dims(cfg, kind)
    r, nope, v = d.rank, d.nope, d.v
    return T * (2 * r - nope - v) > r * (nope + v)


def chunk_walks(cfg, stripe: int, T: int, *arrays) -> dict:
    """kind -> ``"kernel"`` or ``"einsum"``: how ``T`` new tokens a row read a
    latent cache of ``stripe`` positions a slot, each latent kind of ``cfg``.
    The one place that decides it, from what is static at trace time, as
    ``reads_blocks`` does for a decode step: ``_latent_reader`` asks with the
    tracers of the arrays the chunk runs on, the engine once a pool and width
    with the arrays themselves (``llm/engine.py get_stats()["pools"][i]
    ["chunk_walks"]``), and both get the same answer.

    The kernel (``ops/latent_chunk_attention.py``) wants ``T`` a whole number
    of its query tiles, a stripe of whole key blocks, latents of whole lane
    tiles on the chip and everything on one device (``reads_blocks`` says how
    a mesh is seen), and is given the chunk where a row's float32 scores are
    ``_CHUNK_KERNEL_MIN_SCORE_BYTES`` a key position or more (that is where the
    walk's traffic through HBM binds). Anything else keeps the walk in plain
    XLA: a decode step that is not the decode kernel's, a tiny cache, a chunk
    of an odd width, few heads of few queries."""
    one_device = _on_one_device(arrays)
    return {
        kind: "kernel" if (one_device and heads * T * 4 >= _CHUNK_KERNEL_MIN_SCORE_BYTES
                           and chunk_kernel.tiles(heads, T, stripe) is not None
                           and chunk_kernel.takes_widths(latent_dims(cfg, kind).rank)) else "einsum"
        for kind, heads, _ in plan(cfg).kinds if kind in _LATENT_KINDS}


def _latent_reader(cfg, params, cache, positions):
    """``(read, select, absorbed)`` for ``decode_forward`` over a latent
    cache. ``read(q, ck_all, cv_all, lay, chosen)``: ck_all [n, B, 1, S, 128]
    the layer's kind's shared rotated keys, cv_all [n, B, 1, S, rank] its
    normed latents (``_STRIPES_OF_KIND``), the layer at row ``lay.attn_i``; q
    ``_latent_qkv``'s pair. ``absorbed[kind]``: whether the read hands out
    the context in the latent's space [B, T, H, rank] (the absorbed form;
    ``_attn_out`` expands it) or each head's own [B, T, H, v].

    A sliding latent layer's query at position ``t`` sees ``t - window + 1 ..
    t``. An indexed layer's sees what ``select(index, k_index_all, lay)``
    chose for it (``index``: ``_index_qkw``'s queries and weights; k_index_all
    [n, B, 1, S, 128] the index keys): the ``index_topk`` positions up to its
    own of the largest index scores, ties to the lower position. ``select``
    is None where the stripe holds no more than ``index_topk`` positions (all
    are kept); it gives a decode step the positions themselves
    [B, index_topk], anything else a mask [B, T, S].

    One new token a row, a stripe of whole blocks, one device: the absorbed
    form. A layer that is not indexed goes through the decode kernel
    (``ops/decode_attention.py latent_decode_attention``), row ``b`` between
    ``0`` (a sliding layer: the window's start) and ``pos + 1``; an indexed
    one scores the row's index keys (scope ``attn_index``), takes the best
    (``attn_select``: ``ops/topk.py``) and attends over those positions' keys
    and latents, gathered out of the stripe (``ops/decode_attention.py
    sparse_latent_decode_attention``). Anything else walks blocks of key
    positions under the mask (causal over absolute positions, a sliding
    layer's window, an indexed layer's choice, made from the index scores of
    all blocks first: ``_kept``) with a running maximum, sum and context in
    float32, so that neither the work nor any temporary follows the stripe
    where the rows are shorter. Which walk, ``chunk_walks`` says, a kind:

    - ``T`` a whole number of query tiles, a stripe of whole blocks, one
      device, float32 scores of 64 KB a key position or more (dots3's full
      layers from 128 tokens a chunk, its sliding ones at 256): the chunk kernel
      (``ops/latent_chunk_attention.py``), one call a layer, absorbed or
      expanded by the chunk's width (``_chunk_expands``; absorbed where the
      kernel has no expanded form for the shapes); a tile of queries walks
      from its first query's window to its last query's block, and the
      float32 scores never leave VMEM;
    - else (a tiny cache, a chunk of an odd width, ``T`` = 1 off the decode
      kernel's shapes, Kanana's 32 heads at any width): the same walk in
      plain XLA under ``lax.fori_loop``, up to the furthest row's last query
      (a sliding layer: from the block the earliest row's window starts in),
      absorbed or expanded a block at a time, by the chunk's width
      (``_chunk_expands``); its scores [B, heads, T, block] pass through HBM
      (``_LATENT_KEY_BLOCKS``)."""
    from ray_tpu.ops import topk

    B, T = positions.shape
    S = cache["k"].shape[3]
    W, K = cfg.sliding_window, cfg.index_topk
    kinds = {t: h for t, h, _ in plan(cfg).kinds if t in _LATENT_KINDS}

    def padded(q_rope, ck_all):
        # a cached key's row is the rotated key and zeros behind it
        # (``init_kv_cache``): the rotated query gets the same zeros
        return jnp.pad(q_rope, ((0, 0),) * 3 + ((0, ck_all.shape[-1] - q_rope.shape[-1]),))

    if T == 1 and reads_blocks(S, cache["k"], *jax.tree.leaves(params), latent=True):
        hi = positions[:, 0] + 1

        def select(index, k_index_all, lay):
            q, w = index
            with scope("attn_index"):
                keys = jax.lax.dynamic_slice_in_dim(k_index_all, lay.attn_i, 1, 0)
                scores = _index_scores(q, w, keys.reshape((B, S, keys.shape[-1])))[:, 0]  # [B, S]
                # (no -0.0: it ties with 0.0, and goes to the lower position)
                scores = jnp.where(jnp.arange(S)[None, :] < hi[:, None],
                                   jnp.where(scores == 0, 0.0, scores), -jnp.inf)
            with scope("attn_select"):
                return topk.top_k(scores, K)[1]

        def read(q, ck_all, cv_all, lay, chosen=None):
            q_nope, q_rope = q[0], padded(q[1], ck_all)
            ql = _latent_absorb(params, lay, q_nope)
            scale = _latent_scale(cfg, lay.kind)
            if chosen is not None:
                return sparse_latent_decode_attention(
                    q_rope[:, 0], ql[:, 0], ck_all, cv_all, lay.attn_i, chosen, hi, scale
                )[:, None]
            lo = jnp.maximum(hi - W, 0) if lay.kind == "latent_sliding" else jnp.zeros_like(hi)
            return latent_decode_attention(
                q_rope[:, 0], ql[:, 0], ck_all, cv_all, lay.attn_i, lo, hi, scale
            )[:, None]

        return read, (select if 0 < K < S else None), dict.fromkeys(kinds, True)

    def walk(heads):
        """(key positions a block, blocks up to the furthest row's last query)."""
        fits = [b for b in _LATENT_KEY_BLOCKS if S % b == 0]
        bk = next((b for b in fits if B * heads * T * b * 4 <= _LATENT_SCORES_MAX_BYTES),
                  fits[-1] if fits else S)
        # row b's queries are consecutive from positions[b, 0]: the last sees furthest
        return bk, jnp.minimum(jnp.max(positions[:, -1]) // bk + 1, S // bk)

    walks = {kind: walk(max(h, cfg.index_heads if kind == "latent" and K else 0))
             for kind, h in kinds.items()}
    # (the plain kind asked as before it had a sibling: a tool swaps the function)
    # the kernel or this walk, a kind (``chunk_walks``); the kernel expands
    # where the rule says so and its expanded form takes the shapes
    by = chunk_walks(cfg, S, T, cache["k"], *jax.tree.leaves(params))
    expands = {kind: _chunk_expands(cfg, T, *(() if kind == "latent" else (kind,))) and (
        by[kind] != "kernel" or chunk_kernel.expands(heads, T, S, latent_dims(cfg, kind)))
        for kind, heads in kinds.items()}
    if "latent_sliding" in kinds:  # the block the earliest row's window starts in
        first_block = jnp.maximum(jnp.min(positions[:, 0]) - W + 1, 0) // walks["latent_sliding"][0]

    def select(index, k_index_all, lay):
        q, w = index
        bk, n_blocks = walks[lay.kind]
        with scope("attn_index"):
            def block(i, scores):
                at = (lay.attn_i, 0, 0, i * bk, 0)
                kb = jax.lax.dynamic_slice(
                    k_index_all, at, (1, B, 1, bk, k_index_all.shape[-1]))[0, :, 0]
                seen = (i * bk + jnp.arange(bk))[None, None, :] <= positions[:, :, None]
                return jax.lax.dynamic_update_slice(
                    scores, jnp.where(seen, _index_scores(q, w, kb), -jnp.inf), (0, 0, i * bk))

            scores = jnp.full((B, T, S), -jnp.inf, jnp.float32)
            scores = block(0, scores) if bk == S else jax.lax.fori_loop(0, n_blocks, block, scores)
        with scope("attn_select"):
            return _kept(scores, K)

    def kernel_read(q, ck_all, cv_all, lay, chosen):
        """``read`` through ``ops/latent_chunk_attention.py``: the queries
        head-major, the mask [B, T, S] in one elementwise pass (the kernel
        reads it a block at a time and bounds its walk by ``positions``)."""
        q_nope, q_rope = q[0], padded(q[1], ck_all)
        sliding = lay.kind == "latent_sliding"
        at = jnp.arange(S)[None, None, :]
        seen = at <= positions[:, :, None]
        if sliding:
            seen = seen & (positions[:, :, None] - at < W)
        if chosen is not None:
            seen = seen & chosen
        if expands[lay.kind]:  # the kernel takes the layer's row of both leaves where they lie
            q, weights = q_nope, (params[f"wuk_{lay.kind}"], params[f"wuv_{lay.kind}"])
        else:
            q, weights = _latent_absorb(params, lay, q_nope), ()
        out = chunk_kernel.latent_chunk_attention(
            q_rope.transpose(0, 2, 1, 3), q.transpose(0, 2, 1, 3), seen.astype(jnp.int8), ck_all,
            cv_all, lay.attn_i, positions, _latent_scale(cfg, lay.kind), W if sliding else None,
            *weights)
        return out.transpose(0, 2, 1, 3)

    def read(q, ck_all, cv_all, lay, chosen=None):
        if by[lay.kind] == "kernel":
            return kernel_read(q, ck_all, cv_all, lay, chosen)
        q_nope, q_rope = q[0], padded(q[1], ck_all)
        H = q_nope.shape[2]
        d = latent_dims(cfg, lay.kind)
        bk, n_blocks = walks[lay.kind]
        expanded = expands[lay.kind]
        scale = _latent_scale(cfg, lay.kind)
        ql = None if expanded else _latent_absorb(params, lay, q_nope)
        width = d.v if expanded else d.rank

        def block(i, carry):
            m, den, acc = carry
            at = (lay.attn_i, 0, 0, i * bk, 0)
            kb = jax.lax.dynamic_slice(ck_all, at, (1, B, 1, bk, ck_all.shape[-1]))[0, :, 0]
            cb = jax.lax.dynamic_slice(cv_all, at, (1, B, 1, bk, cv_all.shape[-1]))[0, :, 0]
            s = jnp.einsum("bthd,bsd->bhts", q_rope, kb, preferred_element_type=jnp.float32)
            if expanded:
                k_nope, vb = _latent_expand(params, lay, cb)
                s = s + jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                                   preferred_element_type=jnp.float32)
            else:
                s = s + jnp.einsum("bthr,bsr->bhts", ql, cb, preferred_element_type=jnp.float32)
            seen = (i * bk + jnp.arange(bk))[None, None, :] <= positions[:, :, None]  # [B, T, bk]
            if lay.kind == "latent_sliding":
                seen = seen & (positions[:, :, None] - (i * bk + jnp.arange(bk)) < W)
            if chosen is not None:
                seen = seen & jax.lax.dynamic_slice(chosen, (0, 0, i * bk), (B, T, bk))
            s = jnp.where(seen[:, None], s * scale, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            den = alpha * den + p.sum(axis=-1)
            p = p.astype(cb.dtype)
            if expanded:
                pv = jnp.einsum("bhts,bshv->bhtv", p, vb, preferred_element_type=jnp.float32)
            else:
                pv = jnp.einsum("bhts,bsr->bhtr", p, cb, preferred_element_type=jnp.float32)
            return m_new, den, alpha[..., None] * acc + pv

        # every query sees a position of some block of the walk, its own at
        # the latest, and what a block none of whose positions it sees left in
        # its sums goes with the first it does see (``alpha`` is 0 then). Block
        # 0 holds position 0, which every query of a layer without a window or
        # an indexer sees
        init = (jnp.full((B, H, T), -1e30, jnp.float32), jnp.zeros((B, H, T), jnp.float32),
                jnp.zeros((B, H, T, width), jnp.float32))
        if bk == S:
            _, den, acc = block(0, init)
        else:
            start = first_block if lay.kind == "latent_sliding" else 0
            _, den, acc = jax.lax.fori_loop(start, n_blocks, block, init)
        return (acc / den[..., None]).transpose(0, 2, 1, 3).astype(q_rope.dtype)

    return (read, (select if 0 < K < S else None),
            {kind: not expanded for kind, expanded in expands.items()})


def _cache_reader(cfg, params, cache, positions, kinds, block: bool = False):
    """``read(q, ck_all, cv_all, lay)`` for ``decode_forward``: attention of
    the queries [B, T, H, D] over layer ``lay.l`` of the carried cache
    [L, B, K, S, D] -> [B, T, H, D]. Two forms of one read, chosen from what
    is static at trace time, under the same mask (causal over absolute
    positions, and within the window in a sliding layer).

    One new token a row (``T == 1``: every ``decode_step``), a stripe of
    whole blocks and everything on one device: the Pallas kernel
    (``ops/decode_attention.py``), which takes the carried cache where it
    lies and reads row ``b`` between its own bounds, ``[0, pos + 1)`` in a
    full layer and ``[pos - W + 1, pos + 1)`` in a sliding one. Over a mesh
    the einsum stays: the partitioner splits it over the key-value heads with
    no communication, and would hand a kernel it cannot split the whole
    gathered cache.

    Anything else (``prefill``'s chunks, a tiny cache): ``_grouped_attention``
    over the layer's whole stripe; a sliding layer cuts the ``window + T - 1``
    positions its queries can see out of the stripe first, from a start
    rounded down to ``_WINDOW_ALIGN`` (where that is the whole stripe, the
    stripe under the window's mask). A full layer whose scores over the whole
    stripe would pass ``_STRIPE_SCORES_MAX_BYTES`` walks the stripe in blocks
    of ``_FULL_KEY_BLOCK`` key positions instead, up to the furthest row's
    last query and no further, with a running maximum, sum and context in
    float32 (as ``_latent_reader`` walks a latent cache): neither the work nor
    any temporary follows the stripe where the rows are shorter.

    A model that generates by blocks (``cfg.block_length``) reads under the
    block mask: a query sees every position up to the end of its own block.
    Its block step (``block``: ``T`` new tokens a row, one block, already
    written) takes the same kernel: the block's ``T`` queries lie beside each
    key-value head's query heads as rows of one matmul a key block
    (``T * H // K`` of them where a decode step has ``H // K``), all bounded
    ``[0, first position + T)``, so a row's stripe is read once for all of
    them."""
    T = positions.shape[1]
    S = cache["k"].shape[3]
    W, A = cfg.sliding_window, _WINDOW_ALIGN
    if block and reads_blocks(S, cache["k"], *jax.tree.leaves(params)):
        hi = positions[:, 0] + T
        lo = jnp.zeros_like(hi)

        def read(q, ck_all, cv_all, lay):
            B, _, H, D = q.shape
            K = ck_all.shape[2]
            folded = q.reshape(B, T, K, H // K, D).transpose(0, 2, 1, 3, 4).reshape(B, T * H, D)
            out = decode_attention(folded, ck_all, cv_all, lay.cache_i, lo, hi)
            return out.reshape(B, K, T, H // K, D).transpose(0, 2, 1, 3, 4).reshape(B, T, H, D)

        return read
    if T == 1 and reads_blocks(S, cache["k"], *jax.tree.leaves(params)):
        hi = positions[:, 0] + 1
        lo = {**dict.fromkeys(_FULL_KINDS, jnp.zeros_like(hi)), "sliding": jnp.maximum(hi - W, 0)}

        def read(q, ck_all, cv_all, lay):
            return decode_attention(q[:, 0], ck_all, cv_all, lay.cache_i, lo[lay.kind], hi)[:, None]

        return read

    qpos = positions[:, :, None]  # [B, T, 1]
    if cfg.block_length:  # the last position a query sees: its own block's
        qpos = (qpos // cfg.block_length + 1) * cfg.block_length - 1
    slot = jnp.arange(S)[None, None, :]
    span = -(-(W + T - 1 + A - 1) // A) * A  # covers the window from an aligned start
    whole = {**dict.fromkeys(_FULL_KINDS, True), "sliding": span >= S}
    if "sliding" in kinds and not whole["sliding"]:
        first = jnp.clip((positions[:, 0] - W + 1) // A * A, 0, S - span)  # [B]
        wslot = first[:, None, None] + jnp.arange(span)[None, None, :]
        window_mask = (wslot <= qpos) & (qpos - wslot < W)

    def stripe_mask(kind):
        seen = slot <= qpos
        return seen & (qpos - slot < W) if kind == "sliding" else seen

    masks = {kind: stripe_mask(kind) for kind in _SCOPE_OF_KIND if kind in kinds}
    B = positions.shape[0]
    bk = _FULL_KEY_BLOCK
    heads = max((h for t, h, _ in plan(cfg).kinds if t in _FULL_KINDS), default=0)
    in_blocks = S % bk == 0 and S > bk and B * heads * T * S * 4 > _STRIPE_SCORES_MAX_BYTES
    if in_blocks:  # row b's queries are consecutive from positions[b, 0]: the last sees furthest
        n_blocks = jnp.minimum(jnp.max(positions[:, -1]) // bk + 1, S // bk)

    def read_blocks(q, ck_all, cv_all, lay):
        K, D = ck_all.shape[2], q.shape[-1]
        G = q.shape[2] // K
        qg = q.reshape(B, T, K, G, D)

        def block(i, carry):
            m, den, acc = carry
            at = (lay.cache_i, 0, 0, i * bk, 0)
            kb = jax.lax.dynamic_slice(ck_all, at, (1, B, K, bk, D))[0]
            vb = jax.lax.dynamic_slice(cv_all, at, (1, B, K, bk, D))[0]
            s = jnp.einsum("btkgd,bksd->bktgs", qg, kb, preferred_element_type=jnp.float32)
            seen = (i * bk + jnp.arange(bk))[None, None, :] <= qpos  # [B, T, bk]
            s = jnp.where(seen[:, None, :, None, :], s * D**-0.5, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            den = alpha * den + p.sum(axis=-1)
            pv = jnp.einsum("bktgs,bksd->bktgd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            return m_new, den, alpha[..., None] * acc + pv

        # block 0 holds position 0, which every query sees: no row's maximum
        # is still the mask's when a block past its last query comes
        init = (jnp.full((B, K, T, G), -1e30, jnp.float32), jnp.zeros((B, K, T, G), jnp.float32),
                jnp.zeros((B, K, T, G, D), jnp.float32))
        _, den, acc = jax.lax.fori_loop(0, n_blocks, block, init)
        return (acc / den[..., None]).transpose(0, 2, 1, 3, 4).reshape(B, T, K * G, D).astype(q.dtype)

    def read(q, ck_all, cv_all, lay):
        if in_blocks and lay.kind in _FULL_KINDS:
            return read_blocks(q, ck_all, cv_all, lay)
        if whole[lay.kind]:
            return _grouped_attention(q, ck_all[lay.cache_i], cv_all[lay.cache_i], masks[lay.kind])
        return _grouped_attention(
            q, _window_slice(ck_all, lay.cache_i, first, span),
            _window_slice(cv_all, lay.cache_i, first, span), window_mask,
        )

    return read


class _Rows:
    """One set of rows on its way through the layers: its tokens [B, T] at
    ``positions`` [B, T] (``valid`` [B, T] or None marks the real ones), the
    cache it writes into and reads, and the form of each (``_cache_writer``,
    ``_cache_reader`` or ``_latent_reader``), chosen from its own shapes."""

    def __init__(self, cfg, params, kinds, cache, tokens, positions, valid, start_pos,
                 second: bool = False, commit=None):
        self.cache, self.tokens, self.positions, self.valid = cache, tokens, positions, valid
        self.second = second
        # [B] where each row is one block of a model that generates by blocks:
        # the rows whose length advances by the block (``decode_forward``)
        self.commit = commit
        self.B, self.T = tokens.shape
        self.write = _cache_writer(cfg, cache, params, positions, valid, start_pos)
        self.select = None  # an indexed latent layer's choice of positions
        if "latent" in kinds:  # every layer is latent (``plan``); by kind
            self.read, self.select, self.from_latent = _latent_reader(cfg, params, cache, positions)
        else:
            self.read = _cache_reader(cfg, params, cache, positions, kinds, commit is not None)
            self.from_latent = dict.fromkeys(kinds, False)

    def real(self):
        """``valid``, or all of them."""
        return jnp.ones((self.B, self.T), bool) if self.valid is None else self.valid

    def scope(self):
        """The name in front of what these rows run alone: ``beside`` for a
        second set of rows (``decode_forward``), none for the first."""
        return scope("beside") if self.second else contextlib.nullcontext()


def _join(parts):
    """Each set's [B, T, ...] as one [1, all of their rows' tokens, ...]: what
    multiplies by a weight runs on every set's rows together, and the weight
    is read once. One set is itself."""
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate([x.reshape((1, -1) + x.shape[2:]) for x in parts], axis=1)


def _split(x, shapes):
    """``_join``'s inverse: [1, all tokens, ...] as a [B, T, ...] for each
    (B, T) of ``shapes`` again."""
    if len(shapes) == 1:
        return [x]
    ends = np.cumsum([B * T for B, T in shapes])
    return [x[:, end - B * T:end].reshape((B, T) + x.shape[2:])
            for (B, T), end in zip(shapes, ends)]


def decode_forward(
    params, cache, tokens, positions, cfg, valid=None, loras=None, adapter_ids=None,
    with_logits: bool = True, logits_at=None, start_pos=None, beside=None, block_commit=None,
):
    """The body of ``prefill``, ``decode_step`` and ``block_step`` for every model. tokens:
    [B, T]; positions: [B, T]. New k/v are written into the cache before
    attention so new tokens attend to themselves and to all prior cache
    slots. ``valid`` [B, T] marks real (non-padding) tokens; padding writes
    leave the cache's old bytes where they are, so later decode steps never
    attend to stale slots and whatever copies a stripe out (the engine's
    stripe-to-slot copy, the prefix cache's store, the disaggregated
    hand-over) carries none.

    Three forms of one write, chosen from what is static at trace time, with
    the same bytes in the same slots (``_cache_writer``). ``start_pos`` [B] is
    the caller's word that row ``b``'s positions are ``start_pos[b] +
    arange(T)`` (every call through ``prefill``): while ``B <=
    _BLOCK_WRITE_MAX_BATCH`` and the chunk fits the cache (``T <= S``) each
    row is one contiguous block a tensor and layer (``_write_block``: padding
    and the stripe's end keep old bytes). A decode step's rows (``T == 1``,
    every row at an unrelated position, no ``start_pos``; alone or beside a
    chunk) go through the write kernel wherever ``writes_rows`` says the
    shapes take it (``ops/cache_write.py``: one call a layer for keys and
    values and every row, the cache where it lies). Anything else (a wide
    batch, rows a block wide, a latent cache, heads narrower than a lane
    tile, a cache or parameters on a mesh) is the ``[B, K, T]``-index scatter
    with ``mode="drop"``.
    The cache's position axis is never sharded (``llm/spmd.py`` shards the
    key-value heads), so a block partitions over heads as the scatter does.

    Row b's positions are consecutive from ``positions[b, 0]`` (``prefill``
    and ``decode_step`` make no others), which is what lets the read
    (``_cache_reader``) bound a row by its first position: a decode step's
    kernel between the row's own bounds, a sliding layer's window cut out of
    the stripe.

    ``beside``: ``(cache, tokens [B2], live [B2] or None)``, a second set of
    rows that takes the same walk through the layers: the rows of a decode
    step on a cache of their own, each at its cache's ``length``, beside a
    prompt's chunk (``llm/engine.py programs``). Whatever multiplies by a
    weight (the attention's and the state-space mixers' projections in and
    out, the feed-forwards with their router, sort and grouped matmuls, the
    head) runs on both sets' rows as one matrix (``_join``), so the weights
    and the touched experts' banks are read once for both; whatever reads or
    writes a cache runs for each set in its own form, under the scope it has
    alone: the chunk's block write and attention over its stripe, the decode
    rows' write and kernels between each row's own bounds. A row that is
    not ``live`` writes nothing and leaves its length and state where they
    were (its arithmetic is done and dropped, as a free slot's is in a decode
    step). Every row gets the arithmetic it gets alone, in matmuls of more
    rows: on a chip its numbers can differ in the last bit by what shares
    the launch. The routing counts are of both sets' rows together and ride
    out with the first cache. Returns ``(logits, cache, logits2 [B2, 1, V],
    cache2)`` then. Not with ``loras``, nor over a latent cache.

    What the second set's rows run alone is named so: one more part,
    ``beside``, in front of the name it has without them (``_Rows.scope``:
    ``beside/kv_write``, ``beside/attn_core/window``,
    ``beside/attn_core/ssm_mixer/ssm_step``, ``beside/attn_qkv/cca_conv``; a
    middle chunk's last feed-forward where ``narrow`` holds and its head,
    ``beside/moe_ffn/experts`` and ``beside/lm_head``), so that a device trace
    can tell a carried step's own operations from the chunk's
    (``benchmark/carried.py``); a reader that knows no such part books the
    operation to the known name behind it, as before. What multiplies both
    sets' rows as one matrix (``_join``) keeps its name and is booked to the
    chunk: 32-64 rows beside a chunk's 64-1,024 tokens.

    ``block_commit`` [B] (``models/llama.py block_step``): the rows are one
    block each of a model that generates by blocks, every query of a row
    reading the row's stripe up to the block's end (``_cache_reader``), and a
    row's length advances by ``T`` where it commits and stays elsewhere: the
    block's keys and values are written either way (the read needs them),
    behind a length that did not move nothing reads them.

    Such a model's second set is a block a row as well: ``beside`` is then
    ``(cache, tokens [B2, T], live [B2] or None, commit [B2])``, the pool's
    block step beside a prompt's chunk. The rows stand at ``length +
    arange(T)``, are written and read as ``block_step``'s are (the folded
    decode kernel, a stripe read once for the block's queries, under
    ``beside/attn_core/block``), and a row's length advances by ``T`` where it
    commits *and* is live: a row that is not live writes its block behind a
    length that did not move, as a forward that does not commit does.
    ``logits2`` is then a row a position, ``[1, B2 * T, V]``.

    ``loras``/``adapter_ids``: stacked LoRA adapters + per-sequence adapter
    index (0 = base), over layers that are alike.
    ``with_logits=False`` (a prompt's middle chunk) only extends the cache
    and skips the LM head (the vocab projection reads ~0.8 GB of weights at
    128k vocab; chunked admission would pay it once per chunk otherwise).
    ``logits_at`` [B]: project the LM head at ONLY this position per
    sequence (returns [B, 1, V]) — prefill needs one next-token
    distribution, and the full [B, T, V] projection is the single biggest
    prefill allocation (0.5 GB/seq at 7B/128k-vocab scale: the allocation
    that kept 7B from fitting one v5e chip)."""
    pl = plan(cfg)
    kinds = {kind for kind, _, _ in pl.kinds}
    if loras is not None and pl.by_kind:
        raise NotImplementedError("LoRA adapters over layers that are not alike")
    if beside is not None and (loras is not None or "latent" in kinds):
        raise NotImplementedError(
            "models/patterned.py: rows beside a chunk run without LoRA adapters and not "
            "over a latent cache")
    if "latent" in kinds and any(
        jax.typeof(x).sharding.mesh.size > 1 for x in (cache["k"], *jax.tree.leaves(params))
    ):
        raise NotImplementedError(
            "models/patterned.py: latent attention runs on one device (no rule "
            "places its cache or its projections on a mesh)"
        )
    with scope("embed"):
        x = params["embed"][
            _join([tokens] if beside is None else [
                tokens, beside[1] if len(beside) == 4 else beside[1][:, None]])
        ].astype(cfg.dtype)
        x = _times(x, cfg.embedding_multiplier)
    sets = [_Rows(cfg, params, kinds, cache, tokens, positions, valid, start_pos,
                  commit=block_commit)]
    if beside is not None and len(beside) == 4:  # a block a row: written whole, live or not
        cache2, tokens2, live, commit = beside
        with scope("beside"):
            at = cache2["length"][:, None] + jnp.arange(tokens2.shape[1], dtype=jnp.int32)[None, :]
            commit = commit if live is None else commit & live
        sets.append(_Rows(cfg, params, kinds, cache2, tokens2, at, None, None, second=True,
                          commit=commit))
    elif beside is not None:
        cache2, tokens2, live = beside
        sets.append(_Rows(cfg, params, kinds, cache2, tokens2[:, None], cache2["length"][:, None],
                          None if live is None else live[:, None], None, second=True))
    from_latent = sets[0].from_latent  # a latent model's rows are one set
    shapes = [(rows.B, rows.T) for rows in sets]
    positions = _join([rows.positions for rows in sets])
    # the real tokens of all rows, where any set has tokens that are none
    real = None if all(rows.valid is None for rows in sets) else _join(
        [rows.real() for rows in sets])
    if kinds & (set(STATE_MIXERS) | set(STRIPE_STATE)) and any(
        jax.typeof(x).sharding.mesh.size > 1 for x in (cache["k"], *jax.tree.leaves(params))
    ):
        raise NotImplementedError(
            "models/patterned.py: layers that keep a state run on one device (no rule places "
            "their state or their projections on a mesh)"
        )

    # A middle chunk beside decode rows: nothing reads the chunk's own rows
    # behind the last layer's mixer, so where that layer is traced on its own
    # its feed-forward is the decode rows' alone (a chunk alone has no reader
    # of it at all, and the compiler drops it whole; a layer that is a pass of
    # the loop runs for every row either way)
    narrow = (beside is not None and not with_logits and pl.kinds[-1][2] != "none"
              and (pl.reps == 0 or pl.tail_from < cfg.n_layers) and cfg.loop_passes == 1)
    # a model with routed experts carries its routing counts beside x, one
    # with layers that keep a state each set's ``STATE_LEAVES`` behind those
    stats0 = (jnp.zeros((len(moe_stats_names(cfg)),), jnp.int32),) if cfg.moe_experts else ()
    state0 = tuple(
        {name: rows.cache[name] for name in STATE_LEAVES if name in rows.cache} for rows in sets)

    def layer(lay: _Layer, carry):
        x, kv, stats, state, route = carry
        if lay.kind in STATE_MIXERS:
            project, mix, out, names = STATE_MIXERS[lay.kind]
            h = _rmsnorm(x, params["attn_norm"][lay.mixer_i], cfg.rms_eps, cfg.fused_rmsnorm)
            parts, gate = project(params, lay, h, real, cfg)
            mixed = []
            for rows, leaves, *of_rows in zip(
                    sets, state, *(_split(part, shapes) for part in parts)):
                with rows.scope():
                    mixed.append(mix(params, lay, *of_rows, *(leaves[name] for name in names),
                                     rows.valid, cfg))
            state = tuple({**leaves, **dict(zip(names, new))}
                          for leaves, (_, *new) in zip(state, mixed))
            ys = [y for y, *_ in mixed]
            y = ys[0] if len(sets) == 1 else _join(
                [y.reshape(rows.B, rows.T, -1) for rows, y in zip(sets, ys)])
            x = _joined(params, "attn_scale", "attn_out_norm", lay.mixer_i, x,
                        out(params, lay, y, gate, cfg), cfg)
        elif lay.kind != "none":
            h = _rmsnorm(x, params["attn_norm"][lay.mixer_i], cfg.rms_eps, cfg.fused_rmsnorm)
            index = None
            if lay.latent:  # k: the shared rotated key; v: the normed latent
                q, k, v, cq = _latent_qkv(params, lay, h, positions, cfg)
                if lay.indexed:
                    *index, k_index = _index_qkw(params, lay, h, cq, positions, cfg)
            elif lay.kind in STRIPE_STATE:
                # each set's rows behind their own row ``attn_i`` of the leaf
                project, qkv, leaf = STRIPE_STATE[lay.kind]
                of_sets = []
                for rows, leaves, *parts in zip(
                        sets, state, *(_split(t, shapes) for t in project(params, lay, h))):
                    with rows.scope():
                        of_sets.append(qkv(params, lay, *parts, leaves[leaf][lay.attn_i],
                                           rows.positions, rows.valid, cfg))
                q, k, v, carried = zip(*of_sets)
                state = tuple(
                    {**leaves, leaf: jax.lax.dynamic_update_index_in_dim(
                        leaves[leaf], new, lay.attn_i, 0)}
                    for leaves, new in zip(state, carried))
            else:
                q, k, v = _qkv(params, lay, h, positions, cfg, loras, adapter_ids)
                q, k, v = (_split(t, shapes) for t in (q, k, v))
            attn, new_kv = [], []
            # the stripes this layer's kind keeps, and its row in them
            names, row = _STRIPES_OF_KIND.get(lay.kind, ("k", "v")), (
                lay.attn_i if lay.latent else lay.cache_i)
            for j, rows in enumerate(sets):
                ck_all, cv_all = (kv[j][name] for name in names)
                # a latent model's q is a pair, and its rows are one set
                qj, kj, vj = (q, k, v) if lay.latent else (q[j], k[j], v[j])
                written, chosen = {}, ()
                with rows.scope(), scope("kv_write"):
                    if lay.latent:  # zeros up to the cache's row of whole lane tiles (``init_kv_cache``)
                        kj = jnp.pad(kj, ((0, 0),) * 3 + ((0, ck_all.shape[-1] - kj.shape[-1]),))
                    ck_all, cv_all = rows.write(row, (ck_all, cv_all), (kj, vj))
                    if index:
                        ki_all = kv[j]["k_index"]
                        k_index = jnp.pad(
                            k_index, ((0, 0),) * 3 + ((0, ki_all.shape[-1] - k_index.shape[-1]),))
                        written["k_index"], = rows.write(row, (ki_all,), (k_index,))
                if index and rows.select is not None:
                    with scope("attn_core"):  # ``attn_index`` and ``attn_select`` inside it
                        chosen = (rows.select(index, written["k_index"], lay),)
                # (a block step's read has a name of its own under ``attn_core``)
                with rows.scope(), scope("attn_core"), (
                        lay.inner_scope() if rows.commit is None else scope("block")):
                    attn.append(rows.read(qj, ck_all, cv_all, lay, *chosen))
                new_kv.append({**kv[j], names[0]: ck_all, names[1]: cv_all, **written})
            kv = tuple(new_kv)
            x = _attn_out(params, lay, x, h, _join(attn), cfg, from_latent[lay.kind])
        if lay.mlp != "none":
            alone = narrow and lay.last  # the second set's rows alone
            if alone:
                x, *route = (_split(t, shapes)[1] for t in (x, *route))
            with (sets[1] if alone else sets[0]).scope():
                x, layer_stats, route = _feed_forward(params, lay, x, cfg, tuple(route))
            stats = tuple(s + layer_stats for s in stats)
        return (x, kv, stats, state, route)

    stripes = tuple(stripe_cache_shapes(cfg, 1, 1))  # by name
    carry = (x, tuple({name: rows.cache[name] for name in stripes} for rows in sets),
             stats0, state0, _router_stream(cfg, x))

    def head_rows(x):
        """The rows of ``x`` whose next token is asked for: one position a row
        of the first set (``logits_at``) or all of them, and every row of a
        second set; of a middle chunk the second set's alone."""
        heads = [None, x] if narrow else _split(x, shapes)
        if logits_at is not None:
            # the one requested hidden state a sequence BEFORE the vocab
            # projection: [B, T, e] -> [B, 1, e]
            heads[0] = jnp.take_along_axis(heads[0], logits_at[:, None, None], axis=1)
        # a block's rows, a row a position: the head's [1, B * T, V] lies in whole
        # tiles of 8 rows, where [B, T, V] pads each row's T positions to 8 and is relaid
        heads = [h if h is None or rows.commit is None else h.reshape((1, -1) + h.shape[2:])
                 for rows, h in zip(sets, heads)]
        return heads if with_logits else heads[1:]

    if cfg.loop_passes > 1:
        # the stack several times: each pass ends under the final norm, and the
        # rows the head will read pick their pass as they go (``_loop_exit``)
        heads = jax.eval_shape(head_rows, x)  # their shapes: the streams are the passes'
        ex = _loop_exit_start(cfg, jax.eval_shape(_join, heads)) if heads else {
            "passes": jnp.zeros((), jnp.int32)}

        def end_pass(t, carry, ex):
            x, ex = _loop_exit(params, cfg, t, carry[0], ex, lambda x: _join(head_rows(x)))
            return (x, *carry[1:]), ex

        (x, kv, stats, state, _), ex = _run_passes(cfg, layer, end_pass, carry, ex)
    else:
        x, kv, stats, state, _ = _run_layers(cfg, layer, carry)
    new_caches = []
    for rows, written, leaves in zip(sets, kv, state):
        grew = rows.T if rows is sets[0] or rows.valid is None else rows.valid.sum(
            axis=1, dtype=jnp.int32)
        if rows.commit is not None:
            grew = jnp.where(rows.commit, rows.T, 0)
        new_cache = {**written, "length": rows.cache["length"] + grew, **leaves}
        _ride_stats(rows.cache, new_cache, stats)
        if cfg.loop_passes > 1 and "loop_stats" in rows.cache:
            # a second set's rows count where they are live, a first set's all
            # unless its cache says which (``loop_live`` [B]: a decode step's
            # slots that hold a request, as the engine knows them at its launch)
            counted = [r.real() if r is not sets[0] else r.cache.get(
                "loop_live", jnp.ones((r.B,), bool))[:, None]
                       for r in (sets if with_logits else sets[1:])]
            new_cache["loop_stats"] = rows.cache["loop_stats"] + _loop_stats(cfg, ex, counted)
        new_caches.append(new_cache)
    if cfg.loop_passes == 1:
        heads = head_rows(x)
    logits = []
    if heads:
        # a middle chunk's head is the second set's alone
        with (sets[0] if with_logits else sets[-1]).scope():
            x = ex["h"] if cfg.loop_passes > 1 else _rmsnorm(
                _join(heads), params["final_norm"], cfg.rms_eps, cfg.fused_rmsnorm)
            logits = _split(_project_logits(x, params, cfg, None), [h.shape[:2] for h in heads])
    if not with_logits:
        logits = [None] + logits
    if beside is None:
        return logits[0], new_caches[0]
    return logits[0], new_caches[0], logits[1], new_caches[1]


def _loop_stats(cfg, ex: dict, counted: list):
    """What one forward of a stack run several times adds to a cache's
    ``loop_stats`` leaf (int32 [2 + passes], ``LOOP_STATS`` and then a count a
    pass; how the engine's programs get them out beside their tokens, as
    ``_ride_stats``'s): one forward, the passes its stack ran, and of the rows
    whose next token was asked for (``counted``: a mask [B, 1] each set of
    them, false where a row is not live) how many read each pass. A first
    set's rows with all their positions asked for count their last."""
    hist = jnp.zeros((cfg.loop_passes,), jnp.int32)
    if "at" in ex:
        at = ex["at"][:, -1:] if len(counted) == 1 else ex["at"]
        picked = at.reshape(-1)[:, None] == jnp.arange(cfg.loop_passes)[None, :]
        hist = (picked & _join(counted).reshape(-1)[:, None]).sum(axis=0, dtype=jnp.int32)
    return jnp.concatenate([jnp.ones((1,), jnp.int32), ex["passes"][None], hist])


def state_mixer_forms(cfg) -> dict:
    """kind -> {"chunk", "step"}: which form a model's mixers that keep a
    state take for a prompt chunk and for a decode step, ``"kernel"`` or
    ``"plain"``. Static a shape, and asked of the functions the trace asks
    (``ops/ssm.py step_groups``; ``ops/kda.py scan_heads`` and ``step_heads``;
    the state-space chunk has the one form, ``ssm_scan``), so a run says which
    program it measured (``llm/engine.py get_stats()["pools"][i]
    ["state_mixer_forms"]``). Empty for a model whose slots are stripes alone.
    (At the module's end: the lines the served programs are traced from keep
    their numbers, and with them the compile cache's keys.)"""
    from ray_tpu.ops import kda, ssm

    def form(tiles):
        return "plain" if tiles is None else "kernel"

    pl, forms = plan(cfg), {}
    if pl.n_ssm:
        forms["ssm"] = {"chunk": "plain", "step": form(ssm.step_groups(
            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups))}
    if pl.n_kda:
        H, D = cfg.kda_heads, cfg.kda_head_dim
        forms["kda"] = {"chunk": form(kda.scan_heads(H, D, D, cfg.kda_chunk)),
                        "step": form(kda.step_heads(H, D, D))}
    return forms
