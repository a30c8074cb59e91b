"""Llama-family decoder, TPU-first.

Pure-functional JAX: params are a pytree of stacked per-layer arrays scanned
with ``lax.scan`` (one compiled layer body regardless of depth — keeps XLA
compile time flat and lets ``jax.checkpoint`` remat per layer), bfloat16
matmuls onto the MXU, logical-dimension sharding annotations resolved against
whatever mesh the caller built (``ray_tpu.parallel.mesh``).

Capability parity note: the reference's serving layer configures
tensor/pipeline parallel degrees as vLLM engine kwargs
(``llm/_internal/serve/deployments/llm/vllm/vllm_models.py:176-190``) and has
no native sequence parallelism (SURVEY §5). Here TP is a sharding rule, and
SP is ring/ulysses attention selected by config.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ray_tpu.parallel.mesh import logical_sharding, with_sharding
from ray_tpu.parallel.ring_attention import (
    dense_attention,
    full_attention_reference,
    ring_attention,
)
from ray_tpu.parallel.ulysses import ulysses_attention

# ``jax.named_scope`` names, one vocabulary for the train step and the
# engine's programs (which add ``kv_write``, ``sampling``, ``prefix_seed``):
# embed, norm, attn_qkv (projections and rope), attn_core (the kernel; in
# decode, attention over the cache), attn_out, ffn, moe_ffn, lm_head, loss,
# optimizer, grad_norm. They sit inside the scanned layer body, so every
# layer's work pools under one name, and are metadata only: each lands in the
# ``op_name`` of the operations traced under it, which is what a device trace
# is attributed by. The backward pass needs none of its own: JAX writes
# ``jvp(..)`` and ``transpose(jvp(..))`` around the forward scope.
scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # 'full' | 'ring' | 'ulysses' | 'splash' | 'flash'. ring/ulysses engage
    # when the mesh has sp>1; splash/flash are Pallas TPU kernels with no
    # partitioning rule: they need a tpu backend and an unsharded program,
    # and raise anywhere else
    attention: str = "full"
    # route rmsnorm through the fused Pallas kernel (ray_tpu.ops.rmsnorm).
    # Opt-in: pallas_call has no partitioning rule, so under a sharded pjit
    # program XLA would replicate around it — use on single-device/replicated
    # paths (e.g. the serving engine) where it runs in one VMEM pass.
    fused_rmsnorm: bool = False
    # fused blockwise cross-entropy (ops.cross_entropy): never materializes
    # the [B, S, V] logit tensor in the train loss
    fused_ce: bool = True
    remat: bool = True
    # 'full' = recompute everything in backward; 'dots' = save matmul
    # outputs, recompute elementwise (jax.checkpoint_policies.dots_saveable)
    # — trades a little activation memory for ~25% fewer backward FLOPs
    remat_policy: str = "full"
    tie_embeddings: bool = False
    # --- mixture of experts (expert parallelism over the ep mesh axis) ---
    # 0 = dense FFN; >0 replaces every layer's FFN with a top-k routed
    # expert bank (ray_tpu.parallel.moe — all_to_all dispatch over ICI).
    # Reference delegates EP to vLLM engine kwargs (SURVEY §2.4); native here.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # --- pipeline parallelism (pp mesh axis) ---
    # microbatch count for the GPipe schedule when the mesh has pp>1;
    # 0 = default 2*pp. Layers split into pp equal stages.
    pp_microbatches: int = 0
    # width of one attention head; 0 = d_model // n_heads
    head_width: int = 0
    # expert width (0 = d_ff), a shared expert beside the routed ones (its
    # width; 0 = none), and the factor on the routed experts' sum
    moe_d_ff: int = 0
    moe_shared_d_ff: int = 0
    moe_routed_scale: float = 1.0
    # --- layers that are not alike (``models/patterned.py`` runs them) ---
    # ``layer_types``: 'full' | 'sliding' for each layer; () = every layer as
    # this file has it, and none of the fields below is read. A sliding
    # layer's query at position i sees keys j with 0 <= i - j < window.
    layer_types: tuple = ()
    heads_per_layer: tuple = ()  # query heads, one count an attention kind
    mlp_types: tuple = ()  # 'dense' | 'sparse' (the moe_* fields) by layer
    sliding_window: int = 0
    # sliding layers rotate the whole head at this theta; full layers rotate
    # the first ``rope_partial`` of it at ``rope_theta``, with YaRN inverse
    # frequencies where ``yarn_factor`` is set (cos and sin times
    # ``yarn_attention_factor``)
    rope_theta_sliding: float = 10000.0
    rope_partial: float = 1.0
    yarn_factor: float = 0.0
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    # sigmoid of a [d_model, heads] projection of the layer's normed input,
    # times each head's attention output before wo
    attn_gate: bool = False

    def __post_init__(self):
        for name in ("layer_types", "heads_per_layer", "mlp_types"):
            value = tuple(getattr(self, name))
            object.__setattr__(self, name, value)
            if self.layer_types and len(value) != self.n_layers:
                raise ValueError(
                    f"{name} has {len(value)} entries for n_layers={self.n_layers}"
                )

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    def num_params(self) -> int:
        return sum(math.prod(shape) for shape in _param_shapes(self).values())

    # ---- presets ----
    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test/dryrun-size model (runs on the virtual 8-CPU mesh)."""
        d = dict(
            vocab_size=256,
            d_model=64,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            d_ff=128,
            max_seq_len=128,
            dtype=jnp.float32,
            remat=False,
        )
        d.update(kw)
        return LlamaConfig(**d)

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        d = dict(
            vocab_size=32000,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=32,
            d_ff=11008,
            max_seq_len=4096,
        )
        d.update(kw)
        return LlamaConfig(**d)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        d = dict(
            vocab_size=128256,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            d_ff=14336,
            max_seq_len=8192,
            rope_theta=500000.0,
        )
        d.update(kw)
        return LlamaConfig(**d)

    @staticmethod
    def llama32_3b(**kw) -> "LlamaConfig":
        d = dict(
            vocab_size=128256,
            d_model=3072,
            n_layers=28,
            n_heads=24,
            n_kv_heads=8,
            d_ff=8192,
            max_seq_len=8192,
            rope_theta=500000.0,
            tie_embeddings=True,
        )
        d.update(kw)
        return LlamaConfig(**d)

    @staticmethod
    def llama3_70b(**kw) -> "LlamaConfig":
        d = dict(
            vocab_size=128256,
            d_model=8192,
            n_layers=80,
            n_heads=64,
            n_kv_heads=8,
            d_ff=28672,
            max_seq_len=8192,
            rope_theta=500000.0,
        )
        d.update(kw)
        return LlamaConfig(**d)

    @staticmethod
    def laguna_xs2(**kw) -> "LlamaConfig":
        """poolside Laguna-XS.2 (33B-A3B) as its config.json has it: layer 0
        full attention with a dense feed-forward, then sliding, sliding,
        sliding, full with 256 experts of width 512, 8 a token, and one
        shared expert. A caller that cuts ``n_layers`` gets the first
        entries of the three per-layer lists unless it gives its own.
        Inferred, not in the config: the gate is one scalar a head
        (``gating: true``), the router a float32 softmax, top 8 renormalised,
        the shared expert ungated, no query/key norm."""
        d = dict(
            vocab_size=100352,
            d_model=2048,
            n_layers=40,
            n_heads=48,
            n_kv_heads=8,
            head_width=128,
            d_ff=8192,
            max_seq_len=262144,
            rms_eps=1e-6,
            rope_theta=500000.0,
            rope_partial=0.5,
            yarn_factor=64.0,
            yarn_original_len=4096,
            yarn_beta_fast=64.0,
            yarn_beta_slow=1.0,
            yarn_attention_factor=1.4158883083359672,
            rope_theta_sliding=10000.0,
            sliding_window=512,
            attn_gate=True,
            moe_experts=256,
            moe_top_k=8,
            moe_d_ff=512,
            moe_shared_d_ff=512,
            moe_routed_scale=2.5,
        )
        d.update(kw)
        n = d["n_layers"]
        d.setdefault("layer_types", tuple(
            "full" if i % 4 == 0 else "sliding" for i in range(n)))
        d.setdefault("heads_per_layer", tuple(
            48 if t == "full" else 64 for t in d["layer_types"]))
        d.setdefault("mlp_types", ("dense",) + ("sparse",) * (n - 1))
        return LlamaConfig(**d)

    @staticmethod
    def laguna_tiny(**kw) -> "LlamaConfig":
        """Test-size model with ``laguna_xs2``'s pattern: a leading full,
        dense layer and one period (sliding x3, full) of expert layers."""
        d = dict(
            vocab_size=256, d_model=64, n_layers=5, n_heads=6, n_kv_heads=2,
            head_width=16, d_ff=128, max_seq_len=128, dtype=jnp.float32,
            remat=False, rms_eps=1e-6, rope_theta=500000.0, rope_partial=0.5,
            yarn_factor=4.0, yarn_original_len=16, yarn_beta_fast=8.0,
            yarn_beta_slow=1.0, yarn_attention_factor=1.2,
            rope_theta_sliding=10000.0, sliding_window=8, attn_gate=True,
            moe_experts=16, moe_top_k=4, moe_d_ff=32, moe_shared_d_ff=32,
            moe_routed_scale=2.5,
            layer_types=("full", "sliding", "sliding", "sliding", "full"),
            heads_per_layer=(6, 8, 8, 8, 6),
            mlp_types=("dense", "sparse", "sparse", "sparse", "sparse"),
        )
        d.update(kw)
        return LlamaConfig(**d)

# Logical dims per parameter (leading 'layer' dim on stacked block params).
_PARAM_DIMS = {
    "embed": ("vocab", "embed"),
    "unembed": ("embed", "vocab"),
    "final_norm": ("norm",),
    "wq": (None, "embed", "heads", "head_dim"),
    "wk": (None, "embed", "kv_heads", "head_dim"),
    "wv": (None, "embed", "kv_heads", "head_dim"),
    "wo": (None, "heads", "head_dim", "embed"),
    "w_gate": (None, "embed", "mlp"),
    "w_up": (None, "embed", "mlp"),
    "w_down": (None, "mlp", "embed"),
    "attn_norm": (None, "norm"),
    "mlp_norm": (None, "norm"),
    # MoE variant: per-layer expert banks (expert dim -> ep mesh axis)
    "moe_router": (None, "embed", None),
    "moe_w_gate": (None, "expert", "embed", "mlp"),
    "moe_w_up": (None, "expert", "embed", "mlp"),
    "moe_w_down": (None, "expert", "mlp", "embed"),
    "moe_shared_gate": (None, "embed", "mlp"),
    "moe_shared_up": (None, "embed", "mlp"),
    "moe_shared_down": (None, "mlp", "embed"),
}
# layers that are not alike (models/patterned.py): the leaves whose shape
# follows the attention kind are stacked one kind at a time
for _kind in ("full", "sliding"):
    _PARAM_DIMS["wq_" + _kind] = _PARAM_DIMS["wq"]
    _PARAM_DIMS["wo_" + _kind] = _PARAM_DIMS["wo"]
    _PARAM_DIMS["wg_" + _kind] = (None, "embed", "heads")


def param_logical_dims(path, leaf):
    """For ``ray_tpu.parallel.mesh.shard_params``: path -> logical dims."""
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    return _PARAM_DIMS[name]


def param_shardings(cfg: LlamaConfig, mesh: Mesh, rules=None):
    """NamedSharding pytree matching ``init_params`` structure."""
    shapes = _param_shapes(cfg)
    return {
        k: logical_sharding(mesh, *_PARAM_DIMS[k], rules=rules, shape=shapes[k])
        for k in shapes
    }


def _moe_shapes(cfg: LlamaConfig, n: int) -> dict[str, tuple]:
    """The leaves of ``n`` stacked expert layers."""
    e, E, f = cfg.d_model, cfg.moe_experts, cfg.moe_d_ff or cfg.d_ff
    shapes = {
        "moe_router": (n, e, E),
        "moe_w_gate": (n, E, e, f),
        "moe_w_up": (n, E, e, f),
        "moe_w_down": (n, E, f, e),
    }
    if cfg.moe_shared_d_ff:
        fs = cfg.moe_shared_d_ff
        shapes.update({
            "moe_shared_gate": (n, e, fs),
            "moe_shared_up": (n, e, fs),
            "moe_shared_down": (n, fs, e),
        })
    return shapes


def _param_shapes(cfg: LlamaConfig) -> dict[str, tuple]:
    if cfg.layer_types:
        from ray_tpu.models.patterned import param_shapes

        return param_shapes(cfg)
    e, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    h, kv, hd, L = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    shapes = {
        "embed": (v, e),
        "final_norm": (e,),
        "wq": (L, e, h, hd),
        "wk": (L, e, kv, hd),
        "wv": (L, e, kv, hd),
        "wo": (L, h, hd, e),
        "attn_norm": (L, e),
        "mlp_norm": (L, e),
    }
    if cfg.moe_experts:
        shapes.update(_moe_shapes(cfg, L))
    else:
        shapes.update(
            {"w_gate": (L, e, f), "w_up": (L, e, f), "w_down": (L, f, e)}
        )
    if not cfg.tie_embeddings:
        shapes["unembed"] = (e, v)
    return shapes


# How a served leaf lies on the chip. A matmul reads a layer's slice of a
# stacked leaf in place only where the axis it contracts is one of the
# leaf's two minor axes: the chip tiles those two. ``wo`` [.., h, hd, e] and
# the feed-forward leaves [.., e, f] are stored that way. A stacked attention
# input projection [.., e, h, hd] is not: it contracts ``e`` and is tiled over
# heads x head width, so every layer of every decode step and prefill chunk
# copied its slice (50 MB a layer at Mistral-7B widths, 1.1 of a 14.7 ms
# decode step: PERF.md section 6, PR 29). Held head-major, ``e`` lies beside
# the head width; logical shape, values and sharding are what they were.
HEAD_MAJOR = (0, 2, 1, 3)


def serving_layouts(names) -> dict[str, tuple]:
    """name -> ``major_to_minor`` for the leaves among ``names`` that the
    engine holds in another device layout than the default, from what a leaf
    is (``_PARAM_DIMS``): stacked, of rank 4, contracting its ``embed`` axis
    with the heads and the head width behind it."""
    return {
        name: HEAD_MAJOR for name in names
        if len(dims := _PARAM_DIMS.get(name, ())) == 4 and dims[:2] == (None, "embed")
    }


def _layer_keys(cfg: LlamaConfig) -> tuple:
    base = ("wq", "wk", "wv", "wo", "attn_norm", "mlp_norm")
    if cfg.moe_experts:
        return base + tuple(_moe_shapes(cfg, 1))
    return base + ("w_gate", "w_up", "w_down")


def init_params(key, cfg: LlamaConfig, mesh: Optional[Mesh] = None):
    """Initialize params; if a mesh is given, each leaf is created directly
    with its NamedSharding (no host-side full copy — jit init per leaf)."""
    shapes = _param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    # the contraction each attention projection takes part in: its weight
    # is kept [.., e, h, hd] / [.., h, hd, e], so shape[-2] is not it. (Read
    # as shape[-2], q/k/v came out 11-20x too large at Llama-3.2-3B widths,
    # the softmax saturated, and the gradient norm grew ~140x every two
    # layers: 158 at 2 layers, 8.7e7 at 8, on the chip.)
    # (``wq_full``, ``wo_sliding``: a patterned model's leaves by kind)
    def fan_in_of(name, shape):
        if name.startswith(("wq", "wk", "wv")):
            return cfg.d_model
        if name.startswith("wo"):
            return shape[-3] * shape[-2]
        return shape[-2] if len(shape) > 1 else shape[0]

    params = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if "norm" in name:
            maker = lambda shape=shape: jnp.ones(shape, cfg.dtype)
        else:
            fan_in = fan_in_of(name, shape)
            std = fan_in**-0.5
            maker = lambda k=k, shape=shape, std=std: (
                jax.random.normal(k, shape, jnp.float32) * std
            ).astype(cfg.dtype)
        if mesh is not None:
            sh = logical_sharding(mesh, *_PARAM_DIMS[name], shape=shape)
            params[name] = jax.jit(maker, out_shardings=sh)()
        else:
            params[name] = maker()
    return params


@scope("norm")
def _rmsnorm(x, w, eps, fused: bool = False):
    if fused:
        from ray_tpu.ops import rmsnorm as _fused_rmsnorm

        # one VMEM pass; output dtype = x.dtype (model weights share cfg.dtype)
        return _fused_rmsnorm(x, w, eps)
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * w


def _rope(x, positions, theta):
    """x: [B, T, H, D], positions: [B, T]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _attention(q, k, v, cfg: LlamaConfig, mesh: Optional[Mesh]):
    """q: [B, T, H, D]; k/v: [B, T, KV, D]. Returns [B, T, H, D].

    The dense path is GQA-native (kv heads contracted directly, never
    repeated — ``jnp.repeat`` over a tp-sharded heads axis forces SPMD to
    replicate the tensor). Ring/Ulysses/flash kernels expect equal head
    counts, so those paths still expand kv heads first."""
    sp = (
        mesh.shape.get("sp", 1)
        if mesh is not None and "sp" in mesh.axis_names
        else 1
    )
    kernel = cfg.attention in ("flash", "splash")
    if kernel:
        # pallas kernels are TPU-only and have no SPMD partitioning rule
        # (single-chip or per-replica programs only). Dense attention in
        # their place would be a different program under the same name.
        backend = jax.default_backend()
        unsharded = mesh is None or all(s == 1 for s in mesh.shape.values())
        if backend != "tpu" or not unsharded:
            raise ValueError(
                f"attention={cfg.attention!r} needs a tpu backend and an "
                f"unsharded program (backend {backend!r}, mesh "
                f"{dict(mesh.shape) if mesh is not None else None}); "
                "use attention='full'"
            )
    needs_repeat = kernel or (
        sp > 1 and cfg.attention == "ulysses" and cfg.n_kv_heads % sp != 0
    )
    groups = cfg.n_heads // cfg.n_kv_heads
    if needs_repeat and groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    if sp > 1 and cfg.attention == "ring":
        return ring_attention(q, k, v, mesh, causal=True)
    if sp > 1 and cfg.attention == "ulysses":
        return ulysses_attention(q, k, v, mesh, causal=True)
    if cfg.attention == "splash":
        return _splash_attention(q, k, v)
    if cfg.attention == "flash":
        return _flash_attention(q, k, v)
    return dense_attention(q, k, v, causal=True)


def _splash_attention(q, k, v):
    """Splash attention (Pallas TPU): the production blockwise-causal kernel
    — never materializes [B, H, T, S] scores in HBM, and its sparse-mask
    grid skips fully-masked key blocks outright (half the work for causal).
    Block sizes tuned on v5e for T=2048, D=64: 1024×1024 measured 2.5×
    faster than dense XLA attention fwd+bwd (12.6ms vs 31.8ms at
    B8 H16 T2048 D64)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk,
        splash_attention_mask as _sm,
    )

    B, T, H, D = q.shape
    scale = D**-0.5
    qt = jnp.swapaxes(q, 1, 2) * scale  # [B, H, T, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    blk = min(1024, T)
    bs = _sk.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk,
    )
    mask = _sm.MultiHeadMask([_sm.CausalMask((T, T)) for _ in range(H)])
    kernel = _sk.make_splash_mha(
        mask=mask, head_shards=1, q_seq_shards=1, block_sizes=bs
    )
    out = jax.vmap(kernel)(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _flash_attention(q, k, v):
    """Pallas TPU flash attention: blockwise softmax in VMEM, never
    materializing the [B, H, S, S] score matrix in HBM — the single biggest
    HBM-bandwidth lever for long sequences (the kernel is TPU-only)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as _pallas_flash,
    )

    # [B, T, H, D] -> [B, H, T, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _pallas_flash(
        qt, kt, vt, causal=True, sm_scale=1.0 / math.sqrt(q.shape[-1])
    )
    return jnp.swapaxes(out, 1, 2)


def _layer(layer_params, x, positions, cfg: LlamaConfig, mesh: Optional[Mesh]):
    """One transformer block. Returns (x, aux) — aux is the MoE
    load-balancing loss (0.0 for dense layers)."""
    p = layer_params

    def c(y, *dims):
        return with_sharding(mesh, y, *dims) if mesh is not None else y

    h = _rmsnorm(x, p["attn_norm"], cfg.rms_eps, cfg.fused_rmsnorm)
    with scope("attn_qkv"):
        q = jnp.einsum("bte,ehd->bthd", h, p["wq"])
        k = jnp.einsum("bte,ehd->bthd", h, p["wk"])
        v = jnp.einsum("bte,ehd->bthd", h, p["wv"])
        q = c(_rope(q, positions, cfg.rope_theta), "batch", "seq", "heads", "head_dim")
        k = c(_rope(k, positions, cfg.rope_theta), "batch", "seq", "kv_heads", "head_dim")
    with scope("attn_core"):
        attn = _attention(q, k, v, cfg, mesh)
    with scope("attn_out"):
        x = x + c(jnp.einsum("bthd,hde->bte", attn, p["wo"]), "batch", "seq", "embed")

    h = _rmsnorm(x, p["mlp_norm"], cfg.rms_eps, cfg.fused_rmsnorm)
    if cfg.moe_experts:
        with scope("moe_ffn"):
            x2, aux = _moe_ffn(p, h, cfg, mesh)
            return x + c(x2, "batch", "seq", "embed"), aux
    with scope("ffn"):
        gate = jnp.einsum("bte,ef->btf", h, p["w_gate"])
        up = jnp.einsum("bte,ef->btf", h, p["w_up"])
        ff = c(jax.nn.silu(gate) * up, "batch", "seq", "mlp")
        x = x + c(jnp.einsum("btf,fe->bte", ff, p["w_down"]), "batch", "seq", "embed")
    return x, jnp.zeros((), jnp.float32)


def _moe_ffn(p, h, cfg: LlamaConfig, mesh: Optional[Mesh]):
    """Routed expert FFN for one layer. h: [B, T, e] -> ([B, T, e], aux)."""
    from ray_tpu.parallel.moe import moe_dense, moe_layer

    B, T, e = h.shape
    bank = {
        "router": p["moe_router"],
        "w_gate": p["moe_w_gate"],
        "w_up": p["moe_w_up"],
        "w_down": p["moe_w_down"],
    }
    tokens2d = h.reshape(B * T, e)
    ep = (
        mesh.shape.get("ep", 1)
        if mesh is not None and "ep" in mesh.axis_names
        else 1
    )
    if mesh is not None and ep > 1:
        y, aux = moe_layer(
            bank,
            tokens2d,
            mesh,
            num_experts=cfg.moe_experts,
            top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor,
            tokens_axis_names=("dp", "fsdp", "sp"),
        )
    else:
        y, aux = moe_dense(
            bank,
            tokens2d,
            num_experts=cfg.moe_experts,
            top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor,
        )
    y = y.reshape(B, T, e).astype(h.dtype)
    if cfg.moe_routed_scale != 1.0:
        y = y * cfg.moe_routed_scale
    if cfg.moe_shared_d_ff:
        y = y + _shared_expert(p, h)
    return y, aux


def _shared_expert(p, h):
    """The expert every token passes through, ungated. h: [..., e]."""
    with scope("shared_expert"):
        ff = jax.nn.silu(h @ p["moe_shared_gate"]) * (h @ p["moe_shared_up"])
        return ff @ p["moe_shared_down"]


@scope("embed")
def _embed_lookup(table, tokens, cfg: LlamaConfig, mesh: Optional[Mesh]):
    """Token embedding. On a sharded mesh the row-gather is replaced by a
    one-hot matmul: SPMD cannot partition a gather from a table sharded on
    vocab (tp) and embed (fsdp) — it replicates the output ("involuntary
    full rematerialization") — while a matmul contracts the sharded vocab
    dim with a psum and lands directly in activation sharding. The backward
    pass likewise becomes a matmul instead of a scatter-add."""
    sharded = mesh is not None and any(s > 1 for s in mesh.shape.values())
    if not sharded:
        return table[tokens].astype(cfg.dtype)
    onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=cfg.dtype)
    return jnp.einsum("btv,ve->bte", onehot, table.astype(cfg.dtype))


def forward_hidden(
    params,
    tokens,
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
    positions=None,
    with_aux: bool = False,
):
    """tokens: [B, T] int32 -> final hidden states [B, T, d_model].

    ``with_aux=True`` returns (hidden, aux) where aux is the summed MoE
    load-balancing loss (0 for dense configs). When the mesh has pp>1 the
    layer stack runs as a GPipe pipeline over the pp axis
    (``parallel/pipeline.py`` — native PP where the reference only passes
    ``pipeline_parallel_size`` to vLLM, ``vllm_models.py:176-190``)."""
    if cfg.layer_types:
        from ray_tpu.models.patterned import forward_hidden as patterned_hidden

        x = patterned_hidden(params, tokens, cfg, mesh, positions)
        return (x, jnp.zeros((), jnp.float32)) if with_aux else x
    custom_positions = positions is not None
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :], tokens.shape
        )
    x = _embed_lookup(params["embed"], tokens, cfg, mesh)
    if mesh is not None:
        x = with_sharding(mesh, x, "batch", "seq", "embed")

    pp = (
        mesh.shape.get("pp", 1)
        if mesh is not None and "pp" in mesh.axis_names
        else 1
    )
    if pp > 1 and custom_positions:
        # the pipeline path recomputes default positions per microbatch;
        # silently dropping packed/offset positions would corrupt RoPE
        raise NotImplementedError("pp>1 with custom positions is not supported")
    remat_policy = (
        jax.checkpoint_policies.dots_saveable
        if cfg.remat_policy == "dots"
        else None
    )
    stacked = {k: params[k] for k in _layer_keys(cfg)}
    if pp > 1:
        x, aux = _pipeline_hidden(stacked, x, cfg, mesh, pp, remat_policy)
    else:
        layer = lambda p, y: _layer(p, y, positions, cfg, mesh)
        if cfg.remat:
            layer = jax.checkpoint(layer, policy=remat_policy)

        def body(y, p):
            return layer(p, y)

        x, auxs = jax.lax.scan(body, x, stacked)
        aux = auxs.sum()
    x = _rmsnorm(x, params["final_norm"], cfg.rms_eps, cfg.fused_rmsnorm)
    return (x, aux) if with_aux else x


def _pipeline_hidden(stacked, x, cfg: LlamaConfig, mesh: Mesh, pp: int, policy):
    """Run the layer stack as pp GPipe stages (L/pp layers each) over
    microbatches of the batch dim."""
    from ray_tpu.parallel.pipeline import gpipe_spmd

    L = cfg.n_layers
    if L % pp:
        raise ValueError(f"n_layers {L} not divisible by pp={pp}")
    B, T, e = x.shape
    M = cfg.pp_microbatches or 2 * pp
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    stage_params = {
        k: v.reshape((pp, L // pp) + v.shape[1:]) for k, v in stacked.items()
    }
    x_mb = x.reshape(M, B // M, T, e)
    pos = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[None, :], (B // M, T)
    )

    def stage_fn(p_stage, y):
        # the stage sees the REAL mesh: activation constraints, MoE's ep
        # all_to_all, and ring/ulysses' sp collectives all compose under the
        # stage vmap (sharding constraints and shard_map both have batching
        # rules, and the vmapped stage dim keeps its pp sharding); tp also
        # flows through the params' shardings as before
        lyr = lambda p, z: _layer(p, z, pos, cfg, mesh)
        if cfg.remat:
            lyr = jax.checkpoint(lyr, policy=policy)

        def body(carry, p):
            z, aux = carry
            z2, a = lyr(p, z)
            return (z2, aux + a.astype(jnp.float32)), None

        (y, aux), _ = jax.lax.scan(body, (y, jnp.zeros((), jnp.float32)), p_stage)
        return y, aux

    out, aux = gpipe_spmd(stage_params, x_mb, stage_fn, mesh, with_aux=True)
    # per-microbatch aux values are token-MEAN statistics; averaging over
    # the M microbatches matches the non-pp full-batch scale (mean of
    # per-microbatch load-balance terms vs. the batch-level term — equal in
    # expectation, which is all the Switch-style aux promises)
    return out.reshape(B, T, e), aux / jnp.float32(M)


@scope("lm_head")
def _project_logits(x, params, cfg: LlamaConfig, mesh: Optional[Mesh]):
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
    return logits


def forward(
    params,
    tokens,
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
    positions=None,
):
    """tokens: [B, T] int32 -> logits [B, T, vocab] (fp32)."""
    x = forward_hidden(params, tokens, cfg, mesh, positions)
    return _project_logits(x, params, cfg, mesh)


def loss_fn(params, batch, cfg: LlamaConfig, mesh: Optional[Mesh] = None):
    """Next-token cross-entropy. batch: {'tokens': [B, T]} (labels = shift)
    or {'tokens', 'labels', 'mask'}."""
    tokens = batch["tokens"]
    if "labels" in batch:
        labels, mask = batch["labels"], batch.get("mask")
    else:
        labels = tokens[:, 1:]
        tokens = tokens[:, :-1]
        mask = batch.get("mask")
        if mask is not None:
            mask = mask[:, 1:]
    x, aux = forward_hidden(params, tokens, cfg, mesh, with_aux=True)
    if cfg.fused_ce:
        from ray_tpu.ops.cross_entropy import fused_cross_entropy

        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        base = fused_cross_entropy(x, unembed, labels, mask=mask)
    else:
        logits = _project_logits(x, params, cfg, mesh)
        with scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
            if mask is not None:
                denom = jnp.maximum(mask.sum(), 1)
                base = (nll * mask).sum() / denom
            else:
                base = nll.mean()
    if cfg.moe_experts:
        return base + cfg.moe_aux_weight * aux
    return base


# ---------------------------------------------------------------------------
# Decode path (serving): KV cache prefill + single-token step.
# ---------------------------------------------------------------------------


# Rows of one call's routing counts (``_moe_decode_ffn``; summed over expert
# layers by the caller): expert layers run, (token, expert) assignments,
# experts that got at least one token, and the fullest expert's tokens.
MOE_STATS = ("layer_steps", "assignments", "experts_touched", "max_expert_load")


def _moe_decode_ffn(params, row, h, cfg: LlamaConfig):
    """Dropless routed expert FFN for the serving path, and for ``forward``
    of a model whose layers are not alike. ``params`` holds the stacked
    ``moe_*`` leaves, ``row`` (static or traced) is this layer's row in them.
    h: [B, T, e] -> ([B, T, e], routing counts int32 [4], ``MOE_STATS``).

    Inference must never drop tokens (a capacity overflow at prefill would
    silently corrupt the prompt — the reference's serving engine is likewise
    dropless), so instead of the training path's capacity buffers
    (``parallel/moe.py``) every token goes through exactly its top-k experts,
    mixed with the renormalized gate weights (``topk_gates`` on float32
    logits), times ``cfg.moe_routed_scale``, plus the shared expert where
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
    overflow, which is what the decode-vs-forward exactness test pins."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul
    from ray_tpu.parallel.moe import topk_gates

    B, T, e = h.shape
    E, k = cfg.moe_experts, cfg.moe_top_k
    g = h.reshape(B * T, e)
    G = g.shape[0]
    with scope("router"):
        # float32 logits: in the model's own bf16 the 8th and 9th of 256
        # experts swap for some tokens on rounding alone
        _, gate_vals, gate_idx = topk_gates(
            {"router": params["moe_router"][row].astype(jnp.float32)},
            g.astype(jnp.float32), k,
        )
        # tokens an expert: a one-hot sum (a scatter-add is slow on the chip)
        load = jax.nn.one_hot(gate_idx.reshape(-1), E, dtype=jnp.int32).sum(axis=0)
        stats = jnp.stack([
            jnp.int32(1), jnp.int32(G * k), (load > 0).sum(dtype=jnp.int32), load.max(),
        ])
    with scope("experts"):
        order = jnp.argsort(gate_idx.reshape(-1))  # assignments by expert
        rows = g[order // k]  # [G*k, e]: each assignment's token
        n = params["moe_w_gate"].shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n * E,), jnp.int32), load, (row * E,)
        )

        def bank(name):
            w = params[name]
            return w.reshape((n * E,) + w.shape[2:])

        gate = grouped_matmul(rows, bank("moe_w_gate"), sizes)
        up = grouped_matmul(rows, bank("moe_w_up"), sizes)
        out = grouped_matmul(
            jax.nn.silu(gate) * up, bank("moe_w_down"), sizes, jnp.float32
        )
        # back to token order: a gather, not a scatter-add
        out = out[jnp.argsort(order)].reshape(G, k, e)
        y = jnp.einsum("gkd,gk->gd", out, gate_vals) * cfg.moe_routed_scale
        y = y.astype(g.dtype)
    if cfg.moe_shared_d_ff:
        y = y + _shared_expert(
            {n: params[n][row] for n in params if n.startswith("moe_shared_")}, g
        )
    return y.reshape(B, T, e), stats


def init_kv_cache(cfg: LlamaConfig, batch_size: int, max_len: Optional[int] = None):
    """KV cache [L, B, KV_HEADS, S, D] — head-major so each (batch, head)
    attention read streams a contiguous S×D block from HBM (position-major
    put the head axis inside, making every read a 256-byte stride: decode
    measured ~5x off the bandwidth roofline on v5e because of it)."""
    max_len = max_len or cfg.max_seq_len
    shape = (cfg.n_layers, batch_size, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "length": jnp.zeros((batch_size,), jnp.int32),
    }


def init_lora_stack(cfg: LlamaConfig, n_adapters: int, rank: int):
    """Zero-initialized stacked LoRA adapters for the decode path
    (reference: multi-LoRA serving, ``llm/_internal/serve/.../lora``; on TPU
    the idiom is a STACKED adapter tensor gathered per slot, so one compiled
    program serves any adapter mix — no per-adapter recompiles or weight
    swaps). Slot 0 stays all-zero = the base model. Targets q/v projections
    (the classic LoRA placement)."""
    L, e, h, kv, hd = (
        cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
    )
    n = n_adapters + 1  # + base slot 0
    return {
        "wq_a": jnp.zeros((L, n, e, rank), cfg.dtype),
        "wq_b": jnp.zeros((L, n, rank, h, hd), cfg.dtype),
        "wv_a": jnp.zeros((L, n, e, rank), cfg.dtype),
        "wv_b": jnp.zeros((L, n, rank, kv, hd), cfg.dtype),
    }


# Widest batch whose rows ``_decode_forward`` writes as one contiguous
# block each. On a v5e (PERF.md section 6, PR 27; a tensor and layer) a block
# costs about 2 us and 5 ns for each of the row's K*T cache rows (a window
# read, a select, an in-place ``dynamic_update_slice``: 11 us for a 256-token
# chunk), the scatter 73-90 ns a cache row (150 us for the same chunk), both
# linear in B. So the block wins wherever a row brings more than ~32 cache
# rows, as every prompt chunk does, and loses at decode's T = 1 (B = 32: 69 us
# against 23). The blocks are unrolled into the layer loop's body and the cap
# only bounds that program: the engine's scratch stripe has B = 1, the
# benchmark's probe B = 2; a wider gang batch (``llm/spmd.py``) is scattered.
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


def _cache_writer(cfg: LlamaConfig, S: int, positions, valid, start_pos):
    """``write(c_all, new, l)`` for ``_decode_forward`` (and
    ``models/patterned.py``): new keys or values [B, K, T, D] into layer ``l``
    of the carried cache [L, B, K, S, D], as blocks or as the scatter (see
    ``_decode_forward``)."""
    B, T = positions.shape
    as_blocks = (
        start_pos is not None and B <= _BLOCK_WRITE_MAX_BATCH and T <= S
    )
    if as_blocks:
        ok = jnp.ones((B, T), bool) if valid is None else valid

        def write(c_all, new, l):
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

        def write(c_all, new, l):
            return c_all.at[l, bi, ki, pi].set(new, mode="drop")
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


def _decode_forward(
    params, cache, tokens, positions, cfg: LlamaConfig, valid=None,
    loras=None, adapter_ids=None, with_logits: bool = True,
    logits_at=None, start_pos=None,
):
    """Shared prefill/decode body. tokens: [B, T]; positions: [B, T].
    New k/v are written into the cache before attention so new tokens
    attend to themselves and to all prior cache slots. ``valid`` [B, T]
    marks real (non-padding) tokens; padding writes leave the cache's old
    bytes where they are, so later decode steps never attend to stale slots
    and whatever copies a stripe out (the engine's stripe-to-slot copy, the
    prefix cache's store, the disaggregated hand-over) carries none.

    Two forms of one write, chosen from what is static at trace time, with
    the same bytes in the same slots. ``start_pos`` [B] is the caller's word
    that row ``b``'s positions are ``start_pos[b] + arange(T)`` (every call
    through ``prefill``): while ``B <= _BLOCK_WRITE_MAX_BATCH`` and the chunk
    fits the cache (``T <= S``) each row is one contiguous block a tensor and
    layer (``_write_block``: padding and the stripe's end keep old bytes).
    Otherwise (``decode_step``: T = 1, every row at an unrelated position;
    a wide batch) the ``[B, K, T]``-index scatter with ``mode="drop"``.
    The cache's position axis is never sharded (``llm/spmd.py`` shards the
    key-value heads), so a block partitions over heads as the scatter does.

    ``loras``/``adapter_ids``: stacked LoRA adapters + per-sequence adapter
    index (0 = base).
    ``logits_at`` [B]: project the LM head at ONLY this position per
    sequence (returns [B, 1, V]) — prefill needs one next-token
    distribution, and the full [B, T, V] projection is the single biggest
    prefill allocation (0.5 GB/seq at 7B/128k-vocab scale: the allocation
    that kept 7B from fitting one v5e chip)."""
    if cfg.layer_types:
        from ray_tpu.models.patterned import decode_forward

        return decode_forward(
            params, cache, tokens, positions, cfg, valid, loras=loras,
            with_logits=with_logits, logits_at=logits_at, start_pos=start_pos,
        )
    B, T = tokens.shape
    S = cache["k"].shape[3]  # [L, B, K, S, D]
    with scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)

    new_len = cache["length"] + T
    slot = jnp.arange(S)[None, None, :]  # [1, 1, S]
    qpos = positions[:, :, None]  # [B, T, 1]
    seq_mask = slot <= qpos  # causal over absolute positions

    write = _cache_writer(cfg, S, positions, valid, start_pos)

    groups = cfg.n_heads // cfg.n_kv_heads
    scale = cfg.head_dim**-0.5

    # fori_loop with the FULL cache as carry — the per-layer cache writes
    # alias in place (donated buffers), where a lax.scan carrying per-layer
    # cache slices as ys re-materializes the whole cache every step (decode
    # measured 1.6x slower from those copies alone at 3B/B=16 on v5e).
    def body(l, carry):
        x, ck_all, cv_all, *stats = carry
        # a layer's slice of a stacked weight is a copy on the chip (1.1 ms
        # of a 14.7 ms decode step at 7B widths): take it inside the scope
        # that uses it, so that it is booked there
        def p(k):
            return params[k][l]

        h = _rmsnorm(x, p("attn_norm"), cfg.rms_eps, cfg.fused_rmsnorm)
        with scope("attn_qkv"):
            q = jnp.einsum("bte,ehd->bthd", h, p("wq"))
            k = jnp.einsum("bte,ehd->bthd", h, p("wk"))
            v = jnp.einsum("bte,ehd->bthd", h, p("wv"))
            if loras is not None:
                # per-sequence adapter gather + low-rank delta: W x + B(A x)
                lp = {n: loras[n][l] for n in ("wq_a", "wq_b", "wv_a", "wv_b")}
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
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        with scope("kv_write"):
            # cache is [B, K, S, D]: write the new [B, T, K, D] rows head-major
            kh = k.transpose(0, 2, 1, 3)  # [B, K, T, D]
            vh = v.transpose(0, 2, 1, 3)
            ck_all = write(ck_all, kh, l)
            cv_all = write(cv_all, vh, l)

        with scope("attn_core"):
            ck = ck_all[l]
            cv = cv_all[l]
            if groups > 1:
                # GQA without materializing repeated K/V: fold the group axis
                # into the query instead (a jnp.repeat here would write+reread
                # the whole cache ×groups per layer per step — at 3B/B=16 that
                # alone is ~11 GB of HBM traffic per decode step)
                attn = _grouped_attention(q, ck, cv, seq_mask)
            else:
                s = jnp.einsum("bthd,bhsd->bhts", q, ck) * scale
                s = jnp.where(seq_mask[:, None, :, :], s, -1e30)
                w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
                attn = jnp.einsum("bhts,bhsd->bthd", w, cv)
        with scope("attn_out"):
            x = x + jnp.einsum("bthd,hde->bte", attn, p("wo"))

        h = _rmsnorm(x, p("mlp_norm"), cfg.rms_eps, cfg.fused_rmsnorm)
        if cfg.moe_experts:
            with scope("moe_ffn"):
                y, layer_stats = _moe_decode_ffn(params, l, h, cfg)
                x = x + y
                stats = [stats[0] + layer_stats]
        else:
            with scope("ffn"):
                x = x + _dense_ffn(h, p)
        return (x, ck_all, cv_all, *stats)

    # a model with routed experts carries its routing counts beside x
    stats0 = (jnp.zeros((len(MOE_STATS),), jnp.int32),) if cfg.moe_experts else ()
    x, new_k, new_v, *stats = jax.lax.fori_loop(
        0, cfg.n_layers, body, (x, cache["k"], cache["v"], *stats0)
    )
    new_cache = {"k": new_k, "v": new_v, "length": new_len}
    _ride_stats(cache, new_cache, stats)
    if not with_logits:
        # mid-chunk prefill: the caller only extends the KV cache — skip the
        # LM head (the vocab projection reads ~0.8 GB of weights at 128k
        # vocab; chunked admission would pay it once per chunk otherwise)
        return None, new_cache
    if logits_at is not None:
        # gather the single requested hidden state per sequence BEFORE the
        # vocab projection: [B, T, e] -> [B, 1, e]
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    x = _rmsnorm(x, params["final_norm"], cfg.rms_eps, cfg.fused_rmsnorm)
    with scope("lm_head"):
        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = jnp.einsum(
            "bte,ev->btv", x, unembed.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
    return logits, new_cache


def prefill(
    params, cache, tokens, cfg: LlamaConfig, lengths=None,
    loras=None, adapter_ids=None, start_pos=None, with_logits: bool = True,
):
    """Process a prompt batch. tokens: [B, T] (right-padded); lengths: [B].
    Returns (last-token logits [B, vocab] or None, cache).

    ``start_pos`` [B]: absolute position of tokens[:, 0] — the SUFFIX
    prefill used by prefix caching and chunked admission (the cache already
    holds positions 0..start_pos-1; this call extends it). ``with_logits=
    False`` skips the LM head for mid-chunk prefills."""
    B, T = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    rel = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    if start_pos is None:
        start_pos = jnp.zeros((B,), jnp.int32)
    positions = rel + start_pos[:, None]
    valid = rel < lengths[:, None]
    logits, cache = _decode_forward(
        params, cache, tokens, positions, cfg, valid,
        loras=loras, adapter_ids=adapter_ids, with_logits=with_logits,
        logits_at=None if not with_logits else lengths - 1,
        start_pos=start_pos,
    )
    cache["length"] = start_pos + lengths
    if not with_logits:
        return None, cache
    return logits[:, 0], cache


def decode_step(
    params, cache, tokens, cfg: LlamaConfig, loras=None, adapter_ids=None
):
    """One decode step. tokens: [B] or [B, 1] -> (logits [B, vocab], cache)."""
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    positions = cache["length"][:, None]
    logits, cache = _decode_forward(
        params, cache, tokens, positions, cfg,
        loras=loras, adapter_ids=adapter_ids,
    )
    return logits[:, -1], cache
