"""Llama-family decoder, TPU-first: the config, the parameters, the
whole-sequence path the train step traces, and the entry points of the path
through a cache (``init_kv_cache``, ``prefill``, ``decode_step``), whose one
body for every model is ``models/patterned.py decode_forward``. This file
imports that module; nothing there imports this one.

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
from jax.sharding import Mesh

from ray_tpu.models import patterned
from ray_tpu.models.patterned import (
    _embed_lookup,
    _moe_shapes,
    _param_shapes,
    _project_logits,
    _rmsnorm,
    _shared_expert,
    decode_forward,
    scope,
)
from ray_tpu.parallel.mesh import logical_sharding, with_sharding
from ray_tpu.parallel.ring_attention import (
    dense_attention,
    ring_attention,
)
from ray_tpu.parallel.ulysses import ulysses_attention


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
    # 'full' | 'ring' | 'ulysses' | 'splash'. ring/ulysses engage when the
    # mesh has sp>1; splash is a Pallas TPU kernel with no partitioning
    # rule: it needs a tpu backend and an unsharded program, and raises
    # anywhere else
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
    # --- layers that are not alike (``models/patterned.py plan``) ---
    # ``layer_types``: 'full' | 'sliding' for each layer; () = every layer a
    # full one of ``n_heads`` query heads, and the fields below keep their
    # defaults (``attn_gate``, ``yarn_factor`` and ``rope_partial`` are
    # refused without it). A sliding layer's query at position i sees keys j
    # with 0 <= i - j < window.
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
    # sigmoid of a projection of the layer's normed input, times the
    # attention output before wo: True (or 'head') a value a head, ``wg``
    # [d_model, heads] (Laguna's); 'channel' a value a channel of each head,
    # ``wg`` [d_model, heads * head_dim] (Solar-Open2's)
    attn_gate: Any = False
    # --- latent attention (layer type 'latent'; DeepSeek-V3's MLA) ---
    # ``kv_latent_rank`` > 0: a token's cache entry a layer is one normed
    # latent of that width and one rotated key of ``qk_rope_dim`` that all
    # heads share; each head's query is ``qk_nope_dim`` wide against the key
    # expanded from the latent plus ``qk_rope_dim`` against the shared key,
    # its value ``v_head_dim`` wide, expanded from the latent too
    kv_latent_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # rotate the pairs (2i, 2i+1) of the rotated part, not (i, i + half)
    rope_interleave: bool = False
    # the router's scores: 'softmax' over the experts, or 'sigmoid' of each
    # logit with a selection bias (``moe_router_bias``) that takes part in
    # the choice of the top k and not in their weights
    moe_scoring: str = "softmax"
    # --- blocks that are a mixer or a feed-forward alone, and state-space
    # mixers (layer type 'ssm'; Mamba-2, as NVIDIA Nemotron-3-Super's 'M') ---
    # a ``layer_types`` entry may also be 'ssm' or 'none' (no mixer), an
    # ``mlp_types`` entry 'none' (no feed-forward); a block has at least one,
    # and one norm in front of each it has. An 'ssm' layer keeps, for each
    # sequence, a float32 state [ssm_heads, ssm_head_dim, ssm_state] and the
    # last ``ssm_conv - 1`` inputs of its convolution, whatever the
    # sequence's length (``init_kv_cache``); ``ssm_groups`` groups of heads
    # share their B and C, and a prompt is scanned ``ssm_chunk`` tokens at a
    # time (``ops/ssm.py``)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # full and sliding layers rotate their queries and keys (False: no
    # position signal in attention; the state-space layers carry it)
    attn_rope: bool = True
    # an expert's form: 'swiglu' (three matrices) or 'relu2' (two: down(relu(up
    # x) ** 2)); the shared expert has the same form
    moe_activation: str = "swiglu"
    # > 0: the routed experts live in a latent of this width, between a
    # down-projection of the layer's input and an up-projection of their sum
    # (the router and the shared expert read the input itself)
    moe_latent_dim: int = 0
    # > 0: this device holds experts ``moe_experts_first`` .. + held of the
    # router's ``moe_experts`` (one share of an expert-parallel layer, without
    # its exchange): the router scores and chooses over all of them, and what
    # the absent ones would add to a token is left out
    moe_experts_held: int = 0
    moe_experts_first: int = 0
    # --- delta-rule linear attention (layer type 'kda'; Kimi Delta Attention,
    # as upstage Solar-Open2's linear layers) ---
    # a 'kda' layer keeps, for each sequence, a float32 state a head
    # [kda_heads, kda_head_dim (key), kda_head_dim (value)] that a token both
    # decays a key channel and erases from, and the last ``kda_conv - 1``
    # inputs of its three convolutions (query, key, value), whatever the
    # sequence's length and no keys (``init_kv_cache``). The decay and the
    # output gate come through a low rank of the head's width (the published
    # layer's default: a model whose rank differs brings the field with it);
    # a prompt runs ``kda_chunk`` tokens at a time (``ops/kda.py``)
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_chunk: int = 64
    # --- the four scalars of IBM Granite (1: none; need ``layer_types``) ---
    # on the looked-up embedding rows; on every branch (mixer, feed-forward)
    # before it joins the stream; the attention scale in place of
    # ``head_dim ** -0.5`` (0: that default); what divides the logits
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # --- compressed convolutional attention (layer type 'cca'; Zyphra ZAYA1,
    # arXiv 2510.04476) ---
    # a 'cca' layer is attention whose queries and keys pass two short causal
    # convolutions over the sequence (``cca_taps``: a depthwise one, then one
    # that mixes the channels of each head) and whose value heads' second half
    # are the token before's, so a slot holds stripes of keys and values *and*,
    # whatever its length, the last inputs of both convolutions and the last
    # token's shifted value half (``models/patterned.py cca_dims``)
    cca_taps: tuple = (2, 2)
    # > 0: an expert layer's router is no matrix but a projection to this
    # width, plus a learned multiple of the router's own vector of the expert
    # layer before (a second stream through the depth), a norm and an MLP of
    # three matrices with GELU; the chosen experts are weighted by their
    # softmax probabilities (at ``moe_top_k`` 1 unrenormalised), chosen by
    # probability plus ``moe_router_bias``
    moe_router_hidden: int = 0
    # a learned vector on the stream and one on the branch wherever a branch
    # joins the stream: x <- a * x + b * branch (``attn_scale``, ``mlp_scale``)
    residual_scales: bool = False
    # --- latent attention of two widths, a query latent and a learned indexer
    # (dots-studio dots3-note-prev, ``LlamaConfig.dots3_note_prev``) ---
    # ``q_latent_rank`` > 0: a latent layer's queries come through a normed
    # latent of that width (DeepSeek-V3's ``q_lora_rank``). A second latent
    # kind, layer type 'latent_sliding', has sizes of its own (the fields that
    # end in ``_sliding``; its queries' heads in ``heads_per_layer``), rotates
    # at ``rope_theta_sliding`` and sees ``sliding_window`` positions, its own
    # among them. ``latent_rescale``: each normed latent times
    # ``sqrt(d_model / its rank)``.
    q_latent_rank: int = 0
    latent_rescale: bool = False
    kv_latent_rank_sliding: int = 0
    q_latent_rank_sliding: int = 0
    qk_nope_dim_sliding: int = 0
    qk_rope_dim_sliding: int = 0
    v_head_dim_sliding: int = 0
    # ``index_topk`` > 0 (DeepSeek-V3.2's indexer, on the 'latent' layers): a
    # token's cache entry holds one more key, ``index_head_dim`` wide, and a
    # query attends only the ``index_topk`` positions whose keys score highest
    # against its ``index_heads`` index queries (taken from the query latent),
    # ties to the lower position; all of them while there are no more
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # each head's query and key through an RMSNorm of the head's width with a
    # learned scale before they are rotated: one scale for the queries and one
    # for the keys a layer (Qwen3's; leaves ``q_head_norm``, ``k_head_norm``)
    qk_norm: bool = False
    # --- generation by diffusion over blocks (JetLM SDAR; ``block_step``) ---
    # ``block_length`` > 0: a query at position t attends position s iff
    # ``s // block_length <= t // block_length`` (causal across blocks, both
    # ways inside one), the logits at a position are of the token at that
    # position itself, and a sequence grows a block at a time: a block starts
    # as ``mask_token_id`` and is unmasked by confidence over
    # ``denoise_steps`` forwards (a request may ask for fewer), every position
    # whose confidence passes ``confidence_threshold`` with them (1: none
    # does), and its keys and values are kept by one more forward once it is
    # clean. 0: every model that generates a token a step, untouched
    block_length: int = 0
    mask_token_id: int = 0
    denoise_steps: int = 0
    confidence_threshold: float = 1.0
    # --- a stack run several times a token (ByteDance Ouro, arXiv 2510.25741) ---
    # ``loop_passes`` > 1: the whole stack of ``n_layers`` runs that many
    # times over a token with the same weights, pass t + 1 starting from pass
    # t's stream under the final norm, and every pass keeps keys and values of
    # its own (cache row ``t * n_layers + l``: ``init_kv_cache`` has
    # ``n_layers * loop_passes`` rows). A gate (``exit_w``, ``exit_b``: a
    # sigmoid of a ``Linear(d_model, 1)`` on each pass's normed stream) gives
    # the chance that a position stops after a pass; the head reads the first
    # pass at which those chances add up to ``exit_threshold`` (1: the last,
    # unless float32 rounds to 1 before it), every pass run whatever it picks.
    # ``branch_norm``: an RMSNorm on each branch's way out as well as in
    # (``attn_out_norm``, ``mlp_out_norm``), x + norm(branch(norm(x))).
    # 1 and False: every other model, untouched
    loop_passes: int = 1
    branch_norm: bool = False
    exit_threshold: float = 1.0

    def __post_init__(self):
        if self.attention not in ("full", "ring", "ulysses", "splash"):
            raise ValueError(f"unknown attention {self.attention!r}")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_scoring {self.moe_scoring!r}")
        if self.moe_activation not in ("swiglu", "relu2"):
            raise ValueError(f"unknown moe_activation {self.moe_activation!r}")
        if self.attn_gate not in (False, True, "head", "channel"):
            raise ValueError(f"unknown attn_gate {self.attn_gate!r}")
        object.__setattr__(self, "cca_taps", tuple(self.cca_taps))
        for name in ("layer_types", "heads_per_layer", "mlp_types"):
            value = tuple(getattr(self, name))
            object.__setattr__(self, name, value)
            if self.layer_types and len(value) != self.n_layers:
                raise ValueError(
                    f"{name} has {len(value)} entries for n_layers={self.n_layers}"
                )
        scalars = (self.embedding_multiplier, self.residual_multiplier, self.logits_scaling)
        if not self.layer_types and (scalars != (1.0, 1.0, 1.0) or self.attention_multiplier):
            raise ValueError("the Granite multipliers need layer_types (models/patterned.py)")
        if not self.layer_types and (self.moe_router_hidden or self.residual_scales):
            raise ValueError("moe_router_hidden and residual_scales need layer_types "
                             "(models/patterned.py)")
        if not self.layer_types and (self.qk_norm or self.block_length):
            raise ValueError("qk_norm and block_length need layer_types (models/patterned.py)")
        if not self.layer_types and (self.loop_passes != 1 or self.branch_norm):
            raise ValueError("loop_passes and branch_norm need layer_types (models/patterned.py)")
        if self.block_length and not (
            0 < (self.denoise_steps or self.block_length) <= self.block_length
            and 0 <= self.mask_token_id < self.vocab_size
        ):
            raise ValueError("block_length: denoise_steps of 1 .. block_length and a "
                             "mask_token_id inside the vocabulary")

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

    @staticmethod
    def kanana2_30b_a3b(**kw) -> "LlamaConfig":
        """kakaocorp Kanana-2-30B-A3B (``model_type: deepseek_v3``) as its
        config.json has it: 48 latent-attention layers (no query latent: 32
        heads of 128 + 64, a 512-wide key-value latent and one 64-wide
        rotated key a token, values 128 a head, interleaved rotation), layer
        0 a dense SwiGLU of 6144, then 128 experts of width 768, 6 a token by
        a sigmoid router with a selection bias, weights renormalised and
        times 2.448, beside two shared experts (one SwiGLU of 1536). A caller
        that cuts ``n_layers`` gets the first entries of the per-layer lists.
        Not in the config: the bias is a buffer the training moves (seeded
        small here), no group step (``n_group`` 1)."""
        d = dict(
            vocab_size=128256, d_model=2048, n_layers=48, n_heads=32, n_kv_heads=1,
            d_ff=6144, max_seq_len=32768, rms_eps=1e-6, rope_theta=1e6,
            kv_latent_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
            rope_interleave=True, moe_experts=128, moe_top_k=6, moe_d_ff=768,
            moe_shared_d_ff=1536, moe_routed_scale=2.448, moe_scoring="sigmoid",
        )
        d.update(kw)
        return LlamaConfig(**_latent_lists(d))

    @staticmethod
    def kanana_tiny(**kw) -> "LlamaConfig":
        """Test-size model with ``kanana2_30b_a3b``'s pattern: a leading
        dense layer, then expert layers, all latent attention."""
        d = dict(
            vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=1,
            d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False, rms_eps=1e-6,
            rope_theta=1e6, kv_latent_rank=32, qk_nope_dim=16, qk_rope_dim=8,
            v_head_dim=16, rope_interleave=True, moe_experts=16, moe_top_k=3,
            moe_d_ff=32, moe_shared_d_ff=64, moe_routed_scale=2.448,
            moe_scoring="sigmoid",
        )
        d.update(kw)
        return LlamaConfig(**_latent_lists(d))

    @staticmethod
    def nemotron3_super(**kw) -> "LlamaConfig":
        """NVIDIA Nemotron-3-Super-120B-A12B (``model_type: nemotron_h``) as
        its config.json has it: 88 blocks by ``hybrid_override_pattern``, each
        a mixer or a feed-forward alone: ``M`` Mamba-2 (128 heads of 64, 8
        groups, state 128, convolution 4, chunk 128), ``E`` 512 sigmoid-routed
        relu^2 experts of width 2688 in a 1024-wide latent, 22 a token, times
        5, beside a shared expert of 5376 on the input itself, ``*`` GQA of 32
        query and 2 key-value heads of 128 without rotation. A caller that
        cuts ``n_layers`` gets the pattern's first blocks unless it gives its
        own ``pattern``; ``moe_experts_held`` and ``vocab_size`` give a
        device's share. Not served: the multi-token-prediction module."""
        d = dict(
            vocab_size=131072, d_model=4096, n_layers=88, n_heads=32, n_kv_heads=2,
            head_width=128, d_ff=2688, max_seq_len=262144, rms_eps=1e-5, attn_rope=False,
            ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=8, ssm_conv=4,
            ssm_chunk=128, moe_experts=512, moe_top_k=22, moe_d_ff=2688,
            moe_shared_d_ff=5376, moe_routed_scale=5.0, moe_scoring="sigmoid",
            moe_activation="relu2", moe_latent_dim=1024,
            pattern="MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
        )
        d.update(kw)
        return LlamaConfig(**_pattern_lists(d))

    @staticmethod
    def nemotron_tiny(**kw) -> "LlamaConfig":
        """Test-size model with ``nemotron3_super``'s blocks: the first 11 of
        its pattern, 4 of 16 experts held."""
        d = dict(
            vocab_size=256, d_model=64, n_layers=11, n_heads=4, n_kv_heads=2, head_width=16,
            d_ff=48, max_seq_len=128, dtype=jnp.float32, remat=False, rms_eps=1e-5,
            attn_rope=False, ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_groups=2,
            ssm_conv=4, ssm_chunk=8, moe_experts=16, moe_top_k=6, moe_d_ff=48,
            moe_shared_d_ff=96, moe_routed_scale=5.0, moe_scoring="sigmoid",
            moe_activation="relu2", moe_latent_dim=32, moe_experts_held=4,
            pattern="MEMEMEM*EME",
        )
        d.update(kw)
        return LlamaConfig(**_pattern_lists(d))


    @staticmethod
    def solar_open2_250b(**kw) -> "LlamaConfig":
        """upstage Solar-Open2-250B (``model_type: solar_open2``) as its
        config.json has it: 48 layers, each a mixer and an expert layer; the
        layers ``gqa_layers`` (0, 4, .., 44) are GQA of 64 query and 8
        key-value heads of 128 without rotation and with an output gate a
        channel, the other 36 Kimi-delta linear attention (64 heads of 128,
        keys and values alike, convolutions of 4 taps); 320 sigmoid-routed
        SwiGLU experts of width 1280, 8 a token, weights renormalised, beside
        one shared expert. A caller that cuts ``n_layers`` gives its own
        ``gqa_layers`` (the attention layers among the layers it keeps) or
        gets the published ones below its depth; ``moe_experts_held`` and
        ``vocab_size`` give a device's share."""
        d = dict(
            vocab_size=196608, d_model=4096, n_layers=48, n_heads=64, n_kv_heads=8,
            head_width=128, d_ff=10240, max_seq_len=1048576, rms_eps=1e-5, attn_rope=False,
            attn_gate="channel", kda_heads=64, kda_head_dim=128, kda_conv=4, kda_chunk=64,
            moe_experts=320, moe_top_k=8, moe_d_ff=1280, moe_shared_d_ff=1280,
            moe_routed_scale=1.0, moe_scoring="sigmoid", gqa_layers=tuple(range(0, 48, 4)),
        )
        d.update(kw)
        return LlamaConfig(**_gqa_lists(d))

    @staticmethod
    def solar_tiny(**kw) -> "LlamaConfig":
        """Test-size model with ``solar_open2_250b``'s layers: two periods
        (attention, then three delta-rule layers), 4 of 16 experts held, a
        state of 16 x 16 a head (which does not tile: the plain step)."""
        d = dict(
            vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, head_width=16,
            d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False, rms_eps=1e-5,
            attn_rope=False, attn_gate="channel", kda_heads=4, kda_head_dim=16, kda_conv=4,
            kda_chunk=8, moe_experts=16, moe_top_k=4, moe_d_ff=32, moe_shared_d_ff=32,
            moe_routed_scale=1.0, moe_scoring="sigmoid", moe_experts_held=4,
            gqa_layers=(0, 4),
        )
        d.update(kw)
        return LlamaConfig(**_gqa_lists(d))

    @staticmethod
    def granite4_h_micro(**kw) -> "LlamaConfig":
        """IBM Granite-4.0-H-Micro (``model_type: granitemoehybrid``, 3 B) as
        its config.json has it: 40 layers, each a mixer under a dense SwiGLU
        of 8192; layers 5, 15, 25, 35 GQA of 32 query and 8 key-value heads
        of 64 without rotation, the other 36 Mamba-2 (64 heads of 64, one
        group, state 128, convolution 4, chunk 256); the four multipliers; a
        tied head; no routed experts. A caller that cuts ``n_layers`` gets
        the published ``layer_types``' first entries unless it gives its own."""
        d = dict(
            vocab_size=100352, d_model=2048, n_layers=40, n_heads=32, n_kv_heads=8,
            head_width=64, d_ff=8192, max_seq_len=131072, rms_eps=1e-5, attn_rope=False,
            tie_embeddings=True, ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
            ssm_conv=4, ssm_chunk=256, embedding_multiplier=12.0, residual_multiplier=0.22,
            attention_multiplier=0.015625, logits_scaling=8.0,
        )
        d.update(kw)
        n = d["n_layers"]
        d.setdefault("layer_types", tuple("full" if i % 10 == 5 else "ssm" for i in range(n)))
        d.setdefault("heads_per_layer", tuple(
            d["n_heads"] if t == "full" else 0 for t in d["layer_types"]))
        d.setdefault("mlp_types", ("dense",) * n)
        return LlamaConfig(**d)

    @staticmethod
    def granite_tiny(**kw) -> "LlamaConfig":
        """Test-size model with ``granite4_h_micro``'s layers: two periods of
        four (attention at 1 and 5), one group of heads whose 16 x 16 state
        does not tile (the plain step), every scalar set and none of them 1
        or its default's effect (the attention scale is not 16 ** -0.5)."""
        d = dict(
            vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, head_width=16,
            d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False, ssm_heads=8,
            ssm_head_dim=16, ssm_state=16, ssm_chunk=8, embedding_multiplier=3.0,
            residual_multiplier=0.5, attention_multiplier=0.125, logits_scaling=2.0,
        )
        d.update(kw)
        d.setdefault("layer_types", tuple(
            "full" if i % 4 == 1 else "ssm" for i in range(d["n_layers"])))
        return LlamaConfig.granite4_h_micro(**d)

    @staticmethod
    def zaya1_8b(**kw) -> "LlamaConfig":
        """Zyphra ZAYA1-8B (``model_type: zaya``) as its config.json has it:
        40 layers, each compressed convolutional attention (8 query and 2
        key-value heads of 128 in a latent of 1,024 and 256, two convolutions
        of 2 taps, half of every head rotated at theta 5e6) under 16 SwiGLU
        experts of 2,048, one a token, chosen by an MLP router of width 256
        with a stream of its own through the depth; learned scales at every
        join; a tied head over 262,272 rows. A caller that cuts ``n_layers``
        or gives ``moe_experts_held`` gets a device's share."""
        d = dict(
            vocab_size=262272, d_model=2048, n_layers=40, n_heads=8, n_kv_heads=2,
            head_width=128, d_ff=2048, max_seq_len=131072, rms_eps=1e-5, rope_theta=5e6,
            rope_partial=0.5, tie_embeddings=True, cca_taps=(2, 2), moe_experts=16, moe_top_k=1,
            moe_d_ff=2048, moe_router_hidden=256, residual_scales=True,
        )
        d.update(kw)
        n = d["n_layers"]
        d.setdefault("layer_types", ("cca",) * n)
        d.setdefault("heads_per_layer", (d["n_heads"],) * n)
        d.setdefault("mlp_types", ("sparse",) * n)
        return LlamaConfig(**d)

    @staticmethod
    def zaya_tiny(**kw) -> "LlamaConfig":
        """Test-size model with ``zaya1_8b``'s layers: three of them, 4 query
        heads over 2 key-value heads of 16 (a group of 2), 4 of 8 experts
        held, a router of width 16."""
        d = dict(
            vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, head_width=16,
            d_ff=32, max_seq_len=128, dtype=jnp.float32, remat=False, rope_theta=10000.0,
            moe_experts=8, moe_experts_held=4, moe_d_ff=32, moe_router_hidden=16,
        )
        d.update(kw)
        return LlamaConfig.zaya1_8b(**d)


    @staticmethod
    def dots3_note_prev(**kw) -> "LlamaConfig":
        """dots-studio dots3-note-prev's language model (``model_type:
        dots3_note``, 288B-A17B) as its config.json has it: 46 layers of
        width 5120; layers 0, 1, 5, 9 .. 45 latent attention with a query
        latent of 1024 (128 heads of 128 + 64, a 512-wide key-value latent,
        values 128, theta 8e7) under DeepSeek-V3.2's indexer (64 heads of 128,
        2,048 positions a query), the other 33 latent attention of their own
        sizes (64 heads of 192 + 64, both latents 1024, theta 50,000) over a
        window of 513 positions; a sigmoid gate a head on every layer's
        heads; both normed latents rescaled (``latent_rescale``); layer 0 a
        dense SwiGLU of 13824, then 256 sigmoid-routed experts of width 1536,
        8 a token, renormalised, beside one shared. A caller that cuts
        ``n_layers`` gets the first entries of the per-layer lists. Not in
        the config: the selection bias (seeded small), the vision and audio
        towers and the multi-token-prediction module (left out)."""
        d = dict(
            vocab_size=152064, d_model=5120, n_layers=46, n_heads=128, n_kv_heads=1,
            d_ff=13824, max_seq_len=524288, rms_eps=1e-5, rope_theta=8e7,
            q_latent_rank=1024, kv_latent_rank=512, qk_nope_dim=128, qk_rope_dim=64,
            v_head_dim=128, rope_interleave=True, latent_rescale=True, attn_gate=True,
            index_heads=64, index_head_dim=128, index_topk=2048,
            sliding_window=513, rope_theta_sliding=5e4, q_latent_rank_sliding=1024,
            kv_latent_rank_sliding=1024, qk_nope_dim_sliding=192, qk_rope_dim_sliding=64,
            v_head_dim_sliding=128, moe_experts=256, moe_top_k=8, moe_d_ff=1536,
            moe_shared_d_ff=1536, moe_routed_scale=1.0, moe_scoring="sigmoid",
        )
        d.update(kw)
        return LlamaConfig(**_sparse_latent_lists(d, sliding_heads=64))

    @staticmethod
    def dots3_tiny(**kw) -> "LlamaConfig":
        """Test-size model with ``dots3_note_prev``'s pattern: two indexed
        latent layers (the first under a dense feed-forward), then a period
        of sliding latent ones; 8 positions a query, a window of 5."""
        d = dict(
            vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=1, d_ff=128,
            max_seq_len=128, dtype=jnp.float32, remat=False, rms_eps=1e-5, rope_theta=8e7,
            q_latent_rank=32, kv_latent_rank=32, qk_nope_dim=16, qk_rope_dim=8,
            v_head_dim=16, rope_interleave=True, latent_rescale=True, attn_gate=True,
            index_heads=4, index_head_dim=16, index_topk=8,
            sliding_window=5, rope_theta_sliding=5e4, q_latent_rank_sliding=32,
            kv_latent_rank_sliding=48, qk_nope_dim_sliding=24, qk_rope_dim_sliding=8,
            v_head_dim_sliding=16, moe_experts=16, moe_top_k=3, moe_d_ff=32,
            moe_shared_d_ff=32, moe_routed_scale=1.0, moe_scoring="sigmoid",
        )
        d.update(kw)
        return LlamaConfig(**_sparse_latent_lists(d, sliding_heads=2))

    @staticmethod
    def sdar_30b_a3b(**kw) -> "LlamaConfig":
        """JetLM SDAR-30B-A3B-Chat (``model_type: sdar_moe``) as its
        config.json has it: 48 layers alike, 32 query heads over 4 key-value
        heads of 128, 128 experts of width 768, 8 a token, a softmax router
        renormalised over the chosen, no shared expert, head untied. Not in
        the config (``benchmark/configs/sdar-30b-a3b-chat-serve-l6.json``
        ``assumed``): the per-head query and key norms (the family's
        backbone, Qwen3-MoE, has them), blocks of 4 unmasked over 4 denoising
        steps, the confidence threshold 0.9 (which seeded weights never
        reach), the mask token's id. A caller that cuts ``n_layers`` gets the
        pattern's first entries."""
        d = dict(
            vocab_size=151936, d_model=2048, n_layers=48, n_heads=32, n_kv_heads=4,
            head_width=128, d_ff=6144, max_seq_len=32768, rms_eps=1e-6, rope_theta=1e6,
            qk_norm=True, moe_experts=128, moe_top_k=8, moe_d_ff=768,
            block_length=4, mask_token_id=151669, denoise_steps=4, confidence_threshold=0.9,
        )
        d.update(kw)
        return LlamaConfig(**_sdar_lists(d))

    @staticmethod
    def sdar_tiny(**kw) -> "LlamaConfig":
        """Test-size model with ``sdar_30b_a3b``'s parts: grouped-query
        attention under per-head norms over routed experts, blocks of 4."""
        d = dict(
            vocab_size=320, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
            head_width=16, d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False,
            rms_eps=1e-6, rope_theta=1e6, qk_norm=True, moe_experts=8, moe_top_k=2,
            moe_d_ff=32, block_length=4, mask_token_id=300, denoise_steps=4,
            confidence_threshold=0.9,
        )
        d.update(kw)
        return LlamaConfig(**_sdar_lists(d))


    @staticmethod
    def ouro_2_6b(**kw) -> "LlamaConfig":
        """ByteDance Ouro-2.6B (``model_type: ouro``) as its config.json has
        it: 48 layers alike, 16 query and 16 key-value heads of 128, SwiGLU of
        5,632, vocabulary 49,152, head untied, ``rope_theta`` 1e6, no window,
        the stack run ``total_ut_steps`` = 4 times a token, ``early_exit_threshold``
        1. Not in the config but in the published modelling file
        (``benchmark/configs/ouro-2.6b-serve-l48.json`` ``assumed``): the norm
        on each branch's way out, the final norm inside the loop, a cache row
        a pass and layer, the exit gate and its rule."""
        d = dict(
            vocab_size=49152, d_model=2048, n_layers=48, n_heads=16, n_kv_heads=16,
            head_width=128, d_ff=5632, max_seq_len=65536, rms_eps=1e-6, rope_theta=1e6,
            loop_passes=4, branch_norm=True, exit_threshold=1.0,
        )
        d.update(kw)
        return LlamaConfig(**_dense_lists(d))

    @staticmethod
    def ouro_tiny(**kw) -> "LlamaConfig":
        """Test-size model with ``ouro_2_6b``'s parts: two layers run three
        times, norms on both sides of each branch, the exit gate."""
        d = dict(
            vocab_size=320, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
            head_width=16, d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False,
            rms_eps=1e-6, rope_theta=1e6, loop_passes=3, branch_norm=True,
        )
        d.update(kw)
        return LlamaConfig(**_dense_lists(d))


def _dense_lists(d: dict) -> dict:
    """The per-layer lists of a model whose layers are all full attention over
    a dense feed-forward, for its depth."""
    n = d["n_layers"]
    d.setdefault("layer_types", ("full",) * n)
    d.setdefault("heads_per_layer", (d["n_heads"],) * n)
    d.setdefault("mlp_types", ("dense",) * n)
    return d


def _sdar_lists(d: dict) -> dict:
    """The per-layer lists of a model whose layers are all full attention over
    routed experts, for its depth."""
    n = d["n_layers"]
    d.setdefault("layer_types", ("full",) * n)
    d.setdefault("heads_per_layer", (d["n_heads"],) * n)
    d.setdefault("mlp_types", ("sparse",) * n)
    return d


# a block of a ``nemotron_h`` pattern: (mixer, feed-forward)
_BLOCKS = {"M": ("ssm", "none"), "E": ("none", "sparse"), "*": ("full", "none")}


def _pattern_lists(d: dict) -> dict:
    """The per-layer lists of a model given as a pattern of blocks that are a
    mixer or a feed-forward alone, for its depth."""
    blocks = [_BLOCKS[c] for c in d.pop("pattern")[: d["n_layers"]]]
    d.setdefault("layer_types", tuple(t for t, _ in blocks))
    d.setdefault("heads_per_layer", tuple(
        d["n_heads"] if t == "full" else 0 for t in d["layer_types"]))
    d.setdefault("mlp_types", tuple(m for _, m in blocks))
    return d


def _gqa_lists(d: dict) -> dict:
    """The per-layer lists of a model whose layers ``gqa_layers`` are full
    attention and the others delta-rule linear attention, every one with
    experts, for its depth."""
    n = d["n_layers"]
    gqa = set(d.pop("gqa_layers"))
    d.setdefault("layer_types", tuple("full" if i in gqa else "kda" for i in range(n)))
    d.setdefault("heads_per_layer", tuple(
        d["n_heads"] if t == "full" else 0 for t in d["layer_types"]))
    d.setdefault("mlp_types", ("sparse",) * n)
    return d


def _latent_lists(d: dict) -> dict:
    """The per-layer lists of a model whose layers are all latent attention,
    one dense feed-forward and then expert ones, for its depth."""
    n = d["n_layers"]
    d.setdefault("layer_types", ("latent",) * n)
    d.setdefault("heads_per_layer", (d["n_heads"],) * n)
    d.setdefault("mlp_types", ("dense",) + ("sparse",) * (n - 1))
    return d


def _sparse_latent_lists(d: dict, sliding_heads: int) -> dict:
    """The per-layer lists of a model whose layers 0, 1, 5, 9 .. are indexed
    latent attention and the others sliding latent attention of
    ``sliding_heads`` heads, one dense feed-forward and then expert ones, for
    its depth."""
    n = d["n_layers"]
    d.setdefault("layer_types", tuple(
        "latent" if i == 0 or i % 4 == 1 else "latent_sliding" for i in range(n)))
    d.setdefault("heads_per_layer", tuple(
        d["n_heads"] if t == "latent" else sliding_heads for t in d["layer_types"]))
    d.setdefault("mlp_types", ("dense",) + ("sparse",) * (n - 1))
    return d


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
    "q_head_norm": (None, None),
    "k_head_norm": (None, None),
    # a looped model's norms on the branches' way out and its exit gate (one
    # device: ``llm/config.py refuse_looped``)
    "attn_out_norm": (None, "norm"),
    "mlp_out_norm": (None, "norm"),
    "exit_w": (None,),
    "exit_b": (None,),
    # MoE variant: per-layer expert banks (expert dim -> ep mesh axis)
    "moe_router": (None, "embed", None),
    "moe_router_bias": (None, None),
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
# latent attention (one device: ``llm/spmd.py`` and ``llm/gang.py`` refuse
# it): the query projection as any other, the latent's down-projection
# [.., e, rank + rope] (it contracts ``embed`` as a feed-forward leaf does, in
# place), its norm, and a head's two halves of the up-projection, stored so
# that both the expansion (contracts the rank) and the absorbed form
# (contracts the head's width) read a layer's slice in place
_PARAM_DIMS.update({
    "wq_latent": _PARAM_DIMS["wq"],
    "wo_latent": _PARAM_DIMS["wo"],
    "wkv_a_latent": (None, "embed", None),
    "kv_norm_latent": (None, "norm"),
    "wuk_latent": (None, "heads", "head_dim", None),
    "wuv_latent": (None, "heads", None, "head_dim"),
})


# a state-space mixer's leaves and a latent expert layer's two projections
# (one device: ``llm/config.py refuse_stateful``)
_PARAM_DIMS.update({
    "ssm_w_in": (None, "embed", None),
    "ssm_w_out": (None, None, "embed"),
    "ssm_conv_w": (None, None, None),
    **dict.fromkeys(("ssm_conv_b", "ssm_dt_bias", "ssm_a_log", "ssm_d", "ssm_norm"),
                    (None, None)),
    "moe_latent_down": (None, "embed", None),
    "moe_latent_up": (None, None, "embed"),
})
# a delta-rule mixer's leaves (one device too)
_PARAM_DIMS.update({
    "kda_w_in": (None, "embed", None),
    "kda_w_out": (None, None, "embed"),
    **dict.fromkeys(("kda_conv_w", "kda_w_decay", "kda_w_gate"), (None, None, None)),
    **dict.fromkeys(("kda_dt_bias", "kda_a_log", "kda_norm"), (None, None)),
})
# a compressed-convolutional-attention layer's leaves, an MLP router's and the
# learned scales at a join (one device too): the projections as any
# attention's, everything else whole
_PARAM_DIMS.update({
    "wq_cca": _PARAM_DIMS["wq"],
    "wo_cca": _PARAM_DIMS["wo"],
    "cca_conv0_w": (None, None, None),
    "cca_conv1_w": (None, None, None, None),
    **dict.fromkeys(("cca_conv0_b", "cca_conv1_b", "cca_temp", "moe_router_norm",
                     "moe_router_gamma", "moe_router_b1", "moe_router_b2", "moe_router_b3"),
                    (None, None)),
    "moe_router_down": (None, "embed", None),
    **dict.fromkeys(("moe_router_w1", "moe_router_w2", "moe_router_w3", "attn_scale",
                     "mlp_scale"), (None, None, None)),
})


# latent attention of two widths under an indexer (one device too): the
# sliding kind's leaves as the full kind's, a query latent's down-projection
# and norm, the gate a head, and the indexer's leaves whole
for _name in ("wq", "wo", "wkv_a", "kv_norm", "wuk", "wuv"):
    _PARAM_DIMS[_name + "_latent_sliding"] = _PARAM_DIMS[_name + "_latent"]
for _kind in ("latent", "latent_sliding"):
    _PARAM_DIMS["wqa_" + _kind] = (None, "embed", None)
    _PARAM_DIMS["q_norm_" + _kind] = (None, "norm")
    _PARAM_DIMS["wg_" + _kind] = (None, "embed", "heads")
_PARAM_DIMS.update({
    **dict.fromkeys(("index_wq", "index_wk", "index_ww"), (None, None, None)),
    **dict.fromkeys(("index_k_norm", "index_k_bias"), (None, None)),
})


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
# A latent model's query head is 128 + 64 wide, no whole number of 128-lane
# tiles, and head-major the v5e compiler relaid all of ``wq_latent`` (126 MB
# at 5 layers) in every decode step and every chunk (0.45 of a 9.06 ms step).
# It wants ``embed`` itself on the lanes and the head's width on the
# sublanes: PERF.md section 6, PR 33
EMBED_MINOR = (0, 2, 3, 1)


def serving_layouts(names) -> dict[str, tuple]:
    """name -> ``major_to_minor`` for the leaves among ``names`` that the
    engine holds in another device layout than the default, from what a leaf
    is (``_PARAM_DIMS``): stacked, of rank 4, contracting its ``embed`` axis
    with the heads and the head width behind it."""
    return {
        name: EMBED_MINOR if name.startswith("wq_latent") else HEAD_MAJOR for name in names
        if len(dims := _PARAM_DIMS.get(name, ())) == 4 and dims[:2] == (None, "embed")
    }


def _layer_keys(cfg: LlamaConfig) -> tuple:
    base = ("wq", "wk", "wv", "wo", "attn_norm", "mlp_norm")
    if cfg.moe_experts:
        return base + tuple(_moe_shapes(cfg, 1))
    return base + ("w_gate", "w_up", "w_down")


# the largest float32 draw ``init_params`` makes in one piece
_DRAW_WHOLE_MAX_BYTES = 4 << 30
# A state-space mixer's vectors, a value a head [n, H]: the step's bias so that
# ``softplus(dt_bias)`` is log-uniform over Mamba-2's ``time_step_min`` ..
# ``time_step_max`` (0.001 .. 0.1), the decay rate ``A = -exp(a_log)`` with A
# uniform over 1 .. 16, the skip ``D`` one; the convolution's bias small.
_SSM_VECTORS = {
    "ssm_dt_bias": lambda k, shape: _inv_softplus(
        jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))),
    "ssm_a_log": lambda k, shape: jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)),
    "ssm_d": lambda k, shape: jnp.ones(shape, jnp.float32),
    "ssm_conv_b": lambda k, shape: jax.random.normal(k, shape, jnp.float32) * 0.02,
}
# a delta-rule mixer's decay: the same two draws, the bias a channel
_SSM_VECTORS.update(kda_dt_bias=_SSM_VECTORS["ssm_dt_bias"], kda_a_log=_SSM_VECTORS["ssm_a_log"])
# compressed convolutional attention, an MLP router and the scales at a join:
# biases small, a key head's temperature and the scales one, the multiple of
# the router's vector of the layer before a half
_SSM_VECTORS.update({
    **dict.fromkeys(("cca_conv0_b", "cca_conv1_b", "moe_router_b1", "moe_router_b2",
                     "moe_router_b3"), _SSM_VECTORS["ssm_conv_b"]),
    **dict.fromkeys(("cca_temp", "attn_scale", "mlp_scale"), _SSM_VECTORS["ssm_d"]),
    "moe_router_gamma": lambda k, shape: jnp.full(shape, 0.5, jnp.float32),
    # a looped model's exit gate starts unbiased
    "exit_b": lambda k, shape: jnp.zeros(shape, jnp.float32),
})


def _inv_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


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
        if name.startswith("wq_latent"):  # from the input, or from a query latent
            return shape[1]
        if name.startswith(("wq", "wk", "wv")):
            return cfg.d_model
        if name.startswith("wuk"):  # both expand the latent
            return shape[-1]
        if name.startswith("wuv"):
            return shape[-2]
        if name.startswith("wo"):
            return shape[-3] * shape[-2]
        return shape[-2] if len(shape) > 1 else shape[0]

    params = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if "norm" in name:
            maker = lambda shape=shape: jnp.ones(shape, cfg.dtype)
        elif name in _SSM_VECTORS:
            maker = lambda k=k, shape=shape, draw=_SSM_VECTORS[name]: draw(k, shape).astype(cfg.dtype)
        elif math.prod(shape) * 4 > _DRAW_WHOLE_MAX_BYTES:
            # a row of the leading axis at a time: the float32 draw of the
            # whole leaf (7 GB for 5 layers of 128 experts of 1024 x 2688)
            # does not fit beside the leaves already made
            std = fan_in_of(name, shape) ** -0.5
            maker = lambda k=k, shape=shape, std=std: jax.lax.map(
                lambda kk: (jax.random.normal(kk, shape[1:], jnp.float32) * std).astype(cfg.dtype),
                jax.random.split(k, shape[0]),
            )
            if mesh is None:
                maker = jax.jit(maker)
        else:
            # the selection bias is a buffer the training moves: small and
            # not zero, so that the choice and the weights can differ
            std = 0.05 if name in ("moe_router_bias", "index_k_bias") else fan_in_of(
                name, shape) ** -0.5
            maker = lambda k=k, shape=shape, std=std: (
                jax.random.normal(k, shape, jnp.float32) * std
            ).astype(cfg.dtype)
        if mesh is not None:
            sh = logical_sharding(mesh, *_PARAM_DIMS[name], shape=shape)
            params[name] = jax.jit(maker, out_shardings=sh)()
        else:
            params[name] = maker()
    return params


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
    replicate the tensor). The Ulysses and splash kernels expect equal head
    counts, so those paths still expand kv heads first."""
    sp = (
        mesh.shape.get("sp", 1)
        if mesh is not None and "sp" in mesh.axis_names
        else 1
    )
    kernel = cfg.attention == "splash"
    if kernel:
        # the pallas kernel is TPU-only and has no SPMD partitioning rule
        # (single-chip or per-replica programs only). Dense attention in
        # its place would be a different program under the same name.
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
        x = patterned.forward_hidden(params, tokens, cfg, mesh, positions)
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
    if cfg.loop_passes > 1:
        raise NotImplementedError(
            "models/llama.py loss_fn: a stack run several times a token (loop_passes="
            f"{cfg.loop_passes}) is served, not trained: its loss is over the exit "
            "distribution's expected logits, which no path here computes"
        )
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


_LANES = 128  # the minor axis of a tile on the chip


def init_kv_cache(cfg: LlamaConfig, batch_size: int, max_len: Optional[int] = None):
    """KV cache [L, B, KV_HEADS, S, D] — head-major so each (batch, head)
    attention read streams a contiguous S×D block from HBM (position-major
    put the head axis inside, making every read a 256-byte stride: decode
    measured ~5x off the bandwidth roofline on v5e because of it).

    ``k`` and ``v`` hold the attention layers alone (all of them, in every
    model but one with layers that have no attention). A model with layers
    that keep a state (state-space or delta-rule) has two more leaves a kind,
    which are no stripes: a slot's state and the tail of its convolutions'
    inputs, a layer each, of one size whatever the slot's length
    (``models/patterned.py state_cache_shapes``, named in ``STATE_LEAVES``);
    every leaf but ``length`` has the slot on axis 1, which is all that the
    engine's programs that stack, unstack and copy slots know of them.

    A model whose attention layers are of two cache shapes, or hold a third
    number a token, has further stripes under names of their own, each
    ``[layers of its kind, B, 1, S, D]`` (``models/patterned.py
    stripe_cache_shapes``: a sliding latent layer's ``k_sliding`` and
    ``v_sliding``, an indexed one's ``k_index``); whatever moves a slot's
    stripes moves every one of them.

    One rule for every model: ``k`` and ``v`` are two rank-5 leaves whose
    ``D`` need not be equal. A latent-attention model has one key-value
    "head": ``k`` holds the rotated key all heads share, ``v`` the normed
    latent (``kv_latent_rank``), which is the rest of the key and the whole
    value of the absorbed form. The rotated key's ``qk_rope_dim`` numbers lie
    at the front of a row of whole 128-lane tiles, zeros behind them: the
    chip pads a narrower minor axis to 128 lanes anyway (a 64-wide bfloat16
    row holds 256 bytes either way), and the decode kernel's copies cannot
    take part of a lane tile (the v5e compiler: "slice shape along dimension
    4 must be aligned to tiling (128), but is 64")."""
    max_len = max_len or cfg.max_seq_len
    stripes = patterned.stripe_cache_shapes(cfg, batch_size, max_len)
    cache = {
        **{name: jnp.zeros(shape, cfg.dtype) for name, shape in stripes.items()},
        "length": jnp.zeros((batch_size,), jnp.int32),
    }
    cache.update({
        name: jnp.zeros(shape, dtype)
        for name, (shape, dtype) in patterned.state_cache_shapes(cfg, batch_size).items()
    })
    return cache


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


def prefill(
    params, cache, tokens, cfg: LlamaConfig, lengths=None,
    loras=None, adapter_ids=None, start_pos=None, with_logits: bool = True,
    beside=None,
):
    """Process a prompt batch. tokens: [B, T] (right-padded); lengths: [B].
    Returns (last-token logits [B, vocab] or None, cache).

    ``beside``: ``(cache, tokens [B2], live [B2] or None)``, the rows of a
    decode step that ride through the same read of the weights
    (``models/patterned.py decode_forward``); the result then ends with their
    ``decode_step``: ``(.., cache, logits [B2, vocab], their cache)``. Of a
    model that generates by blocks ``(cache, tokens [B2, T], live, commit
    [B2])``, the rows of a block step; the result then ends with their
    ``block_forward``: ``(.., logits [B2 * T, vocab], their cache)``.

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
    logits, cache, *rode = decode_forward(
        params, cache, tokens, positions, cfg, valid,
        loras=loras, adapter_ids=adapter_ids, with_logits=with_logits,
        logits_at=None if not with_logits else lengths - 1,
        start_pos=start_pos, beside=beside,
    )
    cache["length"] = start_pos + lengths
    if rode:  # a token a row: its one position; a block a row: a row a position
        rode = [rode[0][0] if len(beside) == 4 else rode[0][:, -1], rode[1]]
    if not with_logits:
        return (None, cache, *rode)
    return (logits[:, 0], cache, *rode)


def decode_step(
    params, cache, tokens, cfg: LlamaConfig, loras=None, adapter_ids=None
):
    """One decode step. tokens: [B] or [B, 1] -> (logits [B, vocab], cache)."""
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    positions = cache["length"][:, None]
    logits, cache = decode_forward(
        params, cache, tokens, positions, cfg,
        loras=loras, adapter_ids=adapter_ids,
    )
    return logits[:, -1], cache


def block_schedule(step, steps, block_length: int):
    """Positions denoising step ``step`` (0-based) of ``steps`` unmasks in a
    block of ``block_length``: ``block_length // steps``, one more in the first
    ``block_length % steps`` steps. Integers or arrays of them (NumPy's or
    JAX's alike: the engine's host and its program both ask)."""
    return block_length // steps + (step < block_length % steps)


def block_forward(params, cache, tokens, commit, cfg: LlamaConfig):
    """The forward of ``block_step``: tokens [slots, B], each slot's block at
    positions ``[length, length + B)`` -> (logits, a row a position [slots * B,
    V]: the head's own tiles; the cache, a slot's length ``B`` further where
    ``commit`` [slots])."""
    B = cfg.block_length
    if not B or tokens.shape[1] != B:
        raise ValueError(f"block_step: a model with block_length={B} and tokens [slots, {B}]")
    positions = cache["length"][:, None] + jnp.arange(B, dtype=jnp.int32)[None, :]
    logits, cache = decode_forward(params, cache, tokens, positions, cfg, block_commit=commit)
    return logits[0], cache


def block_unmask(rows, tokens, masked, n_unmask, cfg: LlamaConfig, sample=None):
    """What a denoise forward does behind its logits (``block_step``): ``rows``
    [slots * B, V] float32, a row a position of the blocks ``tokens`` [slots,
    B] -> (the blocks after the step, which of them is still masked, the
    logits with the mask token's column at minus infinity). One function for
    the block step launched alone and for one that rode through a prompt's
    chunk (``llm/engine.py programs``)."""
    B = cfg.block_length
    with scope("sampling"):
        with scope("confidence"):
            # one column written where the logits lie: a select over every
            # logit was two more passes over the 155 MB of them
            rows = jax.lax.dynamic_update_slice(
                rows, jnp.full((rows.shape[0], 1), -jnp.inf, rows.dtype), (0, cfg.mask_token_id))
            x0 = (jnp.argmax(rows, -1) if sample is None else sample(rows)).astype(jnp.int32)
            top = rows.max(axis=-1)
            norm = jnp.exp(rows - top[:, None]).sum(axis=-1)
            chosen = jnp.take_along_axis(rows, x0[:, None], axis=-1)[:, 0]
            x0 = x0.reshape(tokens.shape)
            confidence = (jnp.exp(chosen - top) / norm).reshape(tokens.shape)
        with scope("unmask"):
            # a masked position's rank among the slot's masked ones, by
            # confidence, ties to the lower position: those before it
            c = jnp.where(masked, confidence, -1.0)
            at = jnp.arange(B)
            before = (c[:, None, :] > c[:, :, None]) | (
                (c[:, None, :] == c[:, :, None]) & (at[None, None, :] < at[None, :, None]))
            rank = before.sum(axis=-1)
            take = masked & ((rank < n_unmask[:, None]) | (confidence > cfg.confidence_threshold))
            tokens = jnp.where(take, x0, tokens)
            masked = masked & ~take
    return tokens, masked, rows


def block_step(
    params, cache, tokens, masked, n_unmask, commit, cfg: LlamaConfig,
    sample=None, with_logits: bool = False,
):
    """One forward of a block a slot of a model that generates by diffusion
    over blocks (``cfg.block_length``; ``decode_step``'s place for it).
    tokens [slots, B]: each slot's block at positions ``[length, length + B)``,
    ``cfg.mask_token_id`` where ``masked`` [slots, B]; every query of a slot
    attends the slot's cache and the whole block (``models/patterned.py
    decode_forward`` under ``block``: the stripe is read once for all ``B``).

    A *denoise* forward takes at each masked position the token ``x0``
    (``sample``: [slots * B, V] float32 logits, a row a position -> [slots * B]
    tokens; greedy where None) and its confidence, ``softmax(logits)[x0]`` in
    float32, and writes ``x0`` into the ``n_unmask`` [slots] masked positions
    of largest confidence (ties to the lower position) and into every further
    one whose confidence passes ``cfg.confidence_threshold``, the model's own
    constant. The mask token's own logit is set to minus infinity first, so
    that no position unmasks to a mask.
    A slot with ``commit`` [slots] (the caller sets it where the block is
    clean) keeps the block's keys and values: its length advances by ``B``.
    Every forward writes the block's keys and values at ``[length, length +
    B)``, which is how its queries read them; behind a slot's length nothing
    else reads, and the next forward of the block overwrites them, so what
    stays in the cache is what a commit wrote. A free slot's arithmetic is
    done and dropped as in ``decode_step``.

    Returns (the block after the step [slots, B], which of it is still masked
    [slots, B], the logits [slots, B, V] where ``with_logits`` else None, the
    cache)."""
    rows, cache = block_forward(params, cache, tokens, commit, cfg)
    tokens, masked, rows = block_unmask(rows, tokens, masked, n_unmask, cfg, sample)
    return tokens, masked, (rows.reshape(tokens.shape + rows.shape[1:]) if with_logits else None), cache
