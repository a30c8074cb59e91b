"""LLM configs.

Reference: ``python/ray/llm/_internal/serve/configs/`` (``LLMConfig``,
engine kwargs incl. ``tensor_parallel_degree`` — ``vllm_models.py:176-190``).
TPU delta: parallelism is expressed as a mesh spec (tp/sp axes) applied to
the JAX engine's params, not forwarded to an external engine.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


# The most rows one middle-chunk program runs (``llm/engine.py
# _advance_admissions``: a pool's admissions whose next chunk is a middle chunk
# are rows of one launch), whatever ``max_concurrent_admissions`` is: the rows
# ``models/patterned.py`` writes into the cache as blocks (a wider batch falls
# back to the scatter, 150 us a row and layer where a block costs 11).
CHUNK_ROWS_MAX = 8


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 50
    stop_token_ids: Optional[list[int]] = None
    ignore_eos: bool = False
    seed: Optional[int] = None
    # a model that generates by blocks (``LlamaConfig.block_length``): the
    # denoising forwards a block is unmasked over (1 .. the block's length);
    # None: the model's own (``LlamaConfig.denoise_steps``). Any other model
    # takes no notice of it
    denoise_steps: Optional[int] = None


@dataclasses.dataclass
class EngineConfig:
    """Engine shape knobs (static: they size compiled programs)."""

    max_num_seqs: int = 8  # decode slot count (continuous batching width)
    max_seq_len: int = 512
    prefill_buckets: tuple = (32, 64, 128, 256, 512)
    # tp=1 in a MULTI-PROCESS gang = replicated lockstep (every process
    # computes the identical full batch; zero per-step collectives — the
    # gang buys availability + host throughput). tp>1 shards params/KV
    # over the gang's global mesh (the model-bigger-than-one-host shape).
    tensor_parallel_degree: int = 1
    sequence_parallel_degree: int = 1
    dtype: str = "bfloat16"
    # multi-LoRA serving: number of loadable adapter slots (0 disables) and
    # their rank. Adapters live STACKED on device; each sequence picks its
    # adapter by index inside the one compiled program (reference:
    # llm/_internal/serve LoRA support over vLLM's multi-LoRA).
    max_loras: int = 0
    lora_rank: int = 8
    # prefix caching: reuse the KV of previously-computed prompt prefixes
    # (shared system prompts / repeated few-shot preambles). Prefixes are
    # cached at bucket-aligned lengths; hits copy the cached stripe and
    # prefill only the suffix (the TPU-static analog of vLLM's paged
    # prefix caching — reference: vllm_engine.py's reason to exist).
    enable_prefix_caching: bool = True
    prefix_cache_entries: int = 32
    prefix_cache_max_bytes: int = 512 * 1024 * 1024
    # KV stripe pools: slots come in these sequence-length classes so short
    # chats don't pin max_seq_len-sized KV memory; a request routes to the
    # smallest class covering prompt+max_tokens. () = one pool at
    # max_seq_len. Each pool runs its own compiled decode program.
    seq_len_buckets: tuple = ()
    # slots per pool (parallel to seq_len_buckets; () = spread evenly)
    seqs_per_bucket: tuple = ()
    # decode steps per host loop iteration: >1 runs a lax.scan of K steps
    # in ONE device program, one host<->device round trip per K tokens.
    # Stop tokens are honored host-side after the fact (over-decoded tokens
    # discarded); admission latency grows by up to K steps.
    decode_steps: int = 1
    # chunked prefill: prompts are prefilled in chunks of at most this many
    # tokens, with decode programs interleaved between chunks so a long
    # admission can't stall in-flight decodes for a whole prompt's worth of
    # compute (reference: vLLM chunked prefill). Mid-chunks skip the LM
    # head. 0 = prefill each prompt in one program.
    prefill_chunk: int = 256
    # run-ahead depth: decode programs launched before the previous
    # program's sampled tokens have been fetched to the host. 1 hides the
    # device->host round trip behind the next program's compute; finished
    # slots may over-decode up to decode_steps * runahead discarded tokens.
    decode_runahead: int = 1
    # concurrent chunked admissions per pool: each holds a stripe-sized
    # scratch KV until its final chunk lands, so this bounds transient HBM
    # (admissions * stripe KV, and while a middle-chunk program of several
    # rows runs, its rows' stripes once more) and per-pass prefill work; too
    # low serializes admission waves and lets slot occupancy decay before
    # the batch fills. A pool's admissions whose next chunk is a middle
    # chunk run as rows of one launch, so this is also the most rows that
    # program has, and a replica compiles it at every row count up to this
    # before it is ready (a pool of latent attention keeps one row a launch:
    # ``llm/engine.py JaxEngine.__init__``).
    max_concurrent_admissions: int = 4


@dataclasses.dataclass
class ModelConfig:
    # a preset of ``resolve_llama_config``: "tiny" | "llama2-7b" |
    # "llama3-8b" | "llama3.2-3b" | "llama3-70b" | "laguna-xs.2" |
    # "laguna-tiny" | "kanana-2-30b-a3b" | "kanana-tiny" |
    # "nemotron-3-super-120b-a12b" | "nemotron-tiny" | "solar-open2-250b" |
    # "solar-tiny" | "granite-4.0-h-micro" | "granite-tiny" | "zaya1-8b" |
    # "zaya-tiny" | "dots3-note-prev" | "dots3-tiny" | "sdar-30b-a3b-chat" |
    # "sdar-tiny" | "ouro-2.6b" | "ouro-tiny"
    model_id: str = "tiny"
    tokenizer: str = "byte"  # "byte" | transformers tokenizer path
    checkpoint_path: Optional[str] = None  # ray_tpu.train pytree checkpoint
    seed: int = 0
    # extra LlamaConfig overrides applied on top of the preset, any field of
    # it — e.g. {"moe_experts": 8, "moe_top_k": 2} serves a MoE variant (the
    # serving path is dropless: tokens sorted by expert through a grouped
    # matmul, at every batch size, models/patterned.py:_moe_decode_ffn;
    # "moe_shared_d_ff" adds a shared expert, "moe_routed_scale" scales the
    # routed sum). The laguna presets' layers are not alike: a caller that
    # sets "n_layers" sets "layer_types", "heads_per_layer" and "mlp_types"
    # to that length too, or gets the pattern's first entries
    model_kwargs: dict = dataclasses.field(default_factory=dict)


def resolve_llama_config(model: "ModelConfig", engine: "EngineConfig", min_vocab: int = 0):
    """ModelConfig + EngineConfig -> concrete LlamaConfig (preset + kwargs,
    vocab widened to cover the tokenizer). Shared by the continuous-batching
    engine and the gang (multi-process SPMD) generator so both resolve a
    model id identically."""
    import dataclasses as _dc

    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    presets = {
        "tiny": LlamaConfig.tiny,
        "llama2-7b": LlamaConfig.llama2_7b,
        "llama3-8b": LlamaConfig.llama3_8b,
        "llama3.2-3b": LlamaConfig.llama32_3b,
        "llama3-70b": LlamaConfig.llama3_70b,
        "laguna-xs.2": LlamaConfig.laguna_xs2,
        "laguna-tiny": LlamaConfig.laguna_tiny,
        "kanana-2-30b-a3b": LlamaConfig.kanana2_30b_a3b,
        "kanana-tiny": LlamaConfig.kanana_tiny,
        "nemotron-3-super-120b-a12b": LlamaConfig.nemotron3_super,
        "nemotron-tiny": LlamaConfig.nemotron_tiny,
        "solar-open2-250b": LlamaConfig.solar_open2_250b,
        "solar-tiny": LlamaConfig.solar_tiny,
        "granite-4.0-h-micro": LlamaConfig.granite4_h_micro,
        "granite-tiny": LlamaConfig.granite_tiny,
        "zaya1-8b": LlamaConfig.zaya1_8b,
        "zaya-tiny": LlamaConfig.zaya_tiny,
        "dots3-note-prev": LlamaConfig.dots3_note_prev,
        "dots3-tiny": LlamaConfig.dots3_tiny,
        "sdar-30b-a3b-chat": LlamaConfig.sdar_30b_a3b,
        "sdar-tiny": LlamaConfig.sdar_tiny,
        "ouro-2.6b": LlamaConfig.ouro_2_6b,
        "ouro-tiny": LlamaConfig.ouro_tiny,
    }
    kw = dict(
        max_seq_len=engine.max_seq_len,
        dtype=jnp.bfloat16 if engine.dtype == "bfloat16" else jnp.float32,
    )
    kw.update(model.model_kwargs)
    if model.model_id not in presets:
        raise ValueError(f"unknown model_id: {model.model_id}")
    cfg = presets[model.model_id](**kw)
    if cfg.vocab_size < min_vocab:
        cfg = _dc.replace(cfg, vocab_size=min_vocab)
    return cfg


def refuse_latent(cfg, module: str) -> None:
    """``llm/spmd.py`` and ``llm/gang.py`` have their own copies of the
    programs that fill, copy and read the cache, and a sharded engine places
    it on a mesh; none of them knows a latent-attention cache (one shared
    rotated key and one latent a token, no key-value heads to shard). They
    refuse such a model by name rather than serve it wrongly."""
    if cfg.kv_latent_rank:
        raise NotImplementedError(
            f"{module}: a latent-attention model (kv_latent_rank="
            f"{cfg.kv_latent_rank}) is served on one device by llm/engine.py "
            "JaxEngine with tensor_parallel_degree=1; this path has no rule "
            "for its cache"
        )


def refuse_further_stripes(cfg, module: str) -> None:
    """A hand-over of keys and values (``llm/disagg.py``) moves a slot's
    ``k`` and ``v`` and nothing else: a model whose cache holds further
    stripes (a second latent kind's, an indexer's keys: ``models/patterned.py
    stripe_cache_shapes``) is refused by name rather than decoded over stripes
    that never arrived."""
    from ray_tpu.models.patterned import stripe_cache_shapes

    more = [name for name in stripe_cache_shapes(cfg, 1, 1) if name not in ("k", "v")]
    if more:
        raise NotImplementedError(
            f"{module}: a model whose cache holds the stripes {', '.join(more)} beside k and v "
            "(latent attention of two widths, an indexer) is served on one device by "
            "llm/engine.py JaxEngine; this path hands over k and v alone"
        )


def refuse_stateful(cfg, module: str) -> None:
    """The same for a model with layers that keep a state (state-space or
    delta-rule, or attention whose queries and keys pass convolutions: any
    whose cache has ``models/patterned.py STATE_LEAVES``): their slots hold a
    state or a convolution tail beside keys and values, which those copies of
    the cache's programs, a mesh and a hand-over of keys and values alone
    (``llm/disagg.py``) do not know."""
    from ray_tpu.models.patterned import state_cache_shapes

    # the kinds by their leaves' names: ``ssm_state`` -> state-space
    kinds = dict.fromkeys(leaf.split("_")[0] for leaf in state_cache_shapes(cfg, 1))
    if kinds:
        names = " and ".join({"ssm": "state-space", "kda": "delta-rule",
                              "cca": "convolved-attention"}.get(k, k) for k in kinds)
        raise NotImplementedError(
            f"{module}: a model with {names} layers is served on one device by "
            "llm/engine.py JaxEngine with tensor_parallel_degree=1; this path has no "
            "rule for a slot's recurrent state"
        )


def refuse_blocks(cfg, module: str) -> None:
    """The same for a model that generates by diffusion over blocks
    (``LlamaConfig.block_length``): a slot's block (its tokens, which of them
    are masked, the step within it) lives on the device beside the cache and
    a step hands out a block and whether it committed, which those copies of
    the loop, a mesh and a hand-over of keys and values alone
    (``llm/disagg.py``) do not carry."""
    if cfg.block_length:
        raise NotImplementedError(
            f"{module}: a model that generates by blocks (block_length="
            f"{cfg.block_length}) is served on one device by llm/engine.py JaxEngine with "
            "tensor_parallel_degree=1; this path has no block step and does not carry a "
            "slot's block state"
        )


def refuse_looped(cfg, module: str) -> None:
    """The same for a model whose stack runs several times a token
    (``LlamaConfig.loop_passes``): its cache has a row of keys and values for
    every pass and layer and its head reads the pass an exit gate picks, which
    those copies of the cache's programs do not know, no rule places on a
    mesh, and a hand-over made for a row a layer (``llm/disagg.py``) has not
    been held to."""
    if cfg.loop_passes > 1:
        raise NotImplementedError(
            f"{module}: a model whose stack runs several times a token (loop_passes="
            f"{cfg.loop_passes}) is served on one device by llm/engine.py JaxEngine with "
            "tensor_parallel_degree=1; this path has no rule for a cache row a pass and "
            "layer, nor for the exit gate"
        )


@dataclasses.dataclass
class LLMConfig:
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)
    # serve-level options
    name: Optional[str] = None
    num_replicas: int = 1
    ray_actor_options: Optional[dict] = None
    autoscaling_config: Optional[dict] = None
    # multi-LoRA: adapter name -> pytree-checkpoint path, loaded into the
    # engine's stacked adapter slots at replica start; requests select one
    # with model="<served_name>:<adapter>" (reference: the LoRA model-id
    # convention in llm/_internal/serve)
    lora_adapters: dict = dataclasses.field(default_factory=dict)
    # Startup (compile) budget override: how long a replica may legitimately
    # sit in __init__ before serve may treat it as hung. None = derive from
    # the engine shape via compile_budget_s().
    startup_grace_s: Optional[float] = None

    @property
    def served_name(self) -> str:
        return self.name or self.model.model_id

    def compile_budget_s(self) -> float:
        """Worst-case replica startup. The engine runs its whole program set
        before it is ready (``llm/engine.py _warm_programs``): a final chunk
        per prefill bucket, the middle chunk at every row count up to
        ``max_concurrent_admissions``, and one decode program per KV pool; a
        checkout's first start compiles them all (4-11 s a chunk program on a
        v5e at PR 34; 22-26 s a final chunk of Granite's 40 layers and 47-55 s
        one that carries the decode step, 350 s its whole set beside 100 s of
        weights: PERF.md section 6, PR 49, which is what 45 s a program covers).
        Doubled for sharded (gang) meshes whose jax.distributed world must
        also rendezvous. Serve uses this as ``initial_health_grace_s`` so a
        slow first jit is STARTING, not dead."""
        if self.startup_grace_s is not None:
            return self.startup_grace_s
        e = self.engine
        pools = max(len(e.seq_len_buckets), 1)
        programs = (len(e.prefill_buckets) + min(e.max_concurrent_admissions, CHUNK_ROWS_MAX)
                    + 1) * pools
        sharded = e.tensor_parallel_degree * e.sequence_parallel_degree > 1
        return 120.0 + 45.0 * programs * (2 if sharded else 1)
