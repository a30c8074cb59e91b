"""Lockstep SPMD batch generation for gang (multi-process) LLM replicas.

Reference: the reference serves models larger than one host by
gang-scheduling vLLM engine workers TPxPP via placement groups
(``llm/_internal/serve/deployments/llm/vllm/vllm_models.py:176-190``) with
Ray compiled-graph control flow between them. The TPU-first shape is
different: every process in the gang runs ONE AND THE SAME jitted SPMD
program over a global mesh (``jax.distributed`` world), so there is no
driver/worker RPC inside a decode step — the "coordination" is XLA
collectives over ICI/DCN.

The consequence is the lockstep rule: every process must issue identical
programs in identical order with identical host-side control flow. This
module therefore does deterministic synchronous *batch* generation (the
per-call analog of one continuous-batching wave): tokenize → bucket-pad →
prefill → decode loop, with sampling in-program from a seeded key so every
process observes the same tokens without any cross-process chatter. The
dynamic continuous-batching engine (``llm/engine.py``) stays the
single-process serving path; ``GangLLMServer`` (``llm/gang.py``) broadcasts
each batch to all gang workers.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

from ray_tpu.llm.config import (
    LLMConfig,
    SamplingParams,
    refuse_blocks,
    refuse_latent,
    refuse_looped,
    refuse_stateful,
    resolve_llama_config,
)


def _pad_bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class SPMDGenerator:
    """Deterministic batched prefill+decode over a (possibly multi-process)
    mesh. All array programs are jitted with explicit shardings; host logic
    is pure function of the inputs, so N processes stay in lockstep."""

    def __init__(self, config: LLMConfig, mesh=None):
        import jax
        import numpy as np

        from ray_tpu.llm.tokenizer import get_tokenizer
        from ray_tpu.models.llama import init_params, param_shardings
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.train.checkpoint import restore_pytree

        mc, ec = config.model, config.engine
        self.config = config
        self.tokenizer = get_tokenizer(mc.tokenizer)
        self.model_cfg = resolve_llama_config(
            mc, ec, min_vocab=self.tokenizer.vocab_size
        )
        refuse_latent(self.model_cfg, "llm/spmd.py")
        refuse_stateful(self.model_cfg, "llm/spmd.py")
        refuse_blocks(self.model_cfg, "llm/spmd.py")
        refuse_looped(self.model_cfg, "llm/spmd.py")
        if mesh is None:
            n = len(jax.devices())
            if (
                n > 1
                and ec.tensor_parallel_degree == 1
                and ec.sequence_parallel_degree == 1
            ):
                # tp=1 on a multi-device world = REPLICATED lockstep: every
                # process computes the identical full batch over a pure
                # data axis (params and cache replicate; zero per-step
                # collectives). The gang then buys availability and
                # host-side throughput, not memory — the right shape when
                # the model fits one process, and the collective-free
                # regime the decode_steps/run-ahead knobs are benched in.
                # NOTE: defaults used to fall through to tp=n sharding —
                # log the switch so a gang that NEEDS sharding to fit is
                # told which knob restores it instead of OOMing silently.
                logging.getLogger(__name__).warning(
                    "tp=1 on %d devices: building a REPLICATED (dp=%d) "
                    "mesh; set tensor_parallel_degree>1 to shard params/KV "
                    "across the gang",
                    n,
                    n,
                )
                spec = MeshSpec(dp=n)
            else:
                # all GLOBAL devices (jax.devices() spans the
                # jax.distributed world): tp*sp must cover them; -1 infers
                # tp; explicit tp>1 shards params/KV over the gang
                spec = MeshSpec(
                    tp=ec.tensor_parallel_degree or -1,
                    sp=ec.sequence_parallel_degree,
                )
                try:
                    spec = spec.resolve(n)
                except ValueError:
                    spec = MeshSpec(tp=-1).resolve(n)
            mesh = build_mesh(spec)
        self.mesh = mesh
        self.max_seq_len = ec.max_seq_len
        self.prefill_buckets = tuple(ec.prefill_buckets)
        if mc.checkpoint_path:
            params = restore_pytree(mc.checkpoint_path)
            shardings = param_shardings(self.model_cfg, mesh)
            self.params = jax.tree.map(
                lambda x, s: jax.make_array_from_callback(
                    np.shape(x), s, lambda idx: np.asarray(x)[idx]
                ),
                params,
                shardings,
            )
        else:
            self.params = init_params(
                jax.random.PRNGKey(mc.seed), self.model_cfg, mesh=mesh
            )
        self._programs()

    # -- compiled programs ---------------------------------------------------

    def _programs(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.models.llama import decode_step, init_kv_cache, prefill

        cfg = self.model_cfg
        mesh = self.mesh
        rep = NamedSharding(mesh, P())
        # KV cache [L, B, K, S, D]: kv heads ride the tp axis (same layout
        # the tp rules give the wk/wv params), everything else replicated;
        # replicate when tp doesn't divide the kv heads (GQA with small kv)
        tp = mesh.shape.get("tp", 1)
        kv_spec = (
            P(None, None, "tp", None, None)
            if tp > 1 and cfg.n_kv_heads % tp == 0
            else P()
        )
        kv = NamedSharding(mesh, kv_spec)
        self._cache_shardings = {"k": kv, "v": kv, "length": rep}

        def make_cache(batch: int, max_len: int):
            return init_kv_cache(cfg, batch, max_len)

        self._make_cache = jax.jit(
            make_cache,
            static_argnums=(0, 1),
            out_shardings=self._cache_shardings,
        )

        def run_prefill(params, cache, tokens, lengths):
            return prefill(params, cache, tokens, cfg, lengths=lengths)

        self._prefill = jax.jit(
            run_prefill,
            donate_argnums=(1,),
            out_shardings=(rep, self._cache_shardings),
        )

        K = min(64, cfg.vocab_size)
        self._top_k_static = K

        def sample(logits, temp, key, top_k):
            """[B, V] fp32 -> [B] int32; greedy at temp<=0, else
            top-K/temperature categorical. In-program: every gang process
            computes the same replicated tokens from the same seeded key."""
            greedy = jnp.argmax(logits, axis=-1)
            vals, idx = jax.lax.top_k(logits, K)  # [B, K]
            rank_ok = jnp.arange(K)[None, :] < top_k
            scaled = jnp.where(
                rank_ok, vals / jnp.maximum(temp, 1e-6), -jnp.inf
            )
            cat = jax.random.categorical(key, scaled, axis=-1)  # [B]
            sampled = jnp.take_along_axis(idx, cat[:, None], axis=1)[:, 0]
            return jnp.where(temp <= 0.0, greedy, sampled).astype(jnp.int32)

        def run_decode(params, cache, tokens, temp, key, top_k):
            logits, cache = decode_step(params, cache, tokens, cfg)
            return sample(logits, temp, key, top_k), cache

        self._decode = jax.jit(
            run_decode,
            donate_argnums=(1,),
            out_shardings=(rep, self._cache_shardings),
        )
        self._sample = jax.jit(sample, out_shardings=rep)

    # -- generation ----------------------------------------------------------

    @staticmethod
    def _host(arr):
        """Fetch a replicated global array's value on this process (a
        multi-process replicated Array is not fully addressable, so
        np.asarray would throw — every local shard holds the full value)."""
        import numpy as np

        return np.asarray(arr.addressable_shards[0].data)

    def generate_batch(
        self,
        token_lists: list[list[int]],
        sampling_params: Optional[SamplingParams] = None,
    ) -> list[list[int]]:
        """Generate completions for a batch of prompts, lockstep across the
        gang. Returns per-prompt generated token ids (prompt excluded)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        p = sampling_params or SamplingParams()
        B = len(token_lists)
        lengths = [len(t) for t in token_lists]
        limit = min(self.prefill_buckets[-1], self.max_seq_len - 1)
        if max(lengths) > limit:
            # reject, don't crash the lockstep batch: the caller surfaces
            # this as a 400 (vLLM's prompt-too-long contract)
            raise ValueError(
                f"prompt length {max(lengths)} exceeds the maximum "
                f"{limit} (largest prefill bucket / max_seq_len)"
            )
        T = _pad_bucket(max(lengths), self.prefill_buckets)
        # KV length from a fixed bucket ladder, NOT T + max_tokens directly:
        # program shapes must be user-independent or every distinct
        # max_tokens value forces a fresh XLA compile on every gang process
        max_len = self.max_seq_len
        for b in self.prefill_buckets:
            if T + p.max_tokens <= b:
                max_len = min(b, self.max_seq_len)
                break
        toks = np.zeros((B, T), np.int32)
        for i, t in enumerate(token_lists):
            toks[i, : len(t)] = t

        cache = self._make_cache(B, max_len)
        logits, cache = self._prefill(
            self.params,
            cache,
            jnp.asarray(toks),
            jnp.asarray(lengths, jnp.int32),
        )
        key = jax.random.PRNGKey(p.seed if p.seed is not None else 0)
        temp = jnp.asarray(p.temperature, jnp.float32)
        top_k = jnp.asarray(min(p.top_k, self._top_k_static), jnp.int32)
        key, sub = jax.random.split(key)
        nxt = self._sample(logits, temp, sub, top_k)

        eos = self.tokenizer.eos_id
        stop = set(p.stop_token_ids or ())
        out: list[list[int]] = [[] for _ in range(B)]
        finished = [False] * B
        steps = min(p.max_tokens, max_len - max(lengths))
        for step in range(steps):
            host_tok = self._host(nxt)
            for i in range(B):
                if finished[i]:
                    continue
                t = int(host_tok[i])
                # ignore_eos exempts only EOS, never user stop tokens
                # (the JaxEngine contract, engine.py stop handling)
                if (t == eos and not p.ignore_eos) or t in stop:
                    finished[i] = True
                    continue
                out[i].append(t)
                if len(out[i]) >= p.max_tokens:
                    finished[i] = True
            if all(finished) or step == steps - 1:
                break
            key, sub = jax.random.split(key)
            nxt, cache = self._decode(
                self.params, cache, nxt, temp, sub, top_k
            )
        return out


class SPMDEngineWorker:
    """Per-process half of the gang's CONTINUOUS-BATCHING engine.

    The single-host ``JaxEngine`` makes admission/chunk/sampling decisions
    inside its own loop; in a gang that loop must not exist on workers —
    every process has to issue identical programs in identical order. So
    the replica (``GangLLMServer``) runs the scheduler and broadcasts one
    ``StepPlan`` per lockstep iteration; each process executes the plan's
    programs against its local shard of the slot cache and rank 0 reports
    the sampled tokens back. Chunked prefill, the prefix cache, and slot
    state evolve identically on all ranks because they are pure functions
    of the plan stream. (Reference contract: continuous batching at any
    TP×PP, ``llm/_internal/serve/.../vllm_engine.py``.)

    Determinism rule: sampling keys arrive IN the plan, derived from
    ``(request_seed, token_index)`` — replay after a gang rebuild
    regenerates the exact streamed prefix, and batch composition never
    affects a request's tokens.
    """

    def __init__(self, config: LLMConfig, generator: SPMDGenerator):
        import jax
        import jax.numpy as jnp
        import numpy as np  # noqa: F401

        ec = config.engine
        self.config = config
        self.gen = generator
        self.params = generator.params
        self.model_cfg = generator.model_cfg
        self.mesh = generator.mesh
        self.n_slots = ec.max_num_seqs
        self.max_len = ec.max_seq_len
        self.chunk = min(ec.prefill_buckets)
        self._prefix: dict[str, tuple] = {}  # key -> (k, v) device arrays
        self._compile()
        self.cache = self._make_cache(self.n_slots, self.max_len)
        # per-slot scratch stripes: one per in-flight chunked admission
        # (pipelined admissions — up to max_concurrent_admissions coexist)
        self._ones: dict[int, dict] = {}
        # device-resident next-token inputs: decode programs and run-ahead
        # plans chain on these without the host ever seeing the tokens
        # (the host may dispatch plan N+1 before plan N's tokens arrive)
        self._dev_toks = jnp.zeros((self.n_slots,), jnp.int32)

    def _compile(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.models.llama import decode_step, init_kv_cache, prefill

        cfg = self.model_cfg
        mesh = self.mesh
        rep = NamedSharding(mesh, P())
        tp = mesh.shape.get("tp", 1)
        kv_spec = (
            P(None, None, "tp", None, None)
            if tp > 1 and cfg.n_kv_heads % tp == 0
            else P()
        )
        kv = NamedSharding(mesh, kv_spec)
        cache_sh = {"k": kv, "v": kv, "length": rep}
        self._cache_shardings = cache_sh

        self._make_cache = jax.jit(
            lambda b, m: init_kv_cache(cfg, b, m),
            static_argnums=(0, 1),
            out_shardings=cache_sh,
        )

        K = min(64, cfg.vocab_size)
        self._top_k_static = K

        def sample_row(logits_row, temp, top_k, key):
            greedy = jnp.argmax(logits_row, -1)
            vals, idxs = jax.lax.top_k(logits_row, K)
            rank_ok = jnp.arange(K) < top_k
            scaled = jnp.where(rank_ok, vals / jnp.maximum(temp, 1e-6), -jnp.inf)
            sampled = idxs[jax.random.categorical(key, scaled)]
            return jnp.where(temp <= 0.0, greedy, sampled).astype(jnp.int32)

        def chunk_mid(params, one, tokens, eff, start):
            _, one = prefill(
                params, one, tokens, cfg, lengths=eff, start_pos=start,
                with_logits=False,
            )
            return one

        self._chunk_mid = jax.jit(
            chunk_mid, donate_argnums=(1,), out_shardings=cache_sh
        )

        def chunk_final(params, cache, one, tokens, eff, start, slot,
                        temp, top_k, key):
            last_logits, one = prefill(
                params, one, tokens, cfg, lengths=eff, start_pos=start,
            )
            total = start[0] + eff[0]
            cache = {
                "k": cache["k"].at[:, slot].set(one["k"][:, 0]),
                "v": cache["v"].at[:, slot].set(one["v"][:, 0]),
                "length": cache["length"].at[slot].set(total),
            }
            tok = sample_row(last_logits[0], temp, top_k, key)
            return tok, cache

        self._chunk_final = jax.jit(
            chunk_final, donate_argnums=(2,), out_shardings=(rep, cache_sh)
        )

        def decode(params, cache, tokens, temps, top_ks, keys):
            """K lockstep decode steps in ONE broadcast program (lax.scan).
            ``keys``: [K, S, 2] per-step/per-slot PRNG keys derived host-side
            from (request_seed, token_index) so the sampled stream is
            byte-identical at any K. Returns ([K, S] tokens, last tokens,
            cache) — the last tokens stay device-resident for chaining."""

            def body(carry, step_keys):
                toks, cache = carry
                logits, cache = decode_step(params, cache, toks, cfg)
                nt = jax.vmap(sample_row)(logits, temps, top_ks, step_keys)
                return (nt, cache), nt

            (last, cache), out = jax.lax.scan(body, (tokens, cache), keys)
            return out, last, cache

        # one jitted program; XLA specializes per K (keys.shape[0]) — the
        # sweepable decode_steps values each compile once
        self._decode = jax.jit(
            decode, donate_argnums=(1,), out_shardings=(rep, rep, cache_sh)
        )
        # tiny device-side scatter keeping the decode token chain host-free
        # when an admission's first token lands (same idiom as the engine's
        # _set_tok_jit)
        self._set_tok = jax.jit(
            lambda toks, slot, tok: toks.at[slot].set(tok),
            donate_argnums=(0,),
            out_shardings=rep,
        )

        def seed_prefix(one, pk, pv):
            m = pk.shape[2]
            return {
                "k": one["k"].at[:, 0, :, :m].set(pk),
                "v": one["v"].at[:, 0, :, :m].set(pv),
                "length": one["length"],
            }

        self._seed_prefix = jax.jit(
            seed_prefix, donate_argnums=(0,), out_shardings=cache_sh
        )
        # prefix extraction specializes per bucket-aligned m (bounded:
        # max_len / chunk distinct shapes)
        self._extract_cache: dict[int, object] = {}

    def _extract(self, m: int):
        import jax

        fn = self._extract_cache.get(m)
        if fn is None:
            fn = jax.jit(
                lambda cache, slot: (
                    cache["k"][:, slot, :, :m],
                    cache["v"][:, slot, :, :m],
                )
            )
            self._extract_cache[m] = fn
        return fn

    def step(self, plan: dict):
        """Execute one lockstep plan; returns the sampled tokens
        {"admit_toks": {slot: int}, "toks": [K][n_slots]|None} (all ranks
        compute them, only rank 0's copy is consumed).

        Plan sections execute in a fixed order every rank must share:
        evict → stores → admits → decode. ``stores`` precedes ``admits`` so a
        plan that both snapshots a finished prompt's prefix KV and admits a
        new request into the same (just-freed) slot reads the OLD stripe.
        Each ``admits`` entry is one chunk of one in-flight admission — up
        to max_concurrent_admissions interleave per plan. ``decode`` runs a
        K-step scanned program chained on the device-resident token vector
        (run-ahead plans never wait for the host to see sampled tokens)."""
        import jax.numpy as jnp

        for key in plan.get("evict", ()):
            self._prefix.pop(key, None)
        # several admissions can finalize in one plan, so stores is a list
        for store in plan.get("stores", ()):
            if store["key"] not in self._prefix:
                pk, pv = self._extract(store["m"])(
                    self.cache, jnp.int32(store["slot"])
                )
                self._prefix[store["key"]] = (pk, pv)
        admit_toks: dict[int, int] = {}
        for adm in plan.get("admits", ()):
            slot = adm["slot"]
            if adm.get("fresh"):
                self._ones[slot] = self._make_cache(1, self.max_len)
                pref = adm.get("seed_prefix")
                if pref is not None and pref in self._prefix:
                    pk, pv = self._prefix[pref]
                    self._ones[slot] = self._seed_prefix(
                        self._ones[slot], pk, pv
                    )
            tokens = jnp.asarray(adm["tokens"])
            eff = jnp.asarray([adm["eff"]], jnp.int32)
            start = jnp.asarray([adm["start"]], jnp.int32)
            if not adm["final"]:
                self._ones[slot] = self._chunk_mid(
                    self.params, self._ones[slot], tokens, eff, start
                )
            else:
                tok, self.cache = self._chunk_final(
                    self.params, self.cache, self._ones.pop(slot), tokens,
                    eff, start,
                    jnp.int32(slot),
                    jnp.asarray(adm["temp"], jnp.float32),
                    jnp.asarray(adm["top_k"], jnp.int32),
                    jnp.asarray(adm["key"], jnp.uint32),
                )
                # chain the first sampled token into the decode inputs ON
                # DEVICE: the next decode plan may already be dispatched
                self._dev_toks = self._set_tok(
                    self._dev_toks, jnp.int32(slot), tok
                )
                admit_toks[slot] = int(SPMDGenerator._host(tok))
        toks = None
        dec = plan.get("decode")
        if dec is not None:
            keys = jnp.asarray(dec["keys"], jnp.uint32)  # [K, S, 2]
            toks_dev, self._dev_toks, self.cache = self._decode(
                self.params,
                self.cache,
                self._dev_toks,
                jnp.asarray(dec["temps"], jnp.float32),
                jnp.asarray(dec["top_ks"], jnp.int32),
                keys,
            )
            toks = SPMDGenerator._host(toks_dev).tolist()  # [K][S]
        return {"admit_toks": admit_toks, "toks": toks}
