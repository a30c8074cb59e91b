"""Prefill/decode disaggregated serving.

Reference: ``python/ray/llm/_internal/serve/deployments/prefill_decode_disagg/``
— prefill and decode run in separate replica pools sized independently
(prefill is compute-bound, decode is memory-bandwidth-bound), with the KV
cache handed off between them.

TPU mapping: the KV handoff rides the shared-memory object plane between
replica actors (device→host→device today; same-host transfers hit the native
arena store). Prefill replicas run the bucketed prefill program only; decode
replicas run the slot-batched decode program only, so each pool compiles and
serves exactly one kind of workload.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

from ray_tpu.llm.config import (
    LLMConfig, SamplingParams, refuse_blocks, refuse_further_stripes, refuse_looped,
    refuse_stateful, resolve_llama_config,
)


class PrefillWorker:
    """Deployment: prompt -> (KV cache, first-token logits)."""

    def __init__(self, llm_config: LLMConfig):
        import jax

        from ray_tpu.llm.tokenizer import get_tokenizer
        from ray_tpu.llm.engine import JaxEngine

        # the hand-over is keys and values alone: before any weight is made
        model_cfg = resolve_llama_config(llm_config.model, llm_config.engine)
        refuse_stateful(model_cfg, "llm/disagg.py")
        refuse_further_stripes(model_cfg, "llm/disagg.py")
        refuse_blocks(model_cfg, "llm/disagg.py")
        refuse_looped(model_cfg, "llm/disagg.py")
        # reuse the engine's model construction, not its slot loop
        self._engine_shell = JaxEngine.__new__(JaxEngine)
        self._engine_shell.config = llm_config
        self._engine_shell.tokenizer = get_tokenizer(llm_config.model.tokenizer)
        self._engine_shell._mesh = None
        self._engine_shell._build_model()
        self.config = llm_config
        self.tokenizer = self._engine_shell.tokenizer
        self.params = self._engine_shell.params
        self.model_cfg = self._engine_shell.model_cfg

    def prefill(self, prompt: str) -> dict:
        import jax.numpy as jnp

        from ray_tpu.models.llama import init_kv_cache, prefill

        ids = self.tokenizer.encode(prompt)
        max_prompt = self.config.engine.max_seq_len - 1
        ids = ids[-max_prompt:]
        bucket = next(
            (b for b in self.config.engine.prefill_buckets if b >= len(ids)),
            self.config.engine.max_seq_len,
        )
        toks = np.zeros((1, bucket), np.int32)
        toks[0, : len(ids)] = ids
        cache = init_kv_cache(self.model_cfg, 1, self.config.engine.max_seq_len)
        last_logits, cache = prefill(
            self.params,
            cache,
            jnp.asarray(toks),
            self.model_cfg,
            lengths=jnp.asarray([len(ids)], jnp.int32),
        )
        # host-side handoff payload (the object plane carries it to decode)
        return {
            "k": np.asarray(cache["k"]),
            "v": np.asarray(cache["v"]),
            "length": int(len(ids)),
            "first_token": int(np.argmax(np.asarray(last_logits[0]))),
            "prompt_token_ids": list(ids),
        }


class DecodeWorker:
    """Deployment: adopted KV cache -> generated tokens."""

    def __init__(self, llm_config: LLMConfig):
        import jax

        from ray_tpu.llm.engine import JaxEngine
        from ray_tpu.llm.tokenizer import get_tokenizer

        model_cfg = resolve_llama_config(llm_config.model, llm_config.engine)
        refuse_stateful(model_cfg, "llm/disagg.py")
        refuse_further_stripes(model_cfg, "llm/disagg.py")
        refuse_blocks(model_cfg, "llm/disagg.py")
        refuse_looped(model_cfg, "llm/disagg.py")
        shell = JaxEngine.__new__(JaxEngine)
        shell.config = llm_config
        shell.tokenizer = get_tokenizer(llm_config.model.tokenizer)
        shell._mesh = None
        shell._build_model()
        self.config = llm_config
        self.tokenizer = shell.tokenizer
        self.params = shell.params
        self.model_cfg = shell.model_cfg
        self._decode = None

    def decode(self, handoff: dict, max_tokens: int = 64) -> dict:
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.llama import decode_step

        cache = {
            "k": jnp.asarray(handoff["k"]),
            "v": jnp.asarray(handoff["v"]),
            "length": jnp.asarray([handoff["length"]], jnp.int32),
        }
        if self._decode is None:
            cfg = self.model_cfg

            def step(params, cache, token):
                return decode_step(params, cache, token, cfg)

            self._decode = jax.jit(step, donate_argnums=(1,))
        token = jnp.asarray([handoff["first_token"]], jnp.int32)
        out = [int(token[0])]
        eos = self.tokenizer.eos_id
        for _ in range(max_tokens - 1):
            logits, cache = self._decode(self.params, cache, token)
            nxt = int(np.argmax(np.asarray(logits[0])))
            if nxt == eos:
                break
            out.append(nxt)
            token = jnp.asarray([nxt], jnp.int32)
            if handoff["length"] + len(out) >= self.config.engine.max_seq_len:
                break
        return {
            "token_ids": out,
            "text": self.tokenizer.decode(out),
        }


class DisaggRouter:
    """Ingress: prefill pool -> KV handoff -> decode pool."""

    def __init__(self, prefill_handle, decode_handle):
        self.prefill = prefill_handle
        self.decode = decode_handle

    def __call__(self, request) -> dict:
        body = request.json() if hasattr(request, "json") else request
        prompt = body.get("prompt", "")
        max_tokens = int(body.get("max_tokens", 64))
        # the DeploymentResponse forwards the handoff ref replica-to-replica:
        # KV bytes go prefill-replica -> object store -> decode-replica
        # without a driver round-trip
        handoff = self.prefill.prefill.remote(prompt)
        result = self.decode.decode.remote(handoff, max_tokens).result(
            timeout_s=600
        )
        return {"text": result["text"], "num_tokens": len(result["token_ids"])}


def build_pd_disagg_app(
    llm_config: LLMConfig,
    *,
    num_prefill_replicas: int = 1,
    num_decode_replicas: int = 1,
):
    """Reference: ``prefill_decode_disagg`` builders — separate, independently
    sized pools behind one router."""
    from ray_tpu import serve

    prefill = serve.deployment(
        PrefillWorker,
        name=f"prefill:{llm_config.served_name}",
        num_replicas=num_prefill_replicas,
        max_ongoing_requests=4,
    ).bind(llm_config)
    decode = serve.deployment(
        DecodeWorker,
        name=f"decode:{llm_config.served_name}",
        num_replicas=num_decode_replicas,
        max_ongoing_requests=4,
    ).bind(llm_config)
    router = serve.deployment(
        DisaggRouter, name=f"pd-router:{llm_config.served_name}"
    )
    return router.bind(prefill, decode)
