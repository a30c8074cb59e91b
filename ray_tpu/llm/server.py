"""LLMServer: the serve deployment hosting one JaxEngine replica.

Reference: ``python/ray/llm/_internal/serve/deployments/llm/llm_server.py:410``
(LLMServer wrapping a vLLM engine). A replica = one engine = one TPU host (or
slice via ray_actor_options resources); multi-replica = data parallel serving
behind the serve router.
"""

from __future__ import annotations

import time

from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.engine import JaxEngine
from ray_tpu.util import tracing


def sampling_from_body(body: dict) -> SamplingParams:
    """The sampling fields of an OpenAI-shaped request body. ``ignore_eos``
    and ``seed`` are this server's extensions (vLLM's names): a load
    generator that must get ``max_tokens`` tokens sets the first, a caller
    that must get the same sample twice the second. ``denoise_steps`` is for
    a model that generates by blocks (absent: the model's own)."""
    seed = body.get("seed")
    steps = body.get("denoise_steps")
    return SamplingParams(
        max_tokens=int(body.get("max_tokens", 64)),
        temperature=float(body.get("temperature", 0.0)),
        top_k=int(body.get("top_k", 50)),
        ignore_eos=bool(body.get("ignore_eos", False)),
        seed=None if seed is None else int(seed),
        denoise_steps=None if steps is None else int(steps),
    )


class LLMServer:
    def __init__(self, llm_config: LLMConfig):
        self.llm_config = llm_config
        self.engine = JaxEngine(llm_config)
        for name, path in (llm_config.lora_adapters or {}).items():
            self.load_lora(name, path)

    # -- multi-LoRA ----------------------------------------------------------

    def load_lora(self, name: str, path_or_weights) -> bool:
        """Load an adapter into THIS replica's engine stack (the
        reference's LoRA download-and-load role). With num_replicas > 1 a
        plain handle call reaches one replica — use
        ``handle.broadcast("load_lora", name, path)`` so every replica
        serves the adapter (or list it in ``LLMConfig.lora_adapters``,
        loaded at replica start)."""
        if isinstance(path_or_weights, str):
            from ray_tpu.train.checkpoint import restore_pytree

            weights = restore_pytree(path_or_weights)
        else:
            weights = path_or_weights
        self.engine.add_lora(name, weights)
        return True

    def unload_lora(self, name: str) -> bool:
        self.engine.remove_lora(name)
        return True

    def list_loras(self) -> list[str]:
        return self.engine.list_loras()

    def _lora_error(self, body: dict):
        """OpenAI-style 404 for an unknown adapter, instead of a raw
        KeyError escaping through the router as a 500."""
        lora = body.get("_lora")
        if lora and lora not in self.engine.list_loras():
            return {
                "error": {
                    "message": f"LoRA adapter {lora!r} not found on "
                    f"{self.llm_config.served_name}",
                    "code": 404,
                }
            }
        return None

    # -- OpenAI-shaped methods ----------------------------------------------

    def completions(self, body: dict) -> dict:
        err = self._lora_error(body)
        if err is not None:
            return err
        prompt = body.get("prompt", "")
        params = sampling_from_body(body)
        out = self.engine.generate(
            prompt, sampling_params=params, lora=body.get("_lora"),
            trace_ctx=tracing.current_context(),
        )
        return {
            "id": f"cmpl-{out.request_id}",
            "object": "text_completion",
            "created": int(time.time()),
            "model": self.llm_config.served_name,
            "choices": [
                {
                    "index": 0,
                    "text": out.text,
                    "finish_reason": out.finish_reason,
                }
            ],
            "usage": {
                "prompt_tokens": len(out.prompt_token_ids),
                "completion_tokens": len(out.token_ids),
                "total_tokens": len(out.prompt_token_ids) + len(out.token_ids),
            },
        }

    def chat(self, body: dict) -> dict:
        err = self._lora_error(body)
        if err is not None:
            return err
        messages = body.get("messages", [])
        prompt = self._render_chat(messages)
        params = sampling_from_body(body)
        out = self.engine.generate(
            prompt, sampling_params=params, lora=body.get("_lora"),
            trace_ctx=tracing.current_context(),
        )
        return {
            "id": f"chatcmpl-{out.request_id}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": self.llm_config.served_name,
            "choices": [
                {
                    "index": 0,
                    "message": {"role": "assistant", "content": out.text},
                    "finish_reason": out.finish_reason,
                }
            ],
            "usage": {
                "prompt_tokens": len(out.prompt_token_ids),
                "completion_tokens": len(out.token_ids),
                "total_tokens": len(out.prompt_token_ids) + len(out.token_ids),
            },
        }

    def completions_stream(self, body: dict):
        """Generator of OpenAI ``text_completion`` chunk dicts — one per
        generated token as the engine emits it (reference: the vLLM-engine
        streaming path in ``llm/_internal/serve/deployments/llm/llm_server.py``)."""
        err = self._lora_error(body)
        if err is not None:
            yield err
            return
        prompt = body.get("prompt", "")
        params = sampling_from_body(body)
        req = self.engine.submit(
            prompt, sampling_params=params, lora=body.get("_lora"),
            trace_ctx=tracing.current_context(),
        )
        created = int(time.time())
        for inc in self.engine.drain(req):
            yield {
                "id": f"cmpl-{req.request_id}",
                "object": "text_completion",
                "created": created,
                "model": self.llm_config.served_name,
                "choices": [
                    {"index": 0, "text": inc["text"], "finish_reason": None}
                ],
            }
        yield {
            "id": f"cmpl-{req.request_id}",
            "object": "text_completion",
            "created": created,
            "model": self.llm_config.served_name,
            "choices": [
                {"index": 0, "text": "", "finish_reason": req.finish_reason}
            ],
        }

    def chat_stream(self, body: dict):
        """Generator of OpenAI ``chat.completion.chunk`` dicts."""
        err = self._lora_error(body)
        if err is not None:
            yield err
            return
        prompt = self._render_chat(body.get("messages", []))
        params = sampling_from_body(body)
        req = self.engine.submit(
            prompt, sampling_params=params, lora=body.get("_lora"),
            trace_ctx=tracing.current_context(),
        )
        created = int(time.time())
        first = True
        for inc in self.engine.drain(req):
            delta = {"content": inc["text"]}
            if first:
                delta["role"] = "assistant"
                first = False
            yield {
                "id": f"chatcmpl-{req.request_id}",
                "object": "chat.completion.chunk",
                "created": created,
                "model": self.llm_config.served_name,
                "choices": [{"index": 0, "delta": delta, "finish_reason": None}],
            }
        yield {
            "id": f"chatcmpl-{req.request_id}",
            "object": "chat.completion.chunk",
            "created": created,
            "model": self.llm_config.served_name,
            "choices": [
                {"index": 0, "delta": {}, "finish_reason": req.finish_reason}
            ],
        }

    @staticmethod
    def _render_chat(messages: list[dict]) -> str:
        parts = []
        for m in messages:
            parts.append(f"<|{m.get('role', 'user')}|>{m.get('content', '')}")
        parts.append("<|assistant|>")
        return "".join(parts)

    # -- ops ----------------------------------------------------------------

    def model_info(self) -> dict:
        return {
            "id": self.llm_config.served_name,
            "object": "model",
            "owned_by": "ray_tpu",
        }

    def stats(self) -> dict:
        """The engine's ``get_stats()``: slots, queue, the cumulative
        counters, the latency histograms and ``engine_init_s`` (this
        replica's engine constructor to its loop thread's first pass)."""
        return self.engine.get_stats()

    def check_health(self):
        if not self.engine._thread.is_alive():
            raise RuntimeError("engine loop died")
