"""The layers over the engine: the batch processor, prefill and decode on
separate replicas, the OpenAI router and its event stream end to end, and
LoRA adapters in the engine, batched, and routed by model id."""

import numpy as np
import pytest

from ray_tpu.llm import (
    EngineConfig,
    JaxEngine,
    LLMConfig,
    ModelConfig,
    SamplingParams,
)

pytestmark = pytest.mark.timeout(600) if hasattr(pytest.mark, "timeout") else []


def test_batch_processor(ray_start_thread):
    from ray_tpu import data as rd
    from ray_tpu.llm import ProcessorConfig, build_llm_processor

    cfg = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte"),
        engine=EngineConfig(max_num_seqs=4, max_seq_len=64, prefill_buckets=(16, 32, 64)),
    )
    proc = build_llm_processor(
        ProcessorConfig(
            llm_config=cfg,
            batch_size=4,
            sampling_params={"max_tokens": 3, "temperature": 0.0, "ignore_eos": True},
        )
    )
    ds = rd.from_items([{"prompt": f"p{i}"} for i in range(8)], parallelism=2)
    rows = proc(ds).take_all()
    assert len(rows) == 8
    assert all(isinstance(r["generated_text"], str) for r in rows)


def test_prefill_decode_disagg(ray_start_thread):
    """Disagg path must produce the same greedy tokens as the unified engine."""
    from ray_tpu import serve
    from ray_tpu.llm import build_pd_disagg_app

    cfg = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(max_num_seqs=2, max_seq_len=64, prefill_buckets=(16, 32, 64)),
    )
    app = build_pd_disagg_app(cfg)
    handle = serve.run(app, name="pd")
    out = handle.remote({"prompt": "abc", "max_tokens": 5}).result(timeout_s=300)
    assert out["num_tokens"] == 5

    # unified engine reference for the same model/prompt
    eng = JaxEngine(cfg)
    ref = eng.generate(
        "abc", sampling_params=SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
    )
    eng.shutdown()
    assert out["text"] == eng.tokenizer.decode(ref.token_ids)
    serve.shutdown()


def test_openai_router_routing():
    from ray_tpu.llm.openai_api import OpenAIRouter
    from ray_tpu.serve.proxy import Request

    class FakeHandle:
        class chat:
            @staticmethod
            def remote(body):
                class R:
                    @staticmethod
                    def result(timeout_s=None):
                        return {"ok": True, "got": body["model"]}

                return R()

    router = OpenAIRouter(m1=FakeHandle())
    req = Request("GET", "/v1/models", {}, {}, b"")
    out = router(req)
    assert out["data"][0]["id"] == "m1"
    req = Request(
        "POST", "/v1/chat/completions", {}, {}, b'{"model": "m1", "messages": []}'
    )
    assert router(req)["ok"] is True
    req = Request("POST", "/v1/chat/completions", {}, {}, b'{"model": "nope"}')
    assert router(req)["error"]["code"] == 404


def test_openai_sse_end_to_end(ray_start_thread):
    """``stream: true`` through app → router → LLMServer → proxy as SSE
    (reference: the OpenAI router's StreamingResponse path)."""
    import json
    import time
    import urllib.request

    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    cfg = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(
            max_num_seqs=2, max_seq_len=64, prefill_buckets=(16, 32, 64)
        ),
    )
    serve.run(build_openai_app(cfg), name="llm-app", route_prefix="/")
    _, port = serve.start_proxy(port=0)
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/-/routes", timeout=5
            ) as r:
                if "/" in json.loads(r.read()):
                    break
        except Exception:
            pass
        time.sleep(0.2)
    body = json.dumps(
        {
            "model": cfg.served_name,
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4,
            "stream": True,
        }
    ).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=300) as r:
        assert r.headers.get("Content-Type") == "text/event-stream"
        raw = r.read().decode()
    events = [e for e in raw.split("\n\n") if e.startswith("data: ")]
    assert events[-1] == "data: [DONE]"
    chunks = [json.loads(e[len("data: ") :]) for e in events[:-1]]
    assert all(c["object"] == "chat.completion.chunk" for c in chunks)
    assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
    assert chunks[-1]["choices"][0]["finish_reason"] in ("stop", "length")
    # token deltas (all but the final finish chunk) are non-empty text
    assert sum(len(c["choices"][0]["delta"].get("content", "")) for c in chunks) > 0
    serve.shutdown()


def test_multi_lora_engine():
    """Stacked multi-LoRA: adapters change outputs per request within one
    compiled program; the base slot stays bit-identical to a no-LoRA engine."""
    import numpy as np

    from ray_tpu.models.llama import init_lora_stack

    cfg = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(
            max_num_seqs=2, max_seq_len=64, prefill_buckets=(16, 32, 64),
            max_loras=2, lora_rank=4,
        ),
    )
    eng = JaxEngine(cfg)
    p = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    base_out = eng.generate("hello world", sampling_params=p)

    # a zero adapter must not change anything
    zero = {
        k: np.zeros(v.shape[:1] + v.shape[2:], np.float32)
        for k, v in eng.loras.items()
    }
    eng.add_lora("zero", zero)
    out_zero = eng.generate("hello world", sampling_params=p, lora="zero")
    assert out_zero.token_ids == base_out.token_ids

    # a random adapter must change the continuation
    rng = np.random.default_rng(0)
    rand = {
        k: rng.normal(scale=0.5, size=v.shape[:1] + v.shape[2:]).astype(np.float32)
        for k, v in eng.loras.items()
    }
    eng.add_lora("rand", rand)
    out_rand = eng.generate("hello world", sampling_params=p, lora="rand")
    assert out_rand.token_ids != base_out.token_ids

    # base requests are unaffected by loaded adapters
    again = eng.generate("hello world", sampling_params=p)
    assert again.token_ids == base_out.token_ids

    assert eng.list_loras() == ["rand", "zero"]
    with pytest.raises(KeyError):
        eng.generate("x", sampling_params=p, lora="nope")
    with pytest.raises(RuntimeError):  # both slots in use
        eng.add_lora("third", zero)
    eng.remove_lora("zero")
    eng.add_lora("third", zero)  # freed slot is reusable
    eng.shutdown()

    # no-LoRA engine agrees with the base path of the LoRA engine
    cfg0 = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(
            max_num_seqs=2, max_seq_len=64, prefill_buckets=(16, 32, 64)
        ),
    )
    eng0 = JaxEngine(cfg0)
    ref = eng0.generate("hello world", sampling_params=p)
    eng0.shutdown()
    assert ref.token_ids == base_out.token_ids


def test_multi_lora_batched_mixed_adapters():
    """Concurrent requests with DIFFERENT adapters share decode steps and
    still match their sequential per-adapter results."""
    import numpy as np

    cfg = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(
            max_num_seqs=4, max_seq_len=64, prefill_buckets=(16, 32, 64),
            max_loras=2, lora_rank=4,
        ),
    )
    eng = JaxEngine(cfg)
    rng = np.random.default_rng(1)
    for name in ("a", "b"):
        eng.add_lora(
            name,
            {
                k: rng.normal(scale=0.5, size=v.shape[:1] + v.shape[2:]).astype(
                    np.float32
                )
                for k, v in eng.loras.items()
            },
        )
    p = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
    # sequential references
    ref_a = eng.generate("prompt one", sampling_params=p, lora="a").token_ids
    ref_b = eng.generate("prompt two", sampling_params=p, lora="b").token_ids
    ref_0 = eng.generate("prompt three", sampling_params=p).token_ids
    # concurrent mixed batch
    r1 = eng.submit("prompt one", sampling_params=p, lora="a")
    r2 = eng.submit("prompt two", sampling_params=p, lora="b")
    r3 = eng.submit("prompt three", sampling_params=p)
    for r in (r1, r2, r3):
        r.done.wait(timeout=120)
    assert r1.out_tokens == ref_a
    assert r2.out_tokens == ref_b
    assert r3.out_tokens == ref_0
    assert ref_a != ref_b
    eng.shutdown()


def test_lora_openai_model_id_routing(ray_start_thread):
    """model='<base>:<adapter>' routes to the base deployment and applies
    the adapter (reference: serve LoRA model-id convention)."""
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app
    from ray_tpu.serve.proxy import Request

    cfg = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
        engine=EngineConfig(
            max_num_seqs=2, max_seq_len=64, prefill_buckets=(16, 32, 64),
            max_loras=1, lora_rank=4,
        ),
    )
    handle = serve.run(build_openai_app(cfg), name="lora-app", route_prefix="/")
    # load an adapter on the replica dynamically
    llm_handle = serve.get_deployment_handle(f"llm:{cfg.served_name}")
    from ray_tpu.models.llama import LlamaConfig

    L, e, r = 2, 64, 4  # tiny config dims
    tiny = LlamaConfig.tiny(max_seq_len=64)
    rng = np.random.default_rng(2)
    adapter = {
        "wq_a": rng.normal(scale=0.5, size=(tiny.n_layers, tiny.d_model, 4)).astype(np.float32),
        "wq_b": rng.normal(scale=0.5, size=(tiny.n_layers, 4, tiny.n_heads, tiny.head_dim)).astype(np.float32),
        "wv_a": rng.normal(scale=0.5, size=(tiny.n_layers, tiny.d_model, 4)).astype(np.float32),
        "wv_b": rng.normal(scale=0.5, size=(tiny.n_layers, 4, tiny.n_kv_heads, tiny.head_dim)).astype(np.float32),
    }
    assert llm_handle.broadcast("load_lora", "tuned", adapter) == [True]

    import json

    def post(model):
        body = json.dumps(
            {"model": model, "prompt": "abc", "max_tokens": 4}
        ).encode()
        return handle.remote(
            Request("POST", "/v1/completions", {}, {}, body)
        ).result(timeout_s=300)

    base = post(cfg.served_name)
    tuned = post(f"{cfg.served_name}:tuned")
    assert base["object"] == tuned["object"] == "text_completion"
    assert base["choices"][0]["text"] != tuned["choices"][0]["text"]
    missing = post("nope:tuned")
    assert missing["error"]["code"] == 404
    # valid base, unknown adapter -> OpenAI-style 404 (not a raw 500)
    bad_adapter = post(f"{cfg.served_name}:absent")
    assert bad_adapter["error"]["code"] == 404
    serve.shutdown()
