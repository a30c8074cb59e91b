"""OpenAI-compatible LLM serving.

Run: python examples/serve_openai_llm.py
As written the replica runs on the CPU: a replica only gets a chip if the
deployment asks, e.g. LLMConfig(..., ray_actor_options={"num_tpus": 1}).
Then: curl -s localhost:8000/v1/chat/completions -d \
  '{"model":"tiny","messages":[{"role":"user","content":"hi"}],"max_tokens":16}'
"""

import time

import ray_tpu
from ray_tpu import serve
from ray_tpu.llm import EngineConfig, LLMConfig, ModelConfig, build_openai_app

if __name__ == "__main__":
    ray_tpu.init(mode="process")
    cfg = LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte"),
        engine=EngineConfig(max_num_seqs=8, max_seq_len=512),
        name="tiny",
        num_replicas=1,
    )
    serve.run(build_openai_app(cfg), name="llm")
    _, port = serve.start_proxy(port=8000)
    print(f"serving OpenAI API on http://127.0.0.1:{port}/v1 — ctrl-c to stop")
    try:
        while True:
            time.sleep(5)
            print("engine stats:", serve.status()["applications"]["llm"])
    except KeyboardInterrupt:
        serve.shutdown()
        ray_tpu.shutdown()
