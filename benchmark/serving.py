"""What the two serving kinds share: the replica class with the benchmark's
hooks, the application (the program's own router and proxy in front of it),
the HTTP client, and the checks that decide ``correct``.

``BenchLLMServer`` only adds methods to ``LLMServer``. They exist because the
replica is the one process that may touch the chip: weights from the seed, the
comparison with the reference, the trace, the compile count and the scheduler
samples all have to happen inside it. PERF.md lists them as what a tracing PR
should move into ``LLMServer`` itself, so that ``build_openai_app`` is what
runs."""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.error
import urllib.request

from ray_tpu.llm.server import LLMServer

from benchmark import common, families
from benchmark.common import log, require

APP = "llm"
ENGINE_STAGES = ("_pull_waiting", "_advance_admissions", "_launch_decodes", "_drain")


class BenchLLMServer(LLMServer):
    def bench_load_weights(self, seed: int, config: dict) -> dict:
        """Swap the engine's parameters for the benchmark's, made on the
        device from the seed. The engine's programs take the parameters as an
        argument, so nothing recompiles. The old ones go first: two copies of
        7.5 GB do not fit."""
        import gc

        import jax

        t = time.perf_counter()
        old = self.engine.params
        shardings = {k: v.sharding for k, v in old.items()}
        dtype = old["embed"].dtype
        shapes = {k: v.shape for k, v in old.items()}
        self.engine.params = None
        del old
        gc.collect()
        params = families.load(config).make_params(seed, config, dtype, shardings)
        jax.block_until_ready(params)
        require(
            {k: v.shape for k, v in params.items()} == shapes,
            "the benchmark's parameter tree is not the engine's",
        )
        self.engine.params = params
        return {"seconds": time.perf_counter() - t, "memory": self.bench_memory()}

    def bench_memory(self) -> dict:
        """Bytes in use now, and the peak so far, on this replica's chip."""
        import jax

        m = jax.local_devices()[0].memory_stats() or {}
        return {"bytes_in_use": m.get("bytes_in_use"), "peak_bytes_in_use": m.get("peak_bytes_in_use")}

    def bench_check_reference(self, seed: int, config: dict, control=None) -> dict:
        """The probe prompts through the engine's own loop, programs and
        cache, and through ``models/llama.py prefill`` and ``decode_step``,
        against one pass of the reference (``benchmark/compare.py``). With
        ``control`` the program's side runs on weights cut to int8; they are
        then made anew, because the reference reads the weights as made."""
        import jax

        from benchmark import compare

        t = time.perf_counter()
        family = families.load(config)
        probe = config["run"]["probe"]
        rows = compare.probe_rows(seed, probe)
        if control == "int8":
            self.engine.params = family.int8_roundtrip(self.engine.params)
        got = compare.serve_program(self.engine, rows, probe)
        if control == "int8":
            self.bench_load_weights(seed, config)
        ref = family.Reference(config, jax.local_devices()[:1])
        errors = compare.serve_errors(got, ref, self.engine.params, rows, probe)
        return dict(errors, seconds=time.perf_counter() - t, memory=self.bench_memory())

    def bench_instrument(self) -> bool:
        """Host spans around the engine loop's four stages, written into the
        profiler's trace from here: the engine itself has none yet."""
        import jax

        engine = self.engine
        for stage in ENGINE_STAGES:
            inner = getattr(engine, stage)

            def wrapped(inner=inner, name="bench.engine" + stage):
                with jax.profiler.TraceAnnotation(name):
                    return inner()

            setattr(engine, stage, wrapped)
        return True

    def bench_window_open(self, trace_dir, sample_every_s: float) -> bool:
        """Start counting compilations and sampling the scheduler; with a
        ``trace_dir``, start the profiler too."""
        from benchmark import trace

        self._bench_compiles = trace.CompileCounter().__enter__()
        self._bench_samples = []
        self._bench_sampling = threading.Event()
        self._bench_trace_dir = trace_dir
        self._bench_memory_open = self.bench_memory()

        def sample():
            while not self._bench_sampling.wait(sample_every_s):
                s = self.engine.get_stats()
                # tokens whose keys and values a decode step has to read; the
                # engine's stats do not count them yet, so its slots are read
                live = sum(
                    len(r.prompt_token_ids) + len(r.out_tokens)
                    for pool in self.engine._pools for r in list(pool.slots) if r is not None
                )
                self._bench_samples.append({
                    "t": time.time(), "active_slots": s["active_slots"],
                    "admitting": s["admitting"], "waiting": s["waiting"],
                    "max_num_seqs": s["max_num_seqs"], "live_tokens": live,
                })

        self._bench_sampler = threading.Thread(target=sample, daemon=True, name="bench-sampler")
        self._bench_sampler.start()
        if trace_dir:
            import jax

            trace.start(trace_dir)
            self._bench_span = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
            self._bench_span.__enter__()
        return True

    def bench_trace_stop(self) -> bool:
        import jax

        self._bench_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return True

    def bench_window_close(self) -> dict:
        from benchmark import trace

        self._bench_sampling.set()
        self._bench_sampler.join(5)
        self._bench_compiles.__exit__(None, None, None)
        out = {
            "compiles_in_window": self._bench_compiles.count,
            "compiled_in_window": self._bench_compiles.names[:20],
            "samples": self._bench_samples,
            "stats": self.engine.get_stats(),
            "memory": {"window_open": self._bench_memory_open, "window_close": self.bench_memory()},
        }
        if self._bench_trace_dir:
            out["trace"] = trace.reduce_dir(self._bench_trace_dir)
        return out


# ------------------------------------------------------------------ the app


def make_llm_config(config: dict, seed: int, rehearsal: bool):
    from ray_tpu.llm import EngineConfig, LLMConfig

    run = config["run"]
    return LLMConfig(
        model=families.load(config).served_model(config, seed % common.MODEL_SEED_MOD),
        engine=EngineConfig(dtype=run["dtype"], **run["engine"]),
        name=run["served_name"],
        ray_actor_options=None if rehearsal else {"resources": {"TPU": 1}},
    )


def build_app(config: dict, seed: int, rehearsal: bool):
    """``build_openai_app`` with the replica class swapped for the subclass:
    the deployment options are the ones ``build_llm_deployment`` passes, the
    router is the program's ``OpenAIRouter`` under the options
    ``build_openai_app`` gives it."""
    from ray_tpu import serve
    from ray_tpu.llm.openai_api import OpenAIRouter

    llm_config = make_llm_config(config, seed, rehearsal)
    replica = serve.deployment(
        BenchLLMServer,
        name=f"llm:{llm_config.served_name}",
        num_replicas=llm_config.num_replicas,
        max_ongoing_requests=llm_config.engine.max_num_seqs * 2,
        ray_actor_options=llm_config.ray_actor_options,
        autoscaling_config=llm_config.autoscaling_config,
        initial_health_grace_s=llm_config.compile_budget_s(),
    )
    router = serve.deployment(OpenAIRouter, name="openai-router", max_ongoing_requests=64)
    app = router.bind(**{llm_config.served_name: replica.bind(llm_config)})
    return app, f"llm:{llm_config.served_name}", llm_config.served_name


def wait_healthy(deployment: str, timeout_s: float) -> None:
    from ray_tpu import serve

    t0 = time.perf_counter()
    while True:  # serve.run waits for the router only
        d = serve.status()["applications"][APP]["deployments"].get(deployment, {})
        if d.get("replicas", 0) >= 1 and d.get("starting", 1) == 0:
            return
        require(
            time.perf_counter() - t0 < timeout_s,
            f"{deployment} has no healthy replica after {timeout_s:.0f}s: {d}",
        )
        time.sleep(0.25)


# --------------------------------------------------------------- the client


def _post(url: str, body: dict) -> urllib.request.Request:
    return urllib.request.Request(
        url, data=json.dumps(body).encode(), headers={"Content-Type": "application/json"}
    )


def completion_body(model: str, req: dict, traffic: dict, stream: bool) -> dict:
    return {
        "model": model, "prompt": req["prompt"], "max_tokens": req["max_tokens"],
        "temperature": traffic["temperature"], "stream": stream,
    }


def http_completion(url: str, body: dict, timeout_s: float) -> dict:
    """One unary completion. Never raises: a failure is a result."""
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(_post(url, body), timeout=timeout_s) as resp:
            payload = json.loads(resp.read())
            status = resp.status
    except Exception as e:  # noqa: BLE001 - a refused or failed request counts as failed
        return {"ok": False, "error": f"{type(e).__name__}: {e}", "t_end": time.perf_counter()}
    t_end = time.perf_counter()
    usage = payload.get("usage") or {}
    ok = status == 200 and "choices" in payload and 0 < usage.get("completion_tokens", 0) <= body["max_tokens"]
    return {
        "ok": ok, "t_start": t0, "t_end": t_end,
        "completion_tokens": usage.get("completion_tokens", 0),
        "prompt_tokens": usage.get("prompt_tokens", 0),
        "finish_reason": (payload.get("choices") or [{}])[0].get("finish_reason"),
        "text": (payload.get("choices") or [{}])[0].get("text"),
        "error": None if ok else json.dumps(payload)[:300],
    }


def http_stream(url: str, body: dict, timeout_s: float) -> dict:
    """One streamed completion; the arrival time of every token chunk."""
    t0 = time.perf_counter()
    chunk_t, finish, done = [], None, False
    try:
        with urllib.request.urlopen(_post(url, body), timeout=timeout_s) as resp:
            status = resp.status
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data:"):
                    continue
                data = line[5:].strip()
                if data == "[DONE]":
                    done = True
                    break
                payload = json.loads(data)
                if "choices" not in payload:
                    return {"ok": False, "error": data[:300], "t_start": t0,
                            "t_end": time.perf_counter(), "chunk_t": chunk_t}
                choice = payload["choices"][0]
                if choice.get("finish_reason") is None:
                    chunk_t.append(time.perf_counter())  # one chunk per token
                else:
                    finish = choice["finish_reason"]
    except Exception as e:  # noqa: BLE001
        return {"ok": False, "error": f"{type(e).__name__}: {e}", "t_start": t0,
                "t_end": time.perf_counter(), "chunk_t": chunk_t}
    ok = status == 200 and done and 0 < len(chunk_t) <= body["max_tokens"]
    return {
        "ok": ok, "t_start": t0, "t_end": time.perf_counter(), "chunk_t": chunk_t,
        "completion_tokens": len(chunk_t), "finish_reason": finish,
        "error": None if ok else f"status {status} done {done} chunks {len(chunk_t)}",
    }


# ----------------------------------------------------- set-up shared by kinds


class Served:
    """The running application and what the kinds need of it."""

    def __init__(self, ctx: dict):
        from ray_tpu import serve

        self.ctx = ctx
        config, args = ctx["config"], ctx["args"]
        self.config, self.traffic = config, ctx["traffic"]
        self.model_seed = args.seed % common.MODEL_SEED_MOD
        app, self.deployment, self.model = build_app(config, args.seed, ctx["rehearsal"])
        self.spans = {}
        t0 = time.time()
        serve.run(app, name=APP)
        _, port = serve.start_proxy(port=0)
        while port is None:
            # the proxy actor answers get_port before its server has bound
            # (seen once in some fifty runs on the chip): ask again
            require(time.time() - t0 < 60.0, "the HTTP proxy reported no port for 60 s")
            time.sleep(0.1)
            _, port = serve.start_proxy(port=0)
        wait_healthy(self.deployment, 900.0)
        self.spans["replica_start_s"] = time.time() - t0
        self.url = f"http://127.0.0.1:{port}/v1/completions"
        self.handle = serve.get_deployment_handle(self.deployment, APP)

    def call(self, method: str, *a, timeout_s: float = 600.0):
        return getattr(self.handle, method).remote(*a).result(timeout_s=timeout_s)

    def prepare(self) -> dict:
        """Weights from the seed, the comparison with the reference, the
        device check, the repeated greedy request and the warm-up. All of it
        is set-up. Returns the checks."""
        args, config, traffic = self.ctx["args"], self.config, self.traffic
        loaded = self.call("bench_load_weights", self.model_seed, config)
        compared = self.call("bench_check_reference", self.model_seed, config, args.control)
        stats = self.call("stats")
        dev = stats["device"]
        platform = "cpu" if self.ctx["rehearsal"] else "tpu"
        require(
            dev["platform"] == platform and dev["device_count"] == 1,
            f"replica ran on {dev['device_count']} {dev['platform']!r} device(s), "
            f"the cell needs 1 {platform!r}",
        )
        require(
            stats["max_num_seqs"] == config["run"]["engine"]["max_num_seqs"],
            f"engine has {stats['max_num_seqs']} slots",
        )
        # the same greedy request twice; short enough that no prefix-cache
        # path differs between the two
        import random

        from benchmark import traffic as gen

        probe = {"prompt": gen.prompt_text(random.Random(args.seed), 24), "max_tokens": 16}
        twice = [
            http_completion(self.url, completion_body(self.model, probe, traffic, False), 600)
            for _ in range(2)
        ]
        same = all(r["ok"] for r in twice) and all(
            twice[0][k] == twice[1][k] for k in ("text", "completion_tokens", "finish_reason")
        )
        t = time.perf_counter()
        for req in gen.warmup_requests(traffic, args.seed):
            for stream in (False, True):
                fn = http_stream if stream else http_completion
                r = fn(self.url, completion_body(self.model, req, traffic, stream), 900)
                require(r["ok"], f"warm-up request failed: {r['error']}")
        warm_s = time.perf_counter() - t
        decided = common.decide(compared, config["run"]["limits"])
        log(
            compared=decided,
            not_limited={k: v for k, v in compared.items() if k not in decided},
            greedy_repeat_same=same, control=args.control,
            weights_s=loaded["seconds"], reference_s=compared["seconds"], warmup_requests_s=warm_s,
            replica_start_s=self.spans["replica_start_s"],
            memory={"weights": loaded["memory"], "reference": compared["memory"],
                    "warm": self.call("bench_memory")},
        )
        self.device_report = dev
        return {"correct": all(c["ok"] for c in decided.values()) and same}

    def window_open(self):
        args, traffic = self.ctx["args"], self.traffic
        trace_dir = None
        if args.trace:
            trace_dir = os.path.join(self.ctx["out_dir"], "trace")
            self.call("bench_instrument")
        self.call("bench_window_open", trace_dir, traffic.get("sample_every_s", 0.25))
        if args.trace:
            seconds = min(args.seconds, traffic.get("trace_seconds", args.seconds))
            self._stopper = threading.Timer(seconds, lambda: self.call("bench_trace_stop"))
            self._stopper.daemon = True
            self._stopper.start()

    def window_close(self) -> dict:
        if self.ctx["args"].trace:
            self._stopper.join()
        closed = self.call("bench_window_close", timeout_s=900.0)
        self.device_report = closed["stats"]["device"]
        return closed


def summarize_requests(results: list[dict]) -> dict:
    done = [r for r in results if r.get("ok")]
    early = [r for r in done if r.get("finish_reason") == "stop"]
    return {
        "attempted": len(results), "failed": len(results) - len(done),
        "early_stop_share": len(early) / len(done) if done else 0.0,
        "errors": sorted({r["error"] for r in results if not r.get("ok")})[:5],
    }
