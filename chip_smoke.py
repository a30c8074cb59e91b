#!/usr/bin/env python3
"""Bring-up smoke: the trainer and the LLM server on the chip, through the
entry points a user calls. The quickest proof that the system still starts
there.

    python chip_smoke.py              one chip: a train phase, then a serve phase
    python chip_smoke.py --chips 4    four chips: FSDP over the host's mesh
                                      against one chip, and no other phase
    python chip_smoke.py --rehearse   the same control flow at a tiny size on
                                      the CPU (add --chips 4 for four virtual
                                      devices); never prints the chip line

This process is the driver: it calls ``ray_tpu.init(mode="process")`` and
never starts a JAX backend, because a chip belongs to one process at a time.
Everything that touches the device runs in a worker that was granted ``TPU``,
and what is printed about the device is what those workers reported.

Each phase prints one JSON line. The last line of a passing chip run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Anything else (no chip, a phase that fails or times out, a ``TPU`` worker on
another platform) names the reason on a line of its own and exits nonzero.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
import urllib.request

# Each side of the four-chip comparison reduces in its own order and bf16
# keeps 8 bits of mantissa (2**-8 = 0.4%): 1% covers a few roundings.
LOSS_RTOL = 1e-2
LEARNING_RATE = 1e-4

TRAIN_TIMEOUT_S = 480.0
SERVE_TIMEOUT_S = 600.0
SHUTDOWN_TIMEOUT_S = 60.0


def log(**line) -> None:
    print(json.dumps(line), flush=True)


class SmokeFailure(Exception):
    pass


def require(cond: bool, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


def run_phase(name: str, fn, timeout_s: float):
    """Run one phase with a deadline. The waits inside have their own
    timeouts; this bounds what has none (``JaxTrainer.fit``)."""
    box: dict = {}

    def target():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — reported by the caller
            box["error"] = e

    t = threading.Thread(target=target, daemon=True, name=f"smoke-{name}")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise SmokeFailure(f"{name} phase timed out after {timeout_s:.0f}s")
    if "error" in box:
        e = box["error"]
        if isinstance(e, SmokeFailure):
            raise e
        raise SmokeFailure(f"{name} phase failed: {type(e).__name__}: {e}") from e
    return box["value"]


def pid_alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def wait_pid_gone(pid: int, timeout_s: float) -> float:
    t0 = time.monotonic()
    while pid_alive(pid):
        require(
            time.monotonic() - t0 < timeout_s,
            f"worker pid {pid} still alive {timeout_s:.0f}s after its phase ended",
        )
        time.sleep(0.1)
    return time.monotonic() - t0


# --------------------------------------------------------------------- train
#
# The loops run in the trainer's worker process. They are self-contained
# (pickled by value from __main__) and report only Python values: a driver
# that fetched a jax.Array would start a backend of its own.


def _train_setup(config):
    """Shared by both loops: model config, seeded batch, optimizer."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import LlamaConfig

    kw = dict(
        n_layers=config["layers"],
        max_seq_len=config["seq"],
        attention=config["attention"],
        remat=True,
    )
    if config["preset"] == "llama3.2-3b":
        cfg = LlamaConfig.llama32_3b(**kw)
    else:
        cfg = LlamaConfig.tiny(**kw)
    rng = np.random.default_rng(config["seed"])
    tokens = rng.integers(
        0, cfg.vocab_size, (config["batch"], config["seq"] + 1), dtype=np.int32
    )
    # default_optimizer warms up from a learning rate of 0 over 100 steps;
    # this one moves the loss within the smoke's few steps. Adam's first
    # steps move every weight by the learning rate: 1e-3 is a third of the
    # tied embedding's scale (vocab**-0.5) and left the loss flat on the chip
    optimizer = optax.chain(
        optax.clip_by_global_norm(1.0), optax.adamw(LEARNING_RATE, b1=0.9, b2=0.95)
    )
    return cfg, {"tokens": jnp.asarray(tokens)}, optimizer


def _timed_steps(compiled, state, batch, n_steps, report=None):
    """``n_steps`` steps, each timed around ``block_until_ready``; a value
    fetch right after says whether that really waited for the device."""
    import jax

    losses, step_s, fetch_after_block_s = [], [], []
    for i in range(n_steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready(metrics["loss"])
        t1 = time.perf_counter()
        loss = float(metrics["loss"])
        t2 = time.perf_counter()
        losses.append(loss)
        step_s.append(t1 - t0)
        fetch_after_block_s.append(t2 - t1)
        if report is not None:
            report(
                {"step": i, "loss": loss, "step_s": t1 - t0,
                 "grad_norm": float(metrics["grad_norm"])}
            )
    return state, losses, step_s, fetch_after_block_s


def train_loop(config):
    import jax

    import ray_tpu.train as train
    from ray_tpu._private import jax_cache
    from ray_tpu.models.training import make_train_step
    from ray_tpu.ops._common import interpret
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.tpu.accelerator import device_report

    cfg, batch, optimizer = _train_setup(config)
    cache_before = jax_cache.entry_count()
    mesh = build_mesh(MeshSpec(dp=1))
    init_fn, step_fn = make_train_step(cfg, mesh, optimizer=optimizer)
    t0 = time.perf_counter()
    state = init_fn(jax.random.PRNGKey(config["seed"]))
    jax.block_until_ready(state.params)
    init_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = step_fn.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()

    # warm-up on the same batch: its loss is the one the last must beat
    state, warm_losses, warm_s, _ = _timed_steps(
        compiled, state, batch, config["warmup"]
    )
    state, losses, step_s, fetch_after_block_s = _timed_steps(
        compiled, state, batch, config["steps"], report=train.report
    )
    # once around a value fetch alone, for comparison with the above
    t0 = time.perf_counter()
    state, metrics = compiled(state, batch)
    last_loss = float(metrics["loss"])
    step_float_s = time.perf_counter() - t0

    tokens_per_step = config["batch"] * config["seq"]
    train.report(
        {
            "summary": {
                "phase": "train",
                "model": config["preset"],
                "depth": cfg.n_layers,
                "params": cfg.num_params(),
                "attention": cfg.attention,
                "batch": [config["batch"], config["seq"]],
                **device_report(),
                "jax_platforms_env": os.environ.get("JAX_PLATFORMS"),
                "interpret_mode": interpret(),
                "init_s": init_s,
                "compile_s": compile_s,
                "first_step_s": warm_s[0],
                "step_s_block_until_ready": step_s,
                "fetch_after_block_s": fetch_after_block_s,
                "step_s_float_fetch": step_float_s,
                "tokens_per_s": tokens_per_step / sorted(step_s)[len(step_s) // 2],
                "losses": warm_losses + losses + [last_loss],
                "argument_bytes": mem.argument_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "splash_kernel_in_step": "tpu_custom_call" in compiled.as_text(),
                "compile_cache_dir": jax_cache.cache_dir(),
                "compile_cache_entries": [cache_before, jax_cache.entry_count()],
            }
        }
    )


def fsdp_loop(config):
    """Four chips in one process: FSDP over the host's mesh, then the same
    seed and batch on the first chip alone."""
    import gc

    import jax

    import ray_tpu.train as train
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.tpu.accelerator import device_report

    cfg, batch, optimizer = _train_setup(config)
    n = config["chips"]
    sides = {}
    # the one-chip side first: with dense attention it needs most of a chip
    # (14.9 GB by the compiler's account at 8 layers), so it gets a clean one
    for side, mesh in (
        ("one_chip", build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])),
        ("fsdp", build_mesh(MeshSpec(fsdp=n))),
    ):
        init_fn, step_fn = make_train_step(cfg, mesh, optimizer=optimizer)
        state = init_fn(jax.random.PRNGKey(config["seed"]))
        jax.block_until_ready(state.params)
        t0 = time.perf_counter()
        compiled = step_fn.lower(state, batch).compile()
        compile_s = time.perf_counter() - t0
        out = {"compile_s": compile_s}
        if side == "fsdp":
            leaves = jax.tree.leaves(state.params)
            out["param_shard_devices"] = sorted(
                {s.device.id for leaf in leaves for s in leaf.addressable_shards}
            )
            out["sharded_leaves"] = sum(
                leaf.addressable_shards[0].data.shape != leaf.shape
                for leaf in leaves
            )
            out["n_leaves"] = len(leaves)
            out["bytes_in_use_with_state"] = device_report()["bytes_in_use"]
        state, losses, step_s, _ = _timed_steps(
            compiled, state, batch, config["steps"]
        )
        out.update(losses=losses, step_s=step_s)
        sides[side] = out
        del state, compiled
        gc.collect()
    train.report(
        {
            "summary": {
                "phase": "fsdp4",
                "model": config["preset"],
                "depth": cfg.n_layers,
                "params": cfg.num_params(),
                "attention": cfg.attention,
                "batch": [config["batch"], config["seq"]],
                **device_report(),
                **sides,
            }
        }
    )


def fit(loop, config, tpu_chips: int):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    if tpu_chips:
        scaling = ScalingConfig(
            num_workers=1, use_tpu=True, resources_per_worker={"TPU": tpu_chips}
        )
    else:  # rehearsal: a CPU worker
        scaling = ScalingConfig(num_workers=1)
    result = JaxTrainer(
        loop,
        train_loop_config=config,
        scaling_config=scaling,
        run_config=RunConfig(
            name=f"chip-smoke-{config['phase']}",
            storage_path=os.path.join(config["scratch"], "train"),
        ),
    ).fit()
    require(result.error is None, f"trainer reported: {result.error}")
    summary = (result.metrics or {}).get("summary")
    require(summary is not None, "the train loop ended without its summary")
    return summary, result.metrics_history


def check_worker_device(summary: dict, platform: str, count: int) -> None:
    """What the WORKER saw is what counts."""
    require(
        summary["platform"] == platform and summary["device_count"] == count,
        f"{summary['phase']} worker ran on {summary['device_count']} "
        f"{summary['platform']!r} device(s), expected {count} {platform!r}",
    )


def train_phase(args, scratch: str) -> dict:
    config = dict(
        phase="train", scratch=scratch, seed=args.seed, warmup=1, steps=10,
        **(
            dict(preset="tiny", layers=2, seq=64, batch=4, attention="full")
            if args.rehearse
            else dict(preset="llama3.2-3b", layers=args.layers, seq=2048,
                      batch=4, attention="splash")
        ),
    )
    summary, history = fit(train_loop, config, 0 if args.rehearse else 1)
    log(**summary)
    check_worker_device(summary, args.platform, 1)
    steps = [h for h in history if "step" in h]
    require(len(steps) >= 3, f"only {len(steps)} steps came through train.report")
    losses = summary["losses"]
    require(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    require(
        losses[-1] < losses[0],
        f"loss did not fall on a repeated batch: {losses}",
    )
    if not args.rehearse:
        require(not summary["interpret_mode"], "kernels in interpret mode on the chip")
        require(summary["splash_kernel_in_step"], "no splash kernel in the compiled step")
    return summary


def fsdp_phase(args, scratch: str) -> dict:
    config = dict(
        phase="fsdp4", scratch=scratch, seed=args.seed, steps=3, chips=4,
        attention="full",
        **(
            dict(preset="tiny", layers=2, seq=64, batch=4)
            if args.rehearse
            else dict(preset="llama3.2-3b", layers=args.layers, seq=2048, batch=4)
        ),
    )
    summary, _ = fit(fsdp_loop, config, 0 if args.rehearse else 4)
    log(**summary)
    check_worker_device(summary, args.platform, 4)
    a, b = summary["fsdp"]["losses"], summary["one_chip"]["losses"]
    require(all(math.isfinite(x) for x in a + b), f"non-finite loss: {a} {b}")
    worst = max(abs(x - y) / abs(y) for x, y in zip(a, b))
    log(phase="fsdp4_agreement", fsdp=a, one_chip=b, worst_rel=worst, rtol=LOSS_RTOL)
    require(worst <= LOSS_RTOL, f"fsdp and one-chip losses differ by {worst:.4f}")
    require(
        len(summary["fsdp"]["param_shard_devices"]) == 4
        and summary["fsdp"]["sharded_leaves"] > 0,
        f"parameters are not sharded over four devices: {summary['fsdp']}",
    )
    if not args.rehearse:  # the CPU backend reports no memory stats
        held = summary["fsdp"]["bytes_in_use_with_state"]
        require(all(b and b > 0 for b in held), f"a device holds nothing: {held}")
        # parameters and both moments, a quarter each: code that has only
        # run on one chip may leave a whole tree on the first
        require(
            max(held) <= 1.25 * min(held),
            f"the train state is not spread evenly over the chips: {held}",
        )
    return summary


# ----------------------------------------------------- one process per chip


def disjoint_actors_phase() -> None:
    """Two one-chip actors alive at once see one chip each, and not the
    same one. They load no model."""
    import ray_tpu

    @ray_tpu.remote(num_tpus=1)
    class Probe:
        def devices(self):
            import jax
            import jax.numpy as jnp

            x = jnp.ones((1024, 1024), jnp.bfloat16)
            return {
                "pid": os.getpid(),
                "matmul_ok": float((x @ x)[0, 0]) == 1024.0,
                "platform": jax.local_devices()[0].platform,
                "coords": [list(d.coords) for d in jax.local_devices()],
                "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            }

    probes = [Probe.remote() for _ in range(2)]
    try:
        seen = ray_tpu.get([p.devices.remote() for p in probes], timeout=180)
    finally:
        for p in probes:
            ray_tpu.kill(p)
    log(phase="disjoint_actors", actors=seen)
    for s in seen:
        require(
            s["platform"] == "tpu" and len(s["coords"]) == 1 and s["matmul_ok"],
            f"a one-chip actor saw {s}",
        )
    require(
        seen[0]["visible_chips"] != seen[1]["visible_chips"],
        f"two live actors were given the same chip: {seen}",
    )
    for s in seen:
        wait_pid_gone(s["pid"], 60.0)


# --------------------------------------------------------------------- serve


def _post(url: str, body: dict) -> urllib.request.Request:
    return urllib.request.Request(
        url, data=json.dumps(body).encode(), headers={"Content-Type": "application/json"}
    )


def http_json(url: str, body: dict, timeout_s: float) -> tuple[int, dict, float]:
    t0 = time.perf_counter()
    with urllib.request.urlopen(_post(url, body), timeout=timeout_s) as resp:
        payload = json.loads(resp.read())
        return resp.status, payload, time.perf_counter() - t0


def http_stream(url: str, body: dict, timeout_s: float) -> dict:
    t0 = time.perf_counter()
    text, chunks, first_s, done, finish = "", 0, None, False, None
    with urllib.request.urlopen(_post(url, body), timeout=timeout_s) as resp:
        status, ctype = resp.status, resp.headers.get("Content-Type", "")
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                done = True
                break
            choice = json.loads(data)["choices"][0]
            if "content" in choice.get("delta", {}):  # one chunk per token
                if first_s is None:
                    first_s = time.perf_counter() - t0
                text += choice["delta"]["content"]
                chunks += 1
            finish = choice.get("finish_reason") or finish
    return {
        "status": status, "content_type": ctype, "text": text, "chunks": chunks,
        "first_chunk_s": first_s, "total_s": time.perf_counter() - t0,
        "done": done, "finish_reason": finish,
    }


def check_completion(status: int, payload: dict, max_tokens: int) -> dict:
    """HTTP 200, a completion that is not empty and a usage that adds up.
    Emptiness is judged in tokens: the byte tokenizer renders only ids below
    256, and random weights over a 128256-wide vocabulary rarely pick one,
    so the text of a real completion is mostly the empty string."""
    require(status == 200 and "choices" in payload, f"HTTP {status}: {payload}")
    choice = payload["choices"][0]
    text = choice["message"]["content"]
    usage = payload["usage"]
    require(
        isinstance(text, str) and choice["finish_reason"] in ("length", "stop"),
        f"malformed completion: {payload}",
    )
    require(
        usage["prompt_tokens"] > 0
        and 0 < usage["completion_tokens"] <= max_tokens
        and usage["total_tokens"]
        == usage["prompt_tokens"] + usage["completion_tokens"],
        f"usage does not add up: {usage}",
    )
    return {"text": text, "finish_reason": choice["finish_reason"], **usage}


def serve_phase(args, train_pid) -> dict:
    from ray_tpu import serve

    try:
        return _serve_and_query(args, train_pid)
    finally:
        serve.shutdown()  # replicas and proxy go, whatever happened


def _serve_and_query(args, train_pid) -> dict:
    from ray_tpu import serve
    from ray_tpu._private import jax_cache
    from ray_tpu.llm import EngineConfig, LLMConfig, ModelConfig, build_openai_app

    model_id = "tiny" if args.rehearse else "llama3.2-3b"
    cache_before = jax_cache.entry_count()
    llm_config = LLMConfig(
        model=ModelConfig(model_id=model_id, tokenizer="byte", seed=args.seed),
        engine=EngineConfig(
            max_num_seqs=4 if args.rehearse else 16,
            max_seq_len=256 if args.rehearse else 1024,
        ),
        ray_actor_options=None if args.rehearse else {"resources": {"TPU": 1}},
    )
    train_worker_alive_at_start = pid_alive(train_pid)
    t0 = time.perf_counter()
    serve.run(build_openai_app(llm_config), name="llm")
    _, port = serve.start_proxy(port=0)
    deployment = f"llm:{model_id}"
    while True:  # serve.run waits for the router only
        d = serve.status()["applications"]["llm"]["deployments"].get(deployment, {})
        if d.get("replicas", 0) >= 1 and d.get("starting", 1) == 0:
            break
        require(
            time.perf_counter() - t0 < SERVE_TIMEOUT_S / 2,
            f"{deployment} has no healthy replica: {d} (a replica whose worker "
            "was granted TPU and found no chip dies in __init__; see its log lines)",
        )
        time.sleep(0.5)
    startup_s = time.perf_counter() - t0
    # the chip went from one process to the next, never to two at once
    require(not pid_alive(train_pid), f"train worker {train_pid} outlived its phase")

    url = f"http://127.0.0.1:{port}/v1/chat/completions"
    max_tokens = 16

    def body(content: str, **kw) -> dict:
        return {
            "model": model_id, "max_tokens": max_tokens, "temperature": 0.0,
            "messages": [{"role": "user", "content": content}], **kw,
        }

    # the first request compiles a prefill bucket and the decode program
    status, payload, warm_s = http_json(url, body("warm up the engine"), 420)
    check_completion(status, payload, max_tokens)

    greedy = []
    for _ in range(2):  # the same greedy request twice
        status, payload, dt = http_json(url, body("say the same thing twice"), 120)
        greedy.append({**check_completion(status, payload, max_tokens), "s": dt})

    concurrent: list = [None, None]

    def one(i: int):
        try:
            status, payload, dt = http_json(url, body(f"concurrent request {i}"), 120)
            concurrent[i] = {**check_completion(status, payload, max_tokens), "s": dt}
        except Exception as e:  # noqa: BLE001 — judged below
            concurrent[i] = {"error": f"{type(e).__name__}: {e}"}

    t1 = time.perf_counter()
    threads = [threading.Thread(target=one, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(150)
    concurrent_s = time.perf_counter() - t1
    require(
        all(c is not None and "error" not in c for c in concurrent),
        f"a concurrent request failed: {concurrent}",
    )

    streamed = http_stream(url, body("stream this answer", stream=True), 120)
    require(
        streamed["status"] == 200
        and "text/event-stream" in streamed["content_type"]
        and streamed["done"]
        and 0 < streamed["chunks"] <= max_tokens
        and streamed["finish_reason"] in ("length", "stop"),
        f"streamed request: {streamed}",
    )

    stats = serve.get_deployment_handle(deployment, "llm").stats.remote().result(
        timeout_s=60
    )
    summary = {
        "phase": "serve",
        "model": model_id,
        "depth": stats["model"]["n_layers"],
        "params": stats["model"]["num_params"],
        **stats["device"],
        "max_num_seqs": stats["max_num_seqs"],
        "train_worker_alive_when_serve_began": train_worker_alive_at_start,
        "startup_s": startup_s,
        "first_request_s": warm_s,
        # programs the later requests compile (a prefix hit's suffix bucket)
        # show in request_s; the fastest request is the one with none
        "compile_s": warm_s - min(g["s"] for g in greedy + concurrent),
        "request_s": [g["s"] for g in greedy] + [c["s"] for c in concurrent],
        "concurrent_pair_s": concurrent_s,
        "tokens_per_s_concurrent_pair": sum(
            c["completion_tokens"] for c in concurrent
        ) / concurrent_s,
        "stream_first_chunk_s": streamed["first_chunk_s"],
        "stream_total_s": streamed["total_s"],
        "stream_chunks": streamed["chunks"],
        "http_200": 6,
        # the API returns text and counts, not ids: that is what is compared
        "greedy_repeat_matched": all(
            greedy[0][k] == greedy[1][k]
            for k in ("text", "completion_tokens", "finish_reason")
        ),
        "greedy_repeat_text_bytes": [len(g["text"].encode()) for g in greedy],
        "prefix_cache_hits": stats["prefix_cache_hits"],
        "compile_cache_dir": jax_cache.cache_dir(),
        "compile_cache_entries": [cache_before, jax_cache.entry_count()],
    }
    log(**summary)
    check_worker_device(summary, args.platform, 1)
    return summary


# ---------------------------------------------------------------------- main


def native_store_libs() -> list[str]:
    import glob

    from ray_tpu._native import plasma

    return glob.glob(
        os.path.join(os.path.dirname(plasma.__file__), "libplasma_store-*.so")
    )


def store_line(had_library: bool) -> None:
    """Which object store the head runs, and where its library came from
    (``ray_tpu/_native/build.py`` compiles it on demand, and the head falls
    back to the Python store without a word when it cannot)."""
    from ray_tpu._private import worker

    log(
        phase="object_store",
        store=type(worker.global_worker().controller.plasma).__name__,
        library=(
            "found" if had_library
            else "built by this run" if native_store_libs() else "none"
        ),
    )


def driver_jax_line() -> None:
    """The driver must not have started a backend: it would own the chip."""
    backends = []
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        backends = sorted(xla_bridge._backends)
    log(phase="driver", jax_imported="jax" in sys.modules, jax_backends=backends)
    require(not backends, f"the driver started JAX backends {backends}")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny size on the CPU; never prints the chip line")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--layers", type=int, default=8,
                        help="depth of the train model (published: 28)")
    args = parser.parse_args()
    args.platform = "cpu" if args.rehearse else "tpu"

    try:
        import ray_tpu
        from ray_tpu.tpu.accelerator import TPUAcceleratorManager
    except ImportError as e:
        print(f"chip_smoke: the ray_tpu package is not beside this script: {e}",
              file=sys.stderr)
        return 2

    if args.rehearse:
        # workers inherit the environment; this process never starts JAX
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}"
        )
        num_tpus = 0
    else:
        num_tpus = TPUAcceleratorManager.get_current_node_num_accelerators()
        if num_tpus < args.chips:
            log(ok=False, reason=f"no chip found: this host exposes {num_tpus} "
                f"TPU device node(s), the run needs {args.chips}")
            return 1

    scratch = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".scratch", "chip_smoke"
    )
    had_library = bool(native_store_libs())
    ray_tpu.init(mode="process", num_cpus=max(4, os.cpu_count() or 1),
                 num_tpus=num_tpus)
    summaries = []
    failures = []

    def phase(name: str, fn, timeout_s: float):
        """A failed phase is named on a line of its own; the next one still
        runs (its finding is worth having), and the run fails."""
        try:
            summaries.append(run_phase(name, fn, timeout_s))
            return summaries[-1]
        except SmokeFailure as e:
            failures.append(f"{name}: {e}")
            log(ok=False, phase=name, reason=str(e))
            return None

    try:
        store_line(had_library)
        if args.chips == 4:
            if not args.rehearse:
                phase("disjoint_actors", disjoint_actors_phase, 240.0)
            phase("fsdp4", lambda: fsdp_phase(args, scratch), TRAIN_TIMEOUT_S)
        else:
            train = phase("train", lambda: train_phase(args, scratch), TRAIN_TIMEOUT_S)
            phase(
                "serve",
                lambda: serve_phase(args, (train or {}).get("pid")),
                SERVE_TIMEOUT_S,
            )
        phase("driver", driver_jax_line, 10.0)
    finally:
        stopper = threading.Thread(target=ray_tpu.shutdown, daemon=True)
        stopper.start()
        stopper.join(SHUTDOWN_TIMEOUT_S)
    # every process that touched the device is gone: the chip is free for
    # whatever this machine runs next
    leftover = [s["pid"] for s in summaries if s and "pid" in s and pid_alive(s["pid"])]
    if stopper.is_alive() or leftover:
        failures.append(f"shutdown left processes behind: {leftover or 'unknown'}")
    if failures:
        log(ok=False, failed=failures)
        sys.stdout.flush()
        os._exit(1)  # a phase that timed out may still hold a thread
    summaries = [s for s in summaries if s and "device_kind" in s]
    if args.rehearse:
        log(rehearsal=True, phases=[s["phase"] for s in summaries])
        return 0
    kinds = {s["device_kind"] for s in summaries}
    require(len(kinds) == 1, f"phases disagree on the device: {kinds}")
    print(json.dumps({
        "ok": True,
        "device": {"platform": "tpu", "kind": kinds.pop(), "count": args.chips},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
