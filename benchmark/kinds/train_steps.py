"""Traffic kind ``train_steps``: a fixed batch, trained for the window.

The driver half stays off JAX and places ``worker_loop`` with ``JaxTrainer``
on a worker that was granted the cell's chips. The worker half is benchmark
code: it hands the program (``make_train_step``) the benchmark's weights and
batch, compares the first step with the plain reference, warms up, and times
whole steps with the traffic file's ``steps_in_flight`` queued ahead."""

from __future__ import annotations

import os
import time

from benchmark import common, families, stats
from benchmark.common import log, require

TOKEN_KEY = "tokens"
ADAM_B1, ADAM_B2 = 0.9, 0.95


def make_batch(seed: int, traffic: dict, vocab: int):
    """``sequences`` rows of ``seq_len`` + 1 tokens from the seed (inputs and
    their next-token labels), drawn once."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.integers(
        0, vocab, (traffic["sequences"], traffic["seq_len"] + 1), dtype=np.int32
    )


def timed_loop(step, state, batch, warmup: int, seconds: float, in_flight: int = 1,
               annotate=None):
    """Whole steps with ``in_flight`` steps queued behind the one awaited.

    The host dispatches ahead and awaits the oldest step's loss, as a
    production JAX loop runs between two log lines, so the device holds
    ``in_flight`` to ``in_flight`` + 1 steps of work whenever the host is
    away and a host pause shorter than that idles nothing. Inside the window
    nothing is allocated or reported: the awaited loss is the only value
    fetched, and completion times go into a Python list. The window opens
    when the last warm-up step completes. Returns a dict; the steps still in
    flight at the end are awaited but not counted."""
    from collections import deque
    from contextlib import nullcontext

    from benchmark.trace import WINDOW_SPAN

    span = annotate or (lambda name: nullcontext())
    warm_losses, losses, done, pending = [], [], [], deque()

    def dispatch():
        nonlocal state
        state, metrics = step(state, batch)
        pending.append(metrics["loss"])

    for _ in range(in_flight):
        dispatch()
    for _ in range(warmup - 1):
        dispatch()
        warm_losses.append(float(pending.popleft()))
    # the last warm-up step is the oldest in flight; queue one more timed
    # step behind it, and open the window when it completes
    dispatch()
    warm_losses.append(float(pending.popleft()))
    t0, t0_wall = time.perf_counter(), time.time()
    deadline = t0 + seconds
    with span(WINDOW_SPAN):
        while True:
            with span("bench.dispatch"):
                dispatch()
            with span("bench.wait_loss"):
                losses.append(float(pending.popleft()))
            now = time.perf_counter()
            done.append(now)
            if now >= deadline:
                break
    tail = [float(x) for x in pending]  # drain what is in flight, uncounted
    return dict(
        state=state, warm_losses=warm_losses, losses=losses + tail, t0=t0,
        t0_wall=t0_wall, done=done, deadline=deadline,
    )


def build(config: dict, traffic: dict):
    """The program's pieces for one train configuration: model settings, mesh,
    optimizer and the jitted step of ``make_train_step``."""
    import optax

    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    run = config["run"]
    cfg = families.load(config).train_config(config, traffic)
    mesh = build_mesh(MeshSpec(**run["mesh"]))
    optimizer = optax.chain(
        optax.clip_by_global_norm(run["grad_clip"]),
        optax.adamw(run["learning_rate"], b1=ADAM_B1, b2=ADAM_B2),
    )
    _, step_fn = make_train_step(cfg, mesh, optimizer=optimizer)
    return cfg, mesh, optimizer, step_fn


def make_state(params, optimizer, mesh):
    """A ``TrainState`` around the benchmark's parameters. Each Adam moment
    takes its parameter's sharding, as the program's own ``init_fn`` lays
    them out."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.models.training import TrainState

    replicated = NamedSharding(mesh, PartitionSpec())
    moment_shardings = optax.tree_utils.tree_map_params(
        optimizer, lambda _, p: p.sharding, jax.eval_shape(optimizer.init, params),
        params, transform_non_params=lambda _: replicated,
    )
    opt_state = jax.jit(optimizer.init, out_shardings=moment_shardings)(params)
    return TrainState(params, opt_state, jnp.zeros((), jnp.int32))


def make_params(seed: int, config: dict, cfg, mesh, control=None):
    family = families.load(config)
    params = family.make_params(
        seed % common.MODEL_SEED_MOD, config, cfg.dtype, family.param_shardings(cfg, mesh)
    )
    return family.int8_roundtrip(params) if control == "int8" else params


def first_moments(state) -> dict:
    """Adam's first moment in a ``TrainState`` of ``build``'s optimizer: a
    tree shaped like the parameters."""
    import optax

    return optax.tree_utils.tree_get(state.opt_state, "mu")


def worker_loop(payload: dict) -> None:
    """Runs in the trainer's worker, which holds the chips."""
    t_loop = time.time()
    import jax

    import ray_tpu.train as train
    from ray_tpu.models.training import batch_sharding
    from ray_tpu.tpu.accelerator import device_report

    from benchmark import compare, trace

    config, traffic, run = payload["config"], payload["traffic"], payload["config"]["run"]
    seed, seconds = payload["seed"], payload["seconds"]
    out = {"loop_entered_wall": t_loop, "device": device_report()}

    t = time.perf_counter()
    cfg, mesh, optimizer, step_fn = build(config, traffic)
    state = make_state(
        make_params(seed, config, cfg, mesh, payload.get("control")), optimizer, mesh
    )
    tokens = make_batch(seed, traffic, cfg.vocab_size)
    batch = {TOKEN_KEY: jax.device_put(tokens, batch_sharding(mesh))}
    jax.block_until_ready(state)
    out["init_s"] = time.perf_counter() - t

    t = time.perf_counter()
    compiled = step_fn.lower(state, batch).compile()
    out["compile_s"] = time.perf_counter() - t
    mem = compiled.memory_analysis()
    out["program_bytes"] = int(
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
    ) if mem is not None else 0
    text = compiled.as_text()
    out["splash_kernel_in_step"] = "tpu_custom_call" in text

    # -- the comparison with the plain reference, before the window ---------
    t = time.perf_counter()
    rows = len(jax.local_devices())
    if payload.get("control"):
        # the reference keeps the weights as made; only the program's are cut
        sound = make_params(seed, config, cfg, mesh)
    else:
        sound = state.params
    want = compare.train_reference(
        families.load(config).Reference(config, list(mesh.devices.flat)), sound, tokens, run, rows
    )
    del sound
    logits = compare.train_program_logits(state.params, tokens, cfg, mesh, run, rows)
    # the window's own compiled step; its output state carries the gradient
    first_state, first = compiled(state, batch)
    out["first_loss"], out["first_grad_norm"] = float(first["loss"]), float(first["grad_norm"])
    out["errors"] = compare.train_errors(
        first, first_moments(first_state), logits, want, run, ADAM_B1
    )
    out["ref_loss"], out["ref_grad_norm"] = want["loss"], want["grad_norm"]
    del logits, want
    out["reference_s"] = time.perf_counter() - t

    # -- warm-up and the timed window ----------------------------------------
    tracing = bool(payload["trace"])
    window = min(seconds, traffic.get("trace_seconds", seconds)) if tracing else seconds
    if tracing:
        trace.start(payload["trace_dir"])
    with trace.CompileCounter() as compiles:
        res = timed_loop(
            compiled, first_state, batch, traffic["warmup_steps"] - 1, window,
            traffic["steps_in_flight"],
            jax.profiler.TraceAnnotation if tracing else None,
        )
    if tracing:
        jax.profiler.stop_trace()
    del res["state"]
    out.update(res)
    out["compiles_in_window"] = compiles.count
    out["compiled_in_window"] = compiles.names[:20]
    out["warm_losses"] = [out["first_loss"]] + out["warm_losses"]
    out["device_after"] = device_report()
    if tracing:
        out["trace"] = trace.reduce_dir(payload["trace_dir"])
    train.report({"summary": out})


def run(ctx: dict) -> dict:
    """Driver half. Returns what ``run.py`` turns into the result line."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cell, config, traffic, args = ctx["cell"], ctx["config"], ctx["traffic"], ctx["args"]
    chips = cell["chips"]
    tokens_per_step = traffic["sequences"] * traffic["seq_len"]
    payload = dict(
        config=config, traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=args.trace, control=args.control,
        trace_dir=os.path.join(ctx["out_dir"], "trace"),
    )
    if ctx["rehearsal"]:
        scaling = ScalingConfig(num_workers=1)
    else:
        scaling = ScalingConfig(
            num_workers=1, use_tpu=True, resources_per_worker={"TPU": chips}
        )
    t_fit = time.time()
    result = JaxTrainer(
        worker_loop, train_loop_config=payload, scaling_config=scaling,
        run_config=RunConfig(
            name=f"bench-{cell['name']}",
            storage_path=os.path.join(ctx["out_dir"], "train"),
        ),
    ).fit()
    require(result.error is None, f"trainer reported: {result.error}")
    s = (result.metrics or {}).get("summary")
    require(s is not None, "the train loop ended without its summary")

    dev = s["device"]
    platform = "cpu" if ctx["rehearsal"] else "tpu"
    require(
        dev["platform"] == platform and dev["device_count"] == chips,
        f"worker ran on {dev['device_count']} {dev['platform']!r} device(s), "
        f"the cell needs {chips} {platform!r}",
    )

    rate, n, elapsed = stats.whole_step_rate(s["done"], s["t0"], tokens_per_step, s["deadline"])
    steps_ms = [1e3 * (b - a) for a, b in zip([s["t0"]] + s["done"][:-1], s["done"])]
    losses = s["losses"]
    compared = common.decide(s["errors"], config["run"]["limits"])
    loss_fell = losses[-1] < s["warm_losses"][0]
    correct = all(c["ok"] for c in compared.values()) and loss_fell
    log(
        compared=compared,
        not_limited={k: v for k, v in s["errors"].items() if k not in compared},
        loss={"first": s["warm_losses"][0], "last": losses[-1], "fell": loss_fell},
        reference={"loss": s["ref_loss"], "grad_norm": s["ref_grad_norm"]},
        program={"loss": s["first_loss"], "grad_norm": s["first_grad_norm"]},
        control=args.control,
    )
    log(
        steps=n, elapsed_s=elapsed, step_ms_median=sorted(steps_ms)[len(steps_ms) // 2],
        step_ms_max=max(steps_ms), step_ms_min=min(steps_ms),
        compiles_in_window=s["compiles_in_window"], compiled_in_window=s["compiled_in_window"], init_s=s["init_s"],
        compile_s=s["compile_s"], reference_s=s["reference_s"],
        splash_kernel_in_step=s["splash_kernel_in_step"],
        program_bytes=s["program_bytes"],
    )
    log(step_done_s=[t - s["t0"] for t in s["done"]])
    return dict(
        correct=correct, attempted=n, failed=0,
        e2e={"train_tok_s": rate, "setup_s": s["t0_wall"] - ctx["t_start_wall"]},
        device=common.device_entry(
            s["device_after"], common.peak_bytes(s["device_after"], s["program_bytes"])
        ),
        spans={"worker_start_s": s["loop_entered_wall"] - t_fit},
        trace=s.get("trace"), extra={"tokens_per_step": tokens_per_step, "summary": s},
    )
