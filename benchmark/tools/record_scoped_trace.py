"""Record the small traces ``benchmark/tests/data`` keeps for the readers of
scopes and engine spans, and print how the trace is laid out so that it can
be looked at by hand. Run it in the one process that holds the chip:

    python3 benchmark/tools/record_scoped_trace.py <out_dir>

It runs the program's own train step (``make_train_step``: splash attention,
remat, fused loss) and the program's own engine (``JaxEngine``: chunked
prefill, batched decode, in-program sampling) at small widths, each under a
profiler session of its own, and writes ``<out_dir>/train.xplane.pb``,
``<out_dir>/serve.xplane.pb`` (each cut to the device's plane and the host
lines that hold ``engine.*`` or ``bench.*`` spans: a third of the file),
``<out_dir>/serve.stats.json`` (the engine's ``get_stats()`` at the end) and
``<out_dir>/layout.txt`` (planes, lines, and every stat of some events of each
line, as ``jax.profiler.ProfileData`` shows them: the ``op_name`` is not among
them, it is a stat of the event's metadata, see ``benchmark/scopes.py``)."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def layout(path: str, out) -> None:
    from jax.profiler import ProfileData

    print("XPLANE", path, os.path.getsize(path), "bytes", file=out)
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines), "lines", file=out)
        for line in lines[:60]:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events), "events", file=out)
            seen = set()
            for ev in events:
                key = ev.name.split(" = ", 1)[0]
                if key in seen or len(seen) >= 40:
                    continue
                seen.add(key)
                stats = {str(k): str(v)[:300] for k, v in ev.stats}
                print("    EV", repr(ev.name[:120]), ev.duration_ns, stats, file=out)


def cut(src: str, dst: str) -> None:
    """Copy a trace without the host threads no reader looks at. Fields this
    benchmark's cut of the format does not declare pass through unchanged."""
    from benchmark import scopes

    space = scopes._xspace_class()()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        meta = plane.event_metadata
        kept = [
            line.SerializeToString() for line in plane.lines
            if any(meta[e.metadata_id].name.startswith(("engine.", "bench."))
                   for e in line.events)
        ]
        del plane.lines[:]
        for blob in kept:
            plane.lines.add().ParseFromString(blob)
        used = {e.metadata_id for line in plane.lines for e in line.events}
        for key in [k for k in meta if k not in used]:
            del meta[key]
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


def record_train(out_dir: str, on_tpu: bool) -> str:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    from benchmark import trace

    cfg = LlamaConfig(
        vocab_size=2048, d_model=512, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=1024,
        max_seq_len=256, attention="splash" if on_tpu else "full", remat=True, fused_ce=True,
    )
    optimizer = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-4, b1=0.9, b2=0.95))
    init_fn, step_fn = make_train_step(cfg, build_mesh(MeshSpec()), optimizer=optimizer)
    state = init_fn(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 257), jnp.int32)}
    state, m = step_fn(state, batch)
    float(m["loss"])
    d = os.path.join(out_dir, "train_trace")
    trace.start(d)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(3):
            state, m = step_fn(state, batch)
            float(m["loss"])
    jax.profiler.stop_trace()
    return trace.find_xplane(d)


def record_serve(out_dir: str) -> tuple:
    import jax

    from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams

    from benchmark import trace

    engine = JaxEngine(LLMConfig(
        model=ModelConfig(
            model_id="tiny", tokenizer="byte", seed=0,
            model_kwargs=dict(d_model=512, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=1024,
                              vocab_size=2048),
        ),
        engine=EngineConfig(
            max_num_seqs=4, max_seq_len=256, prefill_buckets=(32, 64, 128, 256), prefill_chunk=64,
            enable_prefix_caching=False,
        ),
    ))
    params = SamplingParams(max_tokens=6, ignore_eos=True)
    prompts = ["a" * 20, "b" * 100, "c" * 40, "d" * 150, "e" * 30, "f" * 70]
    for p in prompts[:2] + prompts[3:4]:  # every program the window uses
        engine.generate(p, sampling_params=params)
    d = os.path.join(out_dir, "serve_trace")
    trace.start(d)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        reqs = [engine.submit(p, sampling_params=params) for p in prompts]
        for r in reqs:
            engine._await_done(r)
        time.sleep(0.01)
    jax.profiler.stop_trace()
    stats = engine.get_stats()
    engine.shutdown()
    return trace.find_xplane(d), stats


def main(out_dir: str) -> None:
    import jax

    os.makedirs(out_dir, exist_ok=True)
    on_tpu = jax.default_backend() == "tpu"
    train_pb = record_train(out_dir, on_tpu)
    serve_pb, stats = record_serve(out_dir)
    cut(train_pb, os.path.join(out_dir, "train.xplane.pb"))
    cut(serve_pb, os.path.join(out_dir, "serve.xplane.pb"))
    with open(os.path.join(out_dir, "serve.stats.json"), "w") as f:
        json.dump(stats, f, default=float)
    with open(os.path.join(out_dir, "layout.txt"), "w") as f:
        layout(train_pb, f)
        layout(serve_pb, f)
    for name in ("train.xplane.pb", "serve.xplane.pb", "serve.stats.json", "layout.txt"):
        print(name, os.path.getsize(os.path.join(out_dir, name)), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
