#!/usr/bin/env python3
"""Where a ``JaxEngine`` constructor's seconds go, at a serving cell's own
shapes: JAX's own duration events (``jax.monitoring``) summed by compiled
program while the constructor runs, beside the constructor's phases and
``warm_programs_by_program_s`` as ``get_stats()["init"]`` reports them.

- ``trace_s``: ``/jax/core/compile/jaxpr_trace_duration``;
- ``lower_s``: ``.../jaxpr_to_mlir_module_duration``;
- ``compile_or_fetch_s``: ``.../backend_compile_duration`` (the compile, or
  the fetch from the persistent cache, whichever it was), of which
  ``fetch_s`` is ``/jax/compilation_cache/cache_retrieval_time_sec``.

A fetch names no program: it is booked to the module lowered last. A ``jit``
traced inside another (``wrapped``, ``gmm``) fires its own trace event, and its
seconds are in the outer program's again: read ``trace_s`` off the engine's
own programs (``chunk_mid``, ``chunk_final``, ``decode_fn``), not off the sum. Run the
tool twice in one call for a checkout's first start and a warm one:

    python3 benchmark/tools/warm_split.py --config solar-open2-250b-serve-l4-ep8
"""

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_or_fetch_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "fetch_s",
}


def main():
    import jax
    import jax.monitoring

    from ray_tpu._private import jax_cache
    from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig
    from ray_tpu.models.llama import serving_layouts

    from benchmark import common, families

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--top", type=int, default=12, help="programs listed, by seconds")
    args = parser.parse_args()
    jax_cache.configure()
    entries_before = jax_cache.entry_count()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    family, run = families.load(config), config["run"]
    by_program: dict = {}
    by_phase: dict = {}
    last, init_phase = ["?"], ["outside"]
    run_phase = JaxEngine._init_phase

    def named_phase(self, phase):  # which of the constructor's phases an event falls in
        init_phase[0] = phase.__name__.lstrip("_")
        try:
            run_phase(self, phase)
        finally:
            init_phase[0] = "outside"

    JaxEngine._init_phase = named_phase

    def listen(event, seconds, **kw):
        phase = EVENTS.get(event)
        if phase is None:
            return
        name = kw.get("fun_name")
        if name is None:
            name = last[0]
        else:
            name = last[0] = re.sub(r"^jit[_(]|\)$", "", str(name))
        row = by_program.setdefault(name, dict.fromkeys(EVENTS.values(), 0.0) | {"n": 0})
        row[phase] += seconds
        row["n"] += phase == "compile_or_fetch_s"
        by_phase.setdefault(init_phase[0], dict.fromkeys(EVENTS.values(), 0.0))[phase] += seconds

    jax.monitoring.register_event_duration_secs_listener(listen)
    t = time.perf_counter()
    eng = JaxEngine(LLMConfig(model=family.served_model(config, args.seed),
                              engine=EngineConfig(dtype=run["dtype"], **run["engine"])))
    constructor_s = time.perf_counter() - t
    init = eng.get_stats().get("init") or {}
    total = {phase: sum(row[phase] for row in by_program.values()) for phase in EVENTS.values()}
    cost = lambda row: row["trace_s"] + row["lower_s"] + row["compile_or_fetch_s"]  # noqa: E731
    listed = sorted(by_program.items(), key=lambda kv: -cost(kv[1]))
    compiled = [row for _, row in listed if row["n"] and not row["fetch_s"]]
    # an engine that runs executables (PR 46): whether the decode step's takes the
    # weights in the layout the engine holds them in, leaf by relaid leaf
    held = {}
    for form, program in (getattr(eng, "_programs", None) or {}).items():
        if form[0] == "decode" and hasattr(program, "input_formats"):
            takes = program.input_formats[0][0]
            held = {name: [list(takes[name].layout.major_to_minor),
                           list(eng.params[name].format.layout.major_to_minor)]
                    for name in serving_layouts(eng.params)}
    kept = os.path.join(jax_cache.cache_dir(), "programs")
    sizes = [os.path.getsize(os.path.join(kept, f)) for f in os.listdir(kept)] if os.path.isdir(kept) else []
    print(json.dumps({
        "kept_programs": {"files": len(sizes), "bytes": sum(sizes), "largest": max(sizes, default=0)},
        "decode_takes_and_engine_holds": held, "params_relaid": eng.get_stats()["params_relaid"],
        "config": args.config, "device": jax.devices()[0].device_kind,
        "cache_dir": jax_cache.cache_dir(), "cache_dir_from_env": bool(os.environ.get(jax_cache.ENV_VAR)),
        "cache_entries_before": entries_before, "cache_entries_after": jax_cache.entry_count(),
        "engine_constructor_s": constructor_s, "init": init,
        "programs": len(by_program), "compiles": sum(r["n"] for r in by_program.values()),
        "jax_events_total_s": total, "jax_events_by_init_phase_s": by_phase,
        "compiled_not_fetched": {"programs": len(compiled), "compiles": sum(r["n"] for r in compiled),
                                 "seconds": sum(cost(r) for r in compiled)},
        "by_program": {name: {k: round(v, 4) for k, v in row.items()} for name, row in listed[:args.top]},
        "the_rest": {"programs": len(listed[args.top:]),
                     **{phase: round(sum(r[phase] for _, r in listed[args.top:]), 4)
                        for phase in EVENTS.values()}},
    }), flush=True)
    eng.shutdown()


if __name__ == "__main__":
    main()
