#!/usr/bin/env python3
"""Take the two readings a limit of ``correct`` is set from: over a dozen
seeds, the numbers sound runs of the program give against the reference, and
the numbers the int8 control gives. One process that holds the chips, at the
configuration's own size; the train step and an engine of the cell's own
settings are built once in this process, so no set-up is paid per seed.

    python3 benchmark/tools/calibrate.py --config mistral-7b-v0.3-train-l4 \\
        --traffic pretrain-4x2048 --seeds 12 --first-seed 2147483000
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def train(config, traffic, seeds, control_seeds):
    import jax

    from ray_tpu.models.training import batch_sharding

    from benchmark import compare, families
    from benchmark.kinds import train_steps

    run = config["run"]
    cfg, mesh, optimizer, step_fn = train_steps.build(config, traffic)
    ref = families.load(config).Reference(config, list(mesh.devices.flat))
    rows = len(jax.local_devices())
    for seed in seeds:
        t = time.perf_counter()
        tokens = train_steps.make_batch(seed, traffic, cfg.vocab_size)
        batch = {train_steps.TOKEN_KEY: jax.device_put(tokens, batch_sharding(mesh))}
        params = train_steps.make_params(seed, config, cfg, mesh)
        want = compare.train_reference(ref, params, tokens, run, rows)
        out = {"seed": seed}
        for side in ("sound", "int8") if seed in control_seeds else ("sound",):
            if side == "int8":
                params = train_steps.make_params(seed, config, cfg, mesh, control="int8")
            logits = compare.train_program_logits(params, tokens, cfg, mesh, run, rows)
            state, first = step_fn(train_steps.make_state(params, optimizer, mesh), batch)
            out[side] = compare.train_errors(
                first, train_steps.first_moments(state), logits, want, run, train_steps.ADAM_B1
            )
            del state, params, logits
        del want
        out["seconds"] = time.perf_counter() - t
        yield out


def serve(config, traffic, seeds, control_seeds):
    """An engine of the cell's own settings in this process (no Serve around
    it: the comparison runs inside the replica, below the router)."""
    import gc

    import jax

    from ray_tpu.llm.engine import JaxEngine

    from benchmark import common, compare, families, serving

    family = families.load(config)
    probe = config["run"]["probe"]
    engine = JaxEngine(serving.make_llm_config(config, seeds[0], rehearsal=True))
    shardings = {k: v.sharding for k, v in engine.params.items()}
    dtype = engine.params["embed"].dtype
    ref = family.Reference(config, jax.local_devices()[:1])

    def fresh(model_seed):
        engine.params = None
        gc.collect()
        engine.params = family.make_params(model_seed, config, dtype, shardings)

    try:
        for seed in seeds:
            t = time.perf_counter()
            model_seed = seed % common.MODEL_SEED_MOD
            rows = compare.probe_rows(model_seed, probe)
            out = {"seed": seed}
            fresh(model_seed)
            got = compare.serve_program(engine, rows, probe)
            out["sound"] = compare.serve_errors(got, ref, engine.params, rows, probe)
            if seed in control_seeds:
                engine.params = family.int8_roundtrip(engine.params)
                got = compare.serve_program(engine, rows, probe)
                fresh(model_seed)  # the reference reads the weights as made
                out["int8"] = compare.serve_errors(got, ref, engine.params, rows, probe)
            out["seconds"] = time.perf_counter() - t
            yield out
    finally:
        engine.shutdown()


def main():
    from benchmark import common

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2147483000)
    parser.add_argument("--control-seeds", type=int, default=None,
                        help="run the int8 control on the first N seeds only (default: all)")
    args = parser.parse_args()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    traffic = common.load_traffic(args.traffic)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    import jax

    print(json.dumps({"device": jax.devices()[0].device_kind, "count": len(jax.devices())}), flush=True)
    readings = []
    fn = train if config["run"]["kind"] == "train" else serve
    n_control = args.seeds if args.control_seeds is None else args.control_seeds
    for out in fn(config, traffic, seeds, set(seeds[:n_control])):
        print(json.dumps(out), flush=True)
        readings.append(out)
    numbers = [k for k, v in readings[0]["sound"].items() if isinstance(v, float)]
    summary = {
        k: {"sound_smallest": min(r["sound"][k] for r in readings),
            "sound_largest": max(r["sound"][k] for r in readings),
            "int8_smallest": min((r["int8"][k] for r in readings if "int8" in r), default=None)}
        for k in numbers if k != "top1_agree"
    }
    print(json.dumps({"config": args.config, "seeds": len(readings), "summary": summary}), flush=True)


if __name__ == "__main__":
    main()
