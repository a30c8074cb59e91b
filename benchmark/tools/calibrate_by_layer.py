#!/usr/bin/env python3
"""The serving comparison of ``correct`` split by layer: for each seed, the
relative RMS error of the keys and values the engine's programs left in its
cache against the reference's, one number a layer (prefill positions and
decode positions apart), for sound weights and under the int8 control. The
totals are ``benchmark/tools/calibrate.py``'s; this shows where in the depth
they arise (an expert layer's swapped choices show in the layers after it).

    python3 benchmark/tools/calibrate_by_layer.py --config laguna-xs.2-serve-l5 --seeds 2
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def by_layer(got: dict, want: dict, lens: list, n: int) -> dict:
    import numpy as np

    layers = want["kv"][n][0].shape[0]
    sq = {"prefill": np.zeros((layers, 2)), "decode": np.zeros((layers, 2))}
    for i, (e, p) in enumerate(zip(got["engine"], lens)):
        for have, ref_kv in zip((e["k"], e["v"]), want["kv"][n + i]):
            d = (have.astype(np.float64) - ref_kv) ** 2
            r = ref_kv.astype(np.float64) ** 2
            for part, sl in (("prefill", slice(None, p)), ("decode", slice(p, None))):
                sq[part][:, 0] += d[:, sl].sum(axis=(1, 2, 3))
                sq[part][:, 1] += r[:, sl].sum(axis=(1, 2, 3))
    return {part: [float(x) for x in np.sqrt(s[:, 0] / s[:, 1])] for part, s in sq.items()}


def main():
    import jax

    from ray_tpu.llm.engine import JaxEngine

    from benchmark import common, compare, families, serving

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=2147483000)
    args = parser.parse_args()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    family = families.load(config)
    probe = config["run"]["probe"]
    lens, steps = probe["prompt_lens"], probe["decode_steps"]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    engine = JaxEngine(serving.make_llm_config(config, seeds[0], rehearsal=True))
    shardings = {k: v.sharding for k, v in engine.params.items()}
    dtype = engine.params["embed"].dtype
    ref = family.Reference(config, jax.local_devices()[:1])
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)

    def fresh(model_seed):
        engine.params = None
        gc.collect()
        engine.params = family.make_params(model_seed, config, dtype, shardings)

    try:
        for seed in seeds:
            model_seed = seed % common.MODEL_SEED_MOD
            rows = compare.probe_rows(model_seed, probe)
            fresh(model_seed)
            sides = {"sound": compare.serve_program(engine, rows, probe)}
            engine.params = family.int8_roundtrip(engine.params)
            sides["int8"] = compare.serve_program(engine, rows, probe)
            fresh(model_seed)  # the reference reads the weights as made
            out = {"seed": seed}
            for side, got in sides.items():
                n = len(rows)
                want = ref.forward_rows(
                    engine.params, list(rows) + [e["tokens"] for e in got["engine"]],
                    last=steps + 1, kv_rows=range(n, n + len(got["engine"])))
                out[side] = by_layer(got, want, lens, n)
            print(json.dumps(out), flush=True)
    finally:
        engine.shutdown()


if __name__ == "__main__":
    main()
