#!/usr/bin/env python3
"""The control of a precision that ``int8_roundtrip`` does not touch: family
``ssm_latent_moe`` states its recurrent state and recurrence in float32
(the configuration's ``assumed.ssm_precision``), whatever the weights' type.
Here the plain reference with the state held, multiplied and summed in
bfloat16 is put in the program's place: its logits and its attention block's
keys and values over the probe's seeded rows go through
``benchmark/compare.py serve_errors`` against the float32 reference, on the
cell's own weights, and are set beside the configuration's limits. No engine
runs, so the reading holds none of a sound program's own distance from the
reference (bf16 weights and activations, swapped experts): a control that
passes alone may still fail beside that, and PERF.md section 2 says which.

    python3 benchmark/tools/state_precision.py \\
        --config nemotron-3-super-120b-a12b-serve-l11-ep4 --seeds 3
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def one_seed(ref, control, family, config, seed, dtype):
    from benchmark import common, compare

    import numpy as np

    probe = config["run"]["probe"]
    model_seed = seed % common.MODEL_SEED_MOD
    rows = compare.probe_rows(model_seed, probe)
    params = family.make_params(model_seed, config, dtype)
    steps = probe["decode_steps"]
    made = control.forward_rows(params, rows, last=steps + 1, kv_rows=range(len(rows)))
    got = {
        # what ``engine_probe`` hands out: the tokens whose keys and values
        # were written, and those [L, T, KV, D]
        "engine": [{"tokens": r, "k": made["kv"][i][0], "v": made["kv"][i][1], "generated": steps + 1}
                   for i, r in enumerate(rows)],
        "logits": np.stack(made["logits"]),
    }
    return compare.serve_errors(got, ref, params, rows, probe)


def main():
    import jax
    import jax.numpy as jnp

    from benchmark import common, families

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2347483000)
    args = parser.parse_args()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    family = families.load(config)
    device = jax.local_devices()[:1]
    print(json.dumps({"device": device[0].device_kind}), flush=True)
    ref = family.Reference(config, device)
    control = family.Reference(config, device, state_dtype=jnp.bfloat16)
    dtype = jnp.dtype(config["run"]["dtype"])
    limits = config["run"]["limits"]
    readings = []
    for i in range(args.seeds):
        t = time.perf_counter()
        seed = args.first_seed + 7919 * i
        out = one_seed(ref, control, family, config, seed, dtype)
        print(json.dumps({"seed": seed, "bf16_state": out, "seconds": time.perf_counter() - t}), flush=True)
        readings.append(out)
    summary = {
        k: {"smallest": min(r[k] for r in readings), "largest": max(r[k] for r in readings),
            "limit": limit, "fails": min(r[k] for r in readings) > limit}
        for k, limit in limits.items()
    }
    print(json.dumps({"config": args.config, "seeds": len(readings), "summary": summary}), flush=True)


if __name__ == "__main__":
    main()
