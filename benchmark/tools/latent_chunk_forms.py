#!/usr/bin/env python3
"""Time the two forms a prompt's chunk can take over a latent cache
(``models/patterned.py _chunk_expands`` chooses by the chunk's width:
absorbed or expanded) at the cell's
own shapes: one 256-token final chunk behind ``--start`` cached tokens in a
scratch stripe of the configuration's ``max_seq_len``, whole program and all
layers, on seeded weights. The program has no option for the form; this tool
replaces the module's rule from outside, once a form, before it traces.

    python3 benchmark/tools/latent_chunk_forms.py --config kanana-2-30b-a3b-serve-l5
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import EngineConfig
    from ray_tpu.llm.config import resolve_llama_config
    from ray_tpu.models import patterned
    from ray_tpu.models.llama import init_kv_cache, prefill

    from benchmark import common, families

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--starts", type=int, nargs="+", default=[12288, 20480])
    parser.add_argument("--chunk", type=int, default=256)
    parser.add_argument("--runs", type=int, default=5)
    args = parser.parse_args()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    family = families.load(config)
    run = config["run"]
    cfg = resolve_llama_config(family.served_model(config, 0),
                               EngineConfig(dtype=run["dtype"], **run["engine"]))
    params = family.make_params(1, config, cfg.dtype)
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (1, args.chunk)), jnp.int32)
    length = jnp.asarray([args.chunk], jnp.int32)
    for expands in (False, True):
        patterned._chunk_expands = lambda cfg, T, expands=expands: expands
        fn = jax.jit(lambda p, c, t, n, s: prefill(p, c, t, cfg, lengths=n, start_pos=s),
                     donate_argnums=(1,))
        for start in args.starts:
            one = init_kv_cache(cfg, 1, run["engine"]["max_seq_len"])
            at = jnp.asarray([start], jnp.int32)
            times = []
            for _ in range(args.runs + 1):  # the first compiles
                t = time.perf_counter()
                logits, one = fn(params, one, tokens, length, at)
                jax.block_until_ready(logits)
                times.append(time.perf_counter() - t)
            print(json.dumps({"form": "expanded" if expands else "absorbed", "start": start,
                              "chunk": args.chunk, "compile_and_first_s": times[0],
                              "run_ms": [1e3 * x for x in times[1:]]}), flush=True)


if __name__ == "__main__":
    main()
