#!/usr/bin/env python3
"""Time a decode step's write of its new keys and values at the serving cells'
cache shapes in both forms: the ``[B, K, 1]``-index scatter a tensor and
``ops/cache_write.py``'s kernel (both tensors a call). The measurement behind
``models/patterned.py writes_rows``. One jitted loop over a shape's layers (the
layer index traced, the two caches donated and carried round, as the model's
loop hands them), one position a row drawn inside the stripe; a form's time is
its module's device time in a profiler trace of ``--runs`` launches over the
layers, beside the host's clock round the same launches.

    python3 tools/cache_write_sweep.py                     # through the chip tool
    python3 tools/cache_write_sweep.py --rehearse          # tiny, on the CPU: no times

A line a shape and form: ``us_a_layer`` (device; keys and values together).
"""

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name -> slots, key-value heads, stripe, layers (the cache's rows): the pools
# whose decode step writes a stripe that is not latent
SHAPES = {
    "ouro": dict(slots=12, kv_heads=16, stripe=384, layers=192),  # ouro-2.6b-serve-reason-chat
    "mistral": dict(slots=32, kv_heads=8, stripe=1024, layers=16),  # mistral7b-serve-saturated
    "laguna": dict(slots=32, kv_heads=8, stripe=4096, layers=5),  # laguna-xs2-serve-mixed
    "solar": dict(slots=64, kv_heads=8, stripe=8192, layers=1),  # solar-open2-serve-long-chat
    "nemotron": dict(slots=64, kv_heads=2, stripe=2048, layers=1),  # nemotron3-super-serve-chat
    "zaya": dict(slots=64, kv_heads=2, stripe=4608, layers=20),  # zaya1-8b-serve-long-chat
    "sdar_a_row": dict(slots=64, kv_heads=4, stripe=4096, layers=6),  # (SDAR's pool at T = 1: not served so)
}
REHEARSAL = dict(slots=3, stripe=64, layers=2)  # what ``--rehearse`` cuts every shape to
D = 128


def _module_events(trace_dir):
    """Seconds of every device module event in the trace, by module."""
    from benchmark import trace

    planes = trace.read_planes(trace.find_xplane(trace_dir))
    by = {}
    for dev in planes["devices"].values():
        for name, a, b in dev["modules"]:
            by.setdefault(trace.module_name(name), []).append(b - a)
    return by


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", nargs="+", default=list(SHAPES), choices=list(SHAPES))
    parser.add_argument("--runs", type=int, default=20)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "cache_write_sweep.jsonl"))
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.cache_write import rows_in_stripe, write_rows_in_place

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no chip here ({device.platform}): a time comes from a chip run; --rehearse runs tiny")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    out = open(args.out, "a")

    def say(record):
        line = json.dumps(record)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    say({"device": device.device_kind, "rehearsal": args.rehearse, "runs": args.runs, "seed": args.seed})
    scratch = os.path.join(ROOT, ".scratch", "cache_write_sweep")
    for name in args.shapes:
        shape = {**SHAPES[name], **(REHEARSAL if args.rehearse else {})}
        B, K, S, L = (shape[k] for k in ("slots", "kv_heads", "stripe", "layers"))
        rng = np.random.default_rng(args.seed)
        pos = jnp.asarray(rng.integers(0, S, B), jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 2)
        dtype = jnp.bfloat16
        new_k, new_v = (jax.random.normal(k, (B, K, D), dtype) for k in keys)
        bi, ki = jnp.arange(B)[:, None, None], jnp.arange(K)[None, :, None]

        def scatter(ck, cv, l, new_k, new_v, pos):  # ``models/patterned.py _cache_writer``'s
            pi = pos[:, None, None]
            return (ck.at[l, bi, ki, pi].set(new_k[:, :, None], mode="drop"),
                    cv.at[l, bi, ki, pi].set(new_v[:, :, None], mode="drop"))

        def kernel(ck, cv, l, new_k, new_v, rows):
            return tuple(write_rows_in_place(ck, cv, l, new_k, new_v, *rows))

        results = {}
        for form, write in (("scatter", scatter), ("kernel", kernel)):
            def layers(ck, cv, new_k, new_v, pos, write=write):
                if write is kernel:  # which rows write, and where: outside the loop, as the model asks
                    pos = rows_in_stripe(pos, None, S)
                return jax.lax.fori_loop(
                    0, L, lambda l, c: write(*c, l, new_k, new_v, pos), (ck, cv))

            layers.__name__ = f"write_{form}"
            fn = jax.jit(layers, donate_argnums=(0, 1))
            ck, cv = (jnp.zeros((L, B, K, S, D), dtype) for _ in range(2))
            t0 = time.perf_counter()
            ck, cv = jax.block_until_ready(fn(ck, cv, new_k, new_v, pos))  # compiles
            compile_s = time.perf_counter() - t0
            shutil.rmtree(scratch, ignore_errors=True)
            jax.profiler.start_trace(scratch)
            t0 = time.perf_counter()
            for _ in range(args.runs):
                ck, cv = fn(ck, cv, new_k, new_v, pos)
            jax.block_until_ready((ck, cv))
            host_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            results[form] = (ck, cv)
            record = {"shape": name, "form": form, "rows": B, "kv_heads": K, "stripe": S, "layers": L,
                      "compile_s": compile_s, "host_us_a_layer": 1e6 * host_s / args.runs / L}
            if not args.rehearse:  # (the CPU's trace holds no device plane)
                events = _module_events(scratch).get(f"jit_write_{form}", [])
                record["events"] = len(events)  # the runs, or the name did not match
                if events:
                    record.update({"us_a_layer": 1e6 * float(np.mean(events)) / L,
                                   "us_a_layer_min": 1e6 * min(events) / L,
                                   "us_a_layer_max": 1e6 * max(events) / L})
            say(record)
        same = all(bool((a == b).all()) for a, b in zip(results["scatter"], results["kernel"]))
        say({"shape": name, "the_forms_leave_the_same_bytes": same})
        del results, ck, cv
    shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    main()
