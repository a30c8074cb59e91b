#!/usr/bin/env python3
"""Time the decode kernel's walk (``ray_tpu/ops/decode_attention.py``) at the
serving cells' cache shapes by the positions a block takes: the measurement
behind ``BLOCK_BYTES``. One jitted loop over a shape's layers (the layer index
traced, as the model's loop hands it), rows of lengths drawn as the cell's
traffic leaves them, seeded values in the cell's type; the kernel's time is its
device events' in a profiler trace of ``--runs`` launches, beside the host's
clock round the same launches. The program has no option for the block: this
tool replaces the module's rule from outside, once a length, before it traces.

    python3 tools/decode_block_sweep.py                    # through the chip tool
    python3 tools/decode_block_sweep.py --rehearse         # tiny, on the CPU: no times

A line a shape and block: ``us_a_layer`` (device), ``us_a_block``, the GB/s on
the live bytes (what ``kernel.decode_*_hbm_share`` counts) and on the bytes the
blocks hold, ``read_efficiency`` (live over read positions).
"""

import argparse
import functools
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name -> slots, key-value heads, query heads, key and value widths, stripe,
# layers, the rows' live lengths (a prompt's distribution and the answer's, a
# row caught anywhere in its answer; ``benchmark/traffic/<file>``), a window
# (0: from the stripe's start) and whether the layer is latent
LONG_CHAT = (dict(median=1024, sigma=0.9, min=128, max=4096), dict(median=160, sigma=0.5, min=64, max=448))
CHAT_128 = (dict(median=256, sigma=0.8, min=64, max=1024), dict(median=192, sigma=0.5, min=64, max=512))
CHAT_64 = (dict(median=192, sigma=0.8, min=32, max=768), dict(median=64, sigma=0.6, min=16, max=192))
MIXED = (dict(median=768, sigma=1.0, min=64, max=3584), dict(median=128, sigma=0.6, min=32, max=448))
DOCS = ((12288, 20480), dict(median=96, sigma=0.6, min=32, max=256), dict(median=64, sigma=0.5, min=32, max=128))
SHAPES = {
    # zaya1-8b-serve-long-chat: 2 heads of 128, 20 layers, 64 slots of 4,608
    "zaya": dict(slots=64, kv_heads=2, heads=8, dk=128, dv=128, stripe=4608, layers=20, lengths=LONG_CHAT),
    # nemotron3-super-serve-chat: its one attention block, 32 query heads on 2
    "nemotron": dict(slots=64, kv_heads=2, heads=32, dk=128, dv=128, stripe=2048, layers=1, lengths=CHAT_128),
    # mistral7b-serve-saturated: the shape PR 31 measured BLOCK at
    "mistral": dict(slots=32, kv_heads=8, heads=32, dk=128, dv=128, stripe=1024, layers=16, lengths=CHAT_64),
    # laguna-xs2-serve-mixed: a full layer (48 query heads) and a sliding one (64, window 512)
    "laguna_full": dict(slots=32, kv_heads=8, heads=48, dk=128, dv=128, stripe=4096, layers=2, lengths=MIXED),
    "laguna_window": dict(slots=32, kv_heads=8, heads=64, dk=128, dv=128, stripe=4096, layers=3,
                          lengths=MIXED, window=512),
    # solar-open2-serve-long-chat: its one attention layer, 64 query heads on 8
    "solar": dict(slots=64, kv_heads=8, heads=64, dk=128, dv=128, stripe=8192, layers=1, lengths=LONG_CHAT),
    # kanana2-serve-docs-shared: 32 heads on one rotated key (a 128-lane row) and a 512-wide latent
    "kanana_latent": dict(slots=24, kv_heads=1, heads=32, dk=128, dv=512, stripe=24576, layers=5,
                          lengths=DOCS, latent=True),
    # dots3-note-serve-docs-shared: its sliding latent layers, 64 heads on a 1,024-wide latent, window 513
    "dots3_sliding_latent": dict(slots=16, kv_heads=1, heads=64, dk=128, dv=1024, stripe=24576, layers=3,
                                 lengths=DOCS, window=513, latent=True),
}
REHEARSAL = dict(slots=3, stripe=1024, layers=2)  # what ``--rehearse`` cuts every shape to


def _lognormal(rng, n, median, sigma, min, max):  # noqa: A002 - the traffic files' own keys
    import numpy as np

    return np.clip(np.round(median * np.exp(sigma * rng.standard_normal(n))), min, max).astype(np.int64)


def _lengths(rng, shape):
    import numpy as np

    n, spec = shape["slots"], shape["lengths"]
    if len(spec) == 3:  # a shared document, a tail of its own, part of an answer
        docs, tail, answer = spec
        prompt = rng.choice(docs, n) + _lognormal(rng, n, **tail)
    else:
        prompt, answer = _lognormal(rng, n, **spec[0]), spec[1]
    live = prompt + (rng.random(n) * _lognormal(rng, n, **answer)).astype(np.int64)
    return np.minimum(live, shape["stripe"])


def _kernel_events(trace_dir, name):
    """Seconds of every device event of the kernel ``name`` in the trace, and
    the names of the other operations there."""
    from benchmark import trace

    planes = trace.read_planes(trace.find_xplane(trace_dir))
    ops = [(n, b - a) for dev in planes["devices"].values() for n, a, b in dev["ops"]]
    return ([s for n, s in ops if n.split(".")[0] == name],
            sorted({n for n, _ in ops if n.split(".")[0] != name})[:20])


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", nargs="+", default=list(SHAPES), choices=list(SHAPES))
    parser.add_argument("--blocks", type=int, nargs="+", default=[128, 256, 512, 1024])
    parser.add_argument("--runs", type=int, default=20)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "decode_block_sweep.jsonl"))
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import decode_attention as da

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

    say({"device": device.device_kind, "rehearsal": args.rehearse, "runs": args.runs, "seed": args.seed,
         "block_bytes": da.BLOCK_BYTES})
    scratch = os.path.join(ROOT, ".scratch", "decode_block_sweep")
    for name in args.shapes:
        shape = {**SHAPES[name], **(REHEARSAL if args.rehearse else {})}
        B, K, H, S, L = (shape[k] for k in ("slots", "kv_heads", "heads", "stripe", "layers"))
        latent, window = shape.get("latent", False), shape.get("window", 0)
        rng = np.random.default_rng(args.seed)
        hi_np = _lengths(rng, shape)
        lo_np = np.maximum(hi_np - window, 0) if window else np.zeros_like(hi_np)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        dtype = jnp.bfloat16
        # (one layer's values under every layer index: a draw of the whole
        # leaf holds it several times over in wider types on the way)
        ck = jnp.concatenate([jax.random.normal(keys[0], (1, B, K, S, shape["dk"]), dtype)] * L)
        cv = jnp.concatenate([jax.random.normal(keys[1], (1, B, K, S, shape["dv"]), dtype)] * L)
        q = jax.random.normal(keys[2], (B, H, shape["dk"]), dtype)
        ql = jax.random.normal(keys[3], (B, H, shape["dv"]), dtype)
        lo, hi = jnp.asarray(lo_np, jnp.int32), jnp.asarray(hi_np, jnp.int32)
        row_bytes = da.cache_position_bytes(ck, cv)
        kernel = "latent_decode_attention" if latent else "decode_attention"

        def layers(q, ql, ck, cv, lo, hi, bs):  # ``bs``: static, so each block is traced anew
            def one(l, acc):
                o = (da.latent_decode_attention(q, ql, ck, cv, l, lo, hi, 192 ** -0.5) if latent
                     else da.decode_attention(q, ck, cv, l, lo, hi))
                return acc + o.astype(jnp.float32)

            return jax.lax.fori_loop(0, L, one, jnp.zeros((B, H, shape["dv"]), jnp.float32))

        rule = da.block_size
        for bs in args.blocks:
            if S % bs:
                continue
            da.block_size = lambda stripe, position_bytes, latent=False, bs=bs: bs
            try:
                fn = functools.partial(jax.jit(layers, static_argnames="bs"), bs=bs)
                t0 = time.perf_counter()
                jax.block_until_ready(fn(q, ql, ck, cv, lo, hi))  # compiles
                compile_s = time.perf_counter() - t0
                read = da.positions_read(lo_np, hi_np, S, row_bytes, latent)  # under the replaced rule
            except Exception as e:  # noqa: BLE001 - the chip's compiler refused the block: say so, go on
                say({"shape": name, "block": bs, "refused": f"{type(e).__name__}: {e}"[:400]})
                continue
            finally:
                da.block_size = rule
            shutil.rmtree(scratch, ignore_errors=True)
            jax.profiler.start_trace(scratch)
            t0 = time.perf_counter()
            jax.block_until_ready([fn(q, ql, ck, cv, lo, hi) for _ in range(args.runs)])
            host_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            live = int((hi_np - lo_np).sum())
            record = {"shape": name, "block": bs, "the_rule_gives": rule(S, row_bytes, latent),
                      "position_bytes": row_bytes, "block_bytes": bs * row_bytes,
                      "rows": B, "layers": L, "live_positions_mean": live / B,
                      "blocks_a_layer": int(read.sum()) // bs,
                      "read_efficiency": live / int(read.sum()),
                      "compile_s": compile_s, "host_us_a_layer": 1e6 * host_s / args.runs / L}
            if not args.rehearse:  # (the CPU's trace holds no device plane)
                events, others = _kernel_events(scratch, kernel)
                record["events"] = len(events)  # runs x layers, or the name did not match
                if events:
                    us = 1e6 * float(np.mean(events))
                    record.update({
                        "us_a_layer": us, "us_a_layer_min": 1e6 * min(events),
                        "us_a_layer_max": 1e6 * max(events),
                        "us_a_block": us / record["blocks_a_layer"],
                        "gb_s_live": live * row_bytes / us / 1e3,
                        "gb_s_read": int(read.sum()) * row_bytes / us / 1e3,
                    })
                else:
                    record["device_ops_seen"] = others
            say(record)
        del ck, cv
    shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    main()
