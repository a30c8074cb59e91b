#!/usr/bin/env python3
"""The numbers that decide ``correct`` in a ``looped_dense`` cell, read
with the mechanism itself broken: the two controls of a stack that runs
several times a token. One process that holds the chip; the family's weights
from the seed; the probe's first ``logit_rows`` prompts and seeded decode
tokens through ``models/llama.py prefill`` in the engine's own chunks
(``run.engine.prefill_chunk``) and ``decode_step``, the keys and values read
out of the cache they wrote (no engine and no Serve):

    sound         the program as it is
    three_passes  the program at one pass fewer than the model's (its cache
                  has a pass's rows fewer: the rows it has are compared)
    wrong_row     pass t of the program reads pass t - 1's cache rows (pass 0
                  its own), and writes its own

each against the plain reference's ``total_ut_steps`` passes on the same
tokens: ``kv_prefill_rel_rms`` (the prompts' positions, every row the program's
cache has), ``kv_decode_rel_rms`` (the positions the decode steps wrote),
``kv_pass<t>_rel_rms`` (pass ``t``'s rows alone: ``benchmark/kinds/looped_closed_loop.py
kv_errors``), ``logits_rel_rms`` (a prompt's last position and every step's). The passes a
shorter stack runs are the model's own, so ``three_passes`` wrongs no key or
value and every logit (the head reads another pass's stream); ``wrong_row``
wrongs the attention of every pass but the first, and with it every later
row and the logits. The last line gives each number beside the
configuration's limit and whether it passes: each control must fail at least
one (PERF.md section 2 says which). The cache's row is read from outside
through a wrapper of ``models/patterned.py _cache_reader``, so the program has
no switch for it.

    python3 benchmark/tools/loop_control.py --config ouro-2.6b-serve-l48 --seeds 1
"""

import argparse
import copy
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

MODES = ("sound", "three_passes", "wrong_row")


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private import jax_cache
    from ray_tpu.llm import EngineConfig
    from ray_tpu.llm.config import resolve_llama_config
    from ray_tpu.models import patterned
    from ray_tpu.models.llama import decode_step, init_kv_cache, prefill

    from benchmark import common, compare, families, reference
    from benchmark.kinds.looped_closed_loop import kv_errors

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, default=1)
    parser.add_argument("--first-seed", type=int, default=2147483000)
    args = parser.parse_args()
    jax_cache.configure()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    family, run = families.load(config), config["run"]
    probe, chunk, limits = run["probe"], run["engine"]["prefill_chunk"], run["limits"]
    whole = resolve_llama_config(
        family.served_model(config, 0), EngineConfig(dtype=run["dtype"], **run["engine"]))
    B, steps = probe["logit_rows"], probe["decode_steps"]
    lens = np.asarray(probe["prompt_lens"][:B])
    stripe = -(-(int(lens.max()) + steps) // 128) * 128
    n_rows = patterned.plan(whole).n_attention
    print(json.dumps({"device": jax.devices()[0].device_kind, "stripe": stripe, "rows": B}),
          flush=True)
    ref = family.Reference(config, jax.local_devices()[:1])
    inner = patterned._cache_reader

    def a_pass_behind(*a, **kw):
        read = inner(*a, **kw)

        def shifted(q, ck_all, cv_all, lay):
            lay = copy.copy(lay)
            lay.cache_i = jnp.maximum(lay.cache_i - n_rows, lay.kv_i)
            return read(q, ck_all, cv_all, lay)

        return shifted

    def program(params, rows, mode):
        cfg = dataclasses.replace(whole, loop_passes=whole.loop_passes - 1) \
            if mode == "three_passes" else whole
        patterned._cache_reader = a_pass_behind if mode == "wrong_row" else inner
        try:  # traced inside: a jit a mode
            pre = jax.jit(lambda p, c, t, n, s: prefill(p, c, t, cfg, lengths=n, start_pos=s),
                          donate_argnums=(1,))
            dec = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg), donate_argnums=(1,))
            cache = init_kv_cache(cfg, B, stripe)
            at, logits = np.zeros(B, np.int32), [None] * B
            while (at < lens).any():
                n = np.minimum(lens - at, chunk).astype(np.int32)
                fed = np.zeros((B, chunk), np.int32)
                for b in range(B):
                    fed[b, :n[b]] = rows[b][at[b]:at[b] + n[b]]
                out, cache = pre(params, cache, jnp.asarray(fed), jnp.asarray(n), jnp.asarray(at))
                for b in np.flatnonzero(n):
                    logits[b] = np.asarray(out[b])
                at = at + n
            got = [np.stack(logits)]
            for step in range(steps):
                fed = np.asarray([r[p + step] for r, p in zip(rows, lens)], np.int32)
                out, cache = dec(params, cache, jnp.asarray(fed))
                got.append(np.asarray(out))
        finally:
            patterned._cache_reader = inner
        kv = [np.asarray(cache[name].astype(jnp.float32)).transpose(1, 0, 3, 2, 4)
              for name in ("k", "v")]  # [B, rows, S, K, D]
        return np.stack(got, axis=1), kv

    def errors(got, want):
        logits, kv = got
        return {
            **kv_errors([(have[b][:, :p + steps], ref_kv, p) for b, p in enumerate(lens)
                         for have, ref_kv in zip(kv, want["kv"][b])], whole.loop_passes),
            "logits_rel_rms": reference.rel_rms(logits, np.stack(want["logits"])),
        }

    readings = []
    for i in range(args.seeds):
        seed = (args.first_seed + 7919 * i) % common.MODEL_SEED_MOD
        rows = compare.probe_rows(seed, probe)[:B]
        params = family.make_params(seed, config, whole.dtype)
        want = ref.forward_rows(params, rows, last=steps + 1, kv_rows=range(B))
        out = {"seed": seed, **{mode: errors(program(params, rows, mode), want) for mode in MODES}}
        print(json.dumps(out), flush=True)
        readings.append(out)
        del params
    summary = {
        mode: {k: {"smallest": min(r[mode][k] for r in readings),
                   "largest": max(r[mode][k] for r in readings), "limit": limits[k],
                   "passes": max(r[mode][k] for r in readings) <= limits[k]}
               for k in limits if k in readings[0][mode]}  # (a shorter stack lacks a pass)
        for mode in MODES
    }
    print(json.dumps({"config": args.config, "seeds": len(readings), "summary": summary}),
          flush=True)


if __name__ == "__main__":
    main()
