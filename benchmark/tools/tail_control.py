#!/usr/bin/env python3
"""The three numbers that decide ``correct`` in a ``cca_moe`` cell, read with
the slots' tails lost: the control of what a slot of such a model carries from
token to token beside its stripes (the last inputs of both convolutions and
the last token's shifted values). One process that holds the chip; the
family's weights from the seed; the probe's prompts and seeded decode tokens
through ``models/llama.py prefill`` in the engine's own chunks (``run.engine
.prefill_chunk``) and ``decode_step``, the keys and values read out of the
cache they wrote (no engine and no Serve: the tails are the cache's own leaf,
zeroed between calls here):

    sound   the tails carried as the program carries them
    chunks  the tails zeroed at the start of every chunk but a prompt's first
    steps   the tails zeroed at every decode step (the chunks' carried)

each against the plain reference on the same tokens: ``kv_prefill_rel_rms``
(the prompts' positions), ``kv_decode_rel_rms`` (the positions the decode steps
wrote), ``logits_rel_rms`` (a prompt's last position and every step's). ``steps``
wrongs every decode position of every layer; ``chunks`` wrongs one position in
a thousand (the first of each later chunk), and what follows from it through
the layers. The last line gives each number beside the configuration's limit
and whether it passes: PERF.md section 2 says which limit fails for which.

The sound pass also counts the floor the limits stand on: of the prompts'
tokens, the share whose one chosen expert differs between the program and the
reference, over all layers (``choices_swapped``; the router's choice is read
from outside through a wrapper of ``models/patterned.py _mlp_route``, so the
program has no switch for it). ``benchmark/tools/routing_swaps.py`` counts the
same for a router that is a matrix (it wraps ``parallel/moe.py topk_gates`` and
wants layers traced one by one); this family's router is neither.

    python3 benchmark/tools/tail_control.py --config zaya1-8b-serve-l20-ep2 --seeds 3
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

MODES = ("sound", "chunks", "steps")


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

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2147483000)
    args = parser.parse_args()
    jax_cache.configure()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    family, run = families.load(config), config["run"]
    probe, chunk, limits = run["probe"], run["engine"]["prefill_chunk"], run["limits"]
    cfg = resolve_llama_config(
        family.served_model(config, 0), EngineConfig(dtype=run["dtype"], **run["engine"]))
    lens, steps = np.asarray(probe["prompt_lens"]), probe["decode_steps"]
    B, stripe = len(lens), -(-(int(lens.max()) + steps) // 128) * 128
    print(json.dumps({"device": jax.devices()[0].device_kind, "stripe": stripe}), flush=True)
    ref = family.Reference(config, jax.local_devices()[:1])
    chosen, inner = {}, patterned._mlp_route

    def recording(params, row, g, r_prev, cfg_):
        vals, idx, r = inner(params, row, g, r_prev, cfg_)
        jax.debug.callback(lambda at, e: chosen.__setitem__(int(at), np.asarray(e)), row, idx)
        return vals, idx, r

    patterned._mlp_route = recording  # before the first trace; the program has no switch
    pre = jax.jit(lambda p, c, t, n, s: prefill(p, c, t, cfg, lengths=n, start_pos=s),
                  donate_argnums=(1,))
    dec = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg), donate_argnums=(1,))

    def lost(cache):
        return dict(cache, cca_tail=jnp.zeros_like(cache["cca_tail"]))

    def program(params, rows, mode):
        cache = init_kv_cache(cfg, B, stripe)
        at, logits = np.zeros(B, np.int32), [None] * B
        picked = [[[] for _ in range(B)] for _ in range(cfg.n_layers)]  # [layer][row]: choices
        while (at < lens).any():
            n = np.minimum(lens - at, chunk).astype(np.int32)
            fed = np.zeros((B, chunk), np.int32)
            for b in range(B):
                fed[b, :n[b]] = rows[b][at[b]:at[b] + n[b]]
            if mode == "chunks" and at.any():
                cache = lost(cache)
            out, cache = pre(params, cache, jnp.asarray(fed), jnp.asarray(n), jnp.asarray(at))
            for b in np.flatnonzero(n):
                logits[b] = np.asarray(out[b])
            jax.effects_barrier()
            for layer, e in chosen.items():
                for b in range(B):
                    picked[layer][b].append(e.reshape(B, chunk, -1)[b, :n[b]])
            at = at + n
        got = [np.stack(logits)]
        for step in range(steps):
            if mode == "steps":
                cache = lost(cache)
            fed = np.asarray([r[p + step] for r, p in zip(rows, lens)], np.int32)
            out, cache = dec(params, cache, jnp.asarray(fed))
            got.append(np.asarray(out))
        kv = [np.asarray(cache[name].astype(jnp.float32)).transpose(1, 0, 3, 2, 4)
              for name in ("k", "v")]  # [B, L, S, K, D]
        return np.stack(got, axis=1), kv, [[np.concatenate(r) for r in layer] for layer in picked]

    def errors(got, want):
        logits, kv, picked = got
        sq = {"prefill": np.zeros(2), "decode": np.zeros(2)}
        for b, p in enumerate(lens):
            for have, ref_kv in zip(kv, want["kv"][b]):
                have = have[b][:, :p + steps]
                d, r = (have.astype(np.float64) - ref_kv) ** 2, ref_kv.astype(np.float64) ** 2
                sq["prefill"] += [d[:, :p].sum(), r[:, :p].sum()]
                sq["decode"] += [d[:, p:].sum(), r[:, p:].sum()]
        return {
            "kv_prefill_rel_rms": float(np.sqrt(sq["prefill"][0] / sq["prefill"][1])),
            "kv_decode_rel_rms": float(np.sqrt(sq["decode"][0] / sq["decode"][1])),
            "logits_rel_rms": reference.rel_rms(logits, np.stack(want["logits"])),
            "choices_swapped": float(np.mean(np.concatenate([
                (mine != theirs[:p]).ravel() for layer, ref_layer in zip(picked, want["choices"])
                for mine, theirs, p in zip(layer, ref_layer, lens)]))),
        }

    readings = []
    for i in range(args.seeds):
        seed = (args.first_seed + 7919 * i) % common.MODEL_SEED_MOD
        rows = compare.probe_rows(seed, probe)
        params = family.make_params(seed, config, cfg.dtype)
        want = ref.forward_rows(params, rows, last=steps + 1, kv_rows=range(B))
        out = {"seed": seed, **{mode: errors(program(params, rows, mode), want) for mode in MODES}}
        print(json.dumps(out), flush=True)
        readings.append(out)
        del params
    summary = {
        mode: {k: {"smallest": min(r[mode][k] for r in readings),
                   "largest": max(r[mode][k] for r in readings), "limit": limits[k],
                   "passes": max(r[mode][k] for r in readings) <= limits[k]} for k in limits}
        for mode in MODES
    }
    summary["sound"]["choices_swapped"] = {
        "smallest": min(r["sound"]["choices_swapped"] for r in readings),
        "largest": max(r["sound"]["choices_swapped"] for r in readings)}
    print(json.dumps({"config": args.config, "seeds": len(readings), "summary": summary}),
          flush=True)


if __name__ == "__main__":
    main()
