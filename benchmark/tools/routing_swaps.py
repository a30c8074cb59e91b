#!/usr/bin/env python3
"""Count the floor of family ``moe_window_gqa``'s comparison instead of
arguing it: how many of the experts a token chose differ between the program
and the reference, by layer, and what is left of the error when the program
is handed the reference's choices.

The program's prompt pass (``models/llama.py prefill`` over the probe's rows,
the pass ``logits_rel_rms`` comes from) runs twice on the same seeded weights,
with ``ray_tpu.parallel.moe.topk_gates`` (the one place the program picks
experts) wrapped from outside, so the program itself has no switch for this:

- *free*: the wrapper hands out the experts each token chose; they are set
  against the reference's (``Reference.forward_rows``'s ``choices``).
- *forced*: the wrapper puts the reference's choice in place of the
  program's for every prompt token (the gate weights are still the program's
  own probabilities at those experts, renormalised).

If expert choice is the floor, the forced pass falls to what a model with no
router shows in the same precision (0.011-0.014 for Mistral-7B, PERF.md
section 2), and the free pass's error by layer follows the share of tokens
that swapped. Works on a configuration whose layers trace one by one (no
repeated period under a loop), as the benchmark's 5-layer cut does.

    python3 benchmark/tools/routing_swaps.py --config laguna-xs.2-serve-l5 --seeds 2
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def program_pass(params, cfg, rows, lens, width, forced=None):
    """The prompts (padded to ``width``) through ``prefill`` as one batch.
    Returns (last-position logits [B, V], keys and values by row
    [L, T, KV, D], experts chosen by expert layer [B, width, k]).
    ``forced``: by expert layer, [B, width, k] choices to use where the
    position is a prompt's (others keep the program's own)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import init_kv_cache, prefill
    from ray_tpu.parallel import moe

    tokens = np.zeros((len(lens), width), np.int32)
    for i, (r, n) in enumerate(zip(rows, lens)):
        tokens[i, :n] = r[:n]
    valid = (np.arange(width)[None, :] < np.asarray(lens)[:, None]).reshape(-1)
    inner = moe.topk_gates

    def run(p, cache, t, n):
        seen = []

        def wrapped(router, x, k):
            probs, vals, idx = inner(router, x, k)
            if forced is not None:
                given = jnp.asarray(forced[len(seen)].reshape(-1, k))
                idx = jnp.where(valid[:, None], given, idx)
                vals = jnp.take_along_axis(probs, idx, axis=-1)
                vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
            seen.append(idx)
            return probs, vals, idx

        moe.topk_gates = wrapped
        try:
            logits, cache = prefill(p, cache, t, cfg, lengths=n)
        finally:
            moe.topk_gates = inner
        return logits, cache["k"], cache["v"], seen

    logits, k, v, seen = jax.jit(run)(
        params, init_kv_cache(cfg, len(lens), width), jnp.asarray(tokens), jnp.asarray(lens, jnp.int32))
    kv = [tuple(np.asarray(c[:, b, :, :n].astype(jnp.float32)).transpose(0, 2, 1, 3) for c in (k, v))
          for b, n in enumerate(lens)]
    return np.asarray(logits), kv, [np.asarray(s).reshape(len(lens), width, -1) for s in seen]


def swaps(mine, theirs, lens) -> dict:
    """Over the prompts' tokens: the share whose set of experts differs, and
    the share of single choices that differ."""
    import numpy as np

    tokens = moved = choices = 0
    for b, n in enumerate(lens):
        a, r = np.sort(mine[b, :n], -1), np.sort(np.asarray(theirs[b])[:n], -1)
        same = (a[:, :, None] == r[:, None, :]).any(-1).sum(-1)  # of a token's k choices
        k = a.shape[-1]
        tokens += n
        moved += int((same < k).sum())
        choices += int((k - same).sum())
    return {"tokens_with_a_swap": moved / tokens, "choices_swapped": choices / (tokens * a.shape[-1])}


def kv_by_layer(kv, want_kv, lens) -> list:
    import numpy as np

    layers = want_kv[0][0].shape[0]
    sq = np.zeros((layers, 2))
    for b, n in enumerate(lens):
        for have, ref_kv in zip(kv[b], want_kv[b]):
            sq[:, 0] += ((have.astype(np.float64) - ref_kv[:, :n]) ** 2).sum(axis=(1, 2, 3))
            sq[:, 1] += (ref_kv[:, :n].astype(np.float64) ** 2).sum(axis=(1, 2, 3))
    return [float(x) for x in np.sqrt(sq[:, 0] / sq[:, 1])]


def one_seed(ref, config, cfg, params, rows) -> dict:
    import numpy as np

    from benchmark import reference

    probe = config["run"]["probe"]
    lens, width = probe["prompt_lens"], probe["stripe"]
    prompts = [r[:n] for r, n in zip(rows, lens)]
    want = ref.forward_rows(params, prompts, last=1, kv_rows=range(len(lens)))
    want_logits = np.stack(want["logits"])[:, 0]
    # by expert layer [B, width, k], padded with zeros past each prompt
    theirs = []
    for layer in want["choices"]:
        full = np.zeros((len(lens), width, layer[0].shape[-1]), np.int32)
        for b, n in enumerate(lens):
            full[b, :n] = layer[b]
        theirs.append(full)
    out = {}
    for side, forced in (("free", None), ("forced", theirs)):
        logits, kv, mine = program_pass(params, cfg, rows, lens, width, forced)
        out[side] = {
            "logits_rel_rms": reference.rel_rms(logits, want_logits),
            "kv_rel_rms_by_layer": kv_by_layer(kv, want["kv"], lens),
            "swaps_by_expert_layer": [swaps(m, t, lens) for m, t in zip(mine, theirs)],
        }
    return out


def main():
    import jax

    from ray_tpu.llm import EngineConfig
    from ray_tpu.llm.config import resolve_llama_config

    from benchmark import common, compare, families

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=2147483000)
    args = parser.parse_args()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    family = families.load(config)
    run = config["run"]
    cfg = resolve_llama_config(family.served_model(config, 0),
                               EngineConfig(dtype=run["dtype"], **run["engine"]))
    ref = family.Reference(config, jax.local_devices()[:1])
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for seed in (args.first_seed + 7919 * i for i in range(args.seeds)):
        model_seed = seed % common.MODEL_SEED_MOD
        params = family.make_params(model_seed, config, cfg.dtype)
        rows = compare.probe_rows(model_seed, run["probe"])
        print(json.dumps({"seed": seed, **one_seed(ref, config, cfg, params, rows)}), flush=True)
        del params


if __name__ == "__main__":
    main()
