#!/usr/bin/env python3
"""What a prompt seeded from a stored snapshot leaves, against the same prompt
computed whole, on the chip at a cell's own size: the control of the check
sessions in ``benchmark/kinds/sessions.py``. One process that holds the chip;
an engine of the cell's settings with the family's weights from the seed (no
Serve around it). For each check session of the traffic file:

    miss   turn 2 with nothing stored: its 16 greedy tokens and the snapshot it leaves
    sound  turn 1, then turn 2 seeded from turn 1's snapshot at its exact length
    other  the same, with turn 1's snapshot swapped for another session's turn 1
    tail   the same, with the convolution tails of turn 1's snapshot zeroed
    bf16   the same, with turn 1's state rounded to bfloat16 and back

and for each of the four seeded runs how far the snapshot it leaves lies from
the miss's, by the kind's own ``snapshot_distance`` (the state leaves; the keys
and values of the positions behind turn 1's length), whether that is ``within``
the traffic file's limits (the rule that decides ``correct`` there), and how
many of its 16 tokens lead as the miss's do (logged by the kind, deciding
nothing). ``sound`` must come out within, ``other`` and ``tail`` not; the last
line says whether they did. ``bf16`` lies inside a hit's own rounding: no run
on the chip separates it, the CPU tests do (tests/test_snapshot_prefix.py).

    python3 benchmark/tools/snapshot_control.py --config granite-4.0-h-micro-serve-l40 \\
        --traffic sessions-closed-48 --sessions 4
"""

import argparse
import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.config import SamplingParams
    from ray_tpu.llm.engine import JaxEngine

    from benchmark import common, compare, families, serving
    from benchmark.kinds import sessions

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--sessions", type=int, default=4)
    parser.add_argument("--seed", type=int, default=2347483000)
    args = parser.parse_args()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    traffic = common.load_traffic(args.traffic)
    budget = traffic["hit_check_max_tokens"]
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    engine = JaxEngine(serving.make_llm_config(config, args.seed, rehearsal=True))
    try:
        model_seed = args.seed % common.MODEL_SEED_MOD
        shardings = {k: v.sharding for k, v in engine.params.items()}
        dtype = engine.params["embed"].dtype
        engine.params = None
        engine.params = families.load(config).make_params(model_seed, config, dtype, shardings)
        made = sessions.Sessions(traffic, args.seed)
        greedy = SamplingParams(max_tokens=budget, temperature=0.0, ignore_eos=True)

        def ids_of(req):
            return engine.tokenizer.encode(req["prompt"])

        def send(ids):
            out = engine.generate(prompt_token_ids=ids, sampling_params=greedy)
            key = hashlib.sha1(np.asarray(ids, np.int32).tobytes()).digest()
            return list(out.token_ids), out.metrics["prefix_hit_tokens"], engine._prefix_cache[key]

        def leading(a, b):
            return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), min(len(a), len(b)))

        spoils = {
            "sound": lambda entry, theirs: None,
            "other": lambda entry, theirs: entry.update({n: theirs[n] for n in ("k", "v", "state")}),
            "tail": lambda entry, theirs: entry.update(state={
                n: jnp.zeros_like(x) if n.endswith("conv") else x for n, x in entry["state"].items()}),
            "bf16": lambda entry, theirs: entry.update(state={
                n: x.astype(jnp.bfloat16).astype(x.dtype) if n.endswith("state") else x
                for n, x in entry["state"].items()}),
        }
        within = {name: [] for name in spoils}
        for j in range(args.sessions):
            one, two = ids_of(made.turn(-1 - j, 0)), ids_of(made.turn(-1 - j, 1))
            other_one = ids_of(made.turn(-1001 - j, 0))
            compare.forget_prefixes(engine)
            want_tokens, hit, want = send(two)
            assert hit == 0
            line = {"session": j, "turn1_tokens": len(one), "turn2_tokens": len(two),
                    "miss_tokens": want_tokens}
            for name, spoil in spoils.items():
                compare.forget_prefixes(engine)
                _, _, theirs = send(other_one)
                _, _, entry = send(one)
                spoil(entry, theirs)
                tokens, hit, left = send(two)
                assert hit == len(one), (hit, len(one))
                distance = sessions.snapshot_distance(left, want, len(one))
                line[name] = dict(distance, within=sessions.within(distance, traffic),
                                  same_leading=leading(tokens, want_tokens))
                within[name].append(line[name]["within"])
            print(json.dumps(line), flush=True)
        print(json.dumps({
            "within": {name: sum(w) for name, w in within.items()}, "sessions": args.sessions,
            "the_rule_holds": all(within["sound"]) and not any(within["other"] + within["tail"]),
        }), flush=True)
    finally:
        engine.shutdown()


if __name__ == "__main__":
    main()
