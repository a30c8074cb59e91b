#!/usr/bin/env python3
"""A serving cell's middle-chunk program with one row and with several
(``llm/engine.py _advance_admissions``: a pool's admissions whose next chunk
is a middle chunk are rows of one launch), on the chip, at the cell's own
shapes and on seeded weights:

- what the engine's constructor costs, which runs its whole program set
  (``_warm_programs``): seconds, and the programs compiled or fetched from the
  compile cache with the seconds JAX reports for them. Run the tool twice in
  one call for a cold and a warm reading;
- whether a row is bit-equal alone and beside a companion: two prompts (one
  middle chunk, and three) through ``jit_chunk_mid`` as single rows, then with
  the long prompt's third chunk beside the short one's first (unlike starts),
  each followed by its own final chunk; the first tokens and every key and
  value a slot holds compared bit for bit;
- what a launch of one, two and four rows costs at the starts ``--at`` names
  (in chunks), and of two rows at unlike starts (host clock round ``--runs``
  launches, the device drained at both ends), and a final chunk beside them.

    python3 benchmark/tools/chunk_rows.py --config laguna-xs.2-serve-l5
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def main():
    import jax
    import jax.monitoring
    import numpy as np

    from ray_tpu._private import jax_cache
    from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig

    from benchmark import common, families

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--runs", type=int, default=10)
    parser.add_argument("--tail", type=int, default=200, help="tokens of each final chunk")
    parser.add_argument("--at", type=int, nargs="+", default=[1],
                        help="time the middle chunk at these chunk indices (its start over the chunk)")
    args = parser.parse_args()
    jax_cache.configure()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    family, run = families.load(config), config["run"]
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **kw: compiled.append((str(kw.get("fun_name")), seconds))
        if event == COMPILE_EVENT else None)

    t = time.perf_counter()
    eng = JaxEngine(LLMConfig(model=family.served_model(config, args.seed),
                              engine=EngineConfig(dtype=run["dtype"], **run["engine"])))
    init_s = time.perf_counter() - t
    chunks = [round(s, 3) for name, s in compiled if "chunk_" in name]
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "engine_constructor_s": init_s,
        "engine_init_s": eng.get_stats()["engine_init_s"],
        "programs_compiled_or_fetched": len(compiled), "their_seconds": sum(s for _, s in compiled),
        "chunk_programs": len(chunks), "chunk_programs_seconds": chunks,
    }), flush=True)
    shardings = {k: v.sharding for k, v in eng.params.items()}
    dtype = eng.params["embed"].dtype
    eng.params = None
    gc.collect()
    eng.params = family.make_params(args.seed, config, dtype, shardings)
    compiled.clear()

    pool = eng._pools[-1]
    chunk = eng.config.engine.prefill_chunk
    rng = np.random.default_rng(args.seed)
    lengths = (chunk + args.tail, 3 * chunk + args.tail - 7)
    prompts = [rng.integers(0, 256, n, dtype=np.int32) for n in lengths]
    width = eng._bucket(args.tail)

    def rows_of(which, at):
        """The launch arguments of prompt ``which[i]``'s chunk ``at[i]``."""
        pieces = [prompts[w][a * chunk:(a + 1) * chunk] for w, a in zip(which, at)]
        final = len(pieces[0]) < chunk
        toks = np.zeros((len(which), width if final else chunk), np.int32)
        for row, piece in zip(toks, pieces):
            row[:len(piece)] = piece
        return dict(toks=toks, lens=[len(p) for p in pieces], starts=[a * chunk for a in at],
                    adapters=[0] * len(which))

    def final(one, w, slot):
        """Prompt ``w``'s final chunk into ``slot``; its first token, still on the device."""
        (row,) = (rows_of([w], [lengths[w] // chunk]),)
        return eng._run_chunk_final(
            pool, one, row["toks"], row["lens"][0], row["starts"][0], slot, 0.0, 1, 0, 0)[0]

    def slot_bits(slot, n):
        return [np.asarray(pool.cache[k][:, slot, :, :n].astype("float32")) for k in ("k", "v")]

    new = lambda: eng._new_stripe_jit(pool.stripe_len)  # noqa: E731
    mid = lambda ones, which, at: eng._run_chunk_mid(ones=ones, **rows_of(which, at))  # noqa: E731
    # each prompt alone, a row a launch
    alone = []
    for w, slot in ((0, 0), (1, 1)):
        one = (new(),)
        for a in range(lengths[w] // chunk):
            one = mid(one, [w], [a])
        alone.append((int(np.asarray(final(one[0], w, slot))), slot_bits(slot, lengths[w])))
    # paired: the long prompt's first two middle chunks alone, then its third
    # beside the short one's first; then each prompt's final chunk
    long_one = mid(mid((new(),), [1], [0]), [1], [1])
    ones = mid((new(), long_one[0]), [0, 1], [0, 2])
    for w, slot in ((0, 2), (1, 3)):
        tok, bits = alone[w]
        paired = int(np.asarray(final(ones[w], w, slot)))
        got = slot_bits(slot, lengths[w])
        print(json.dumps({
            "row": w, "prompt_tokens": lengths[w], "final_width": width,
            "first_token_alone": tok, "first_token_paired": paired,
            "differing": {name: {"elements": int((a != b).sum()), "of": int(a.size),
                                 "layers": sorted({int(i) for i in np.nonzero(a != b)[0]}),
                                 "largest": float(np.abs(a - b).max()),
                                 "largest_value": float(np.abs(a).max())}
                          for name, a, b in zip(("k", "v"), bits, got)},
        }), flush=True)

    def timed(launch, runs):
        jax.block_until_ready(pool.cache)
        t = time.perf_counter()
        out = None
        for i in range(runs):
            out = launch(i)
        jax.block_until_ready((out, pool.cache))
        return 1e3 * (time.perf_counter() - t) / runs

    fresh = [new() for _ in range(args.runs + 1)]  # a final chunk's stripe is donated
    run_final = lambda i: final(fresh[i], 1, 0)  # noqa: E731
    run_final(args.runs)
    print(json.dumps({"chunk_final_ms": timed(run_final, args.runs), "final_width": width}),
          flush=True)
    del fresh
    # the program's cost does not know the tokens: a chunk of the long prompt at any start
    for at in args.at:
        for starts in ([at], [at] * 2, [at] * 4, [1, at]):
            rows = len(starts)
            state = [tuple(new() for _ in range(rows))]

            def run_mid(i):
                row = rows_of([1] * rows, [1] * rows)  # noqa: B023
                row["starts"] = [a * chunk for a in starts]  # noqa: B023
                state[0] = eng._run_chunk_mid(ones=state[0], **row)  # noqa: B023
                return state[0]  # noqa: B023

            run_mid(0)
            print(json.dumps({
                "rows": rows, "starts": [a * chunk for a in starts],
                "chunk_mid_ms": timed(run_mid, args.runs),
                "compiled_after_ready": [name for name, _ in compiled],
            }), flush=True)
    eng.shutdown()


if __name__ == "__main__":
    main()
