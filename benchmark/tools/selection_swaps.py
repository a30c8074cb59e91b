#!/usr/bin/env python3
"""How many of the positions an indexed layer's queries attend differ between
the program's arithmetic and the reference's: the floor that the choice of
``index_topk`` positions adds to ``logits_rel_rms``, as expert swaps do
(``benchmark/tools/routing_swaps.py``). Layer 0 alone, whose input is the
embedding itself and so exact on both sides: what differs is the indexer's
rounding (the program's served type against float32 at the highest precision).

    python3 benchmark/tools/selection_swaps.py --config dots3-note-prev-serve-l5-ep16 --seeds 2

For each seed the probe's long row goes through the program's own functions
(``models/patterned.py _latent_qkv``, ``_index_qkw``, ``_index_scores``,
``_kept``) and through the reference's (``benchmark/reference_sparse_latent.py``);
printed: of the queries past ``index_topk`` positions, the mean and the largest
number of their chosen positions that the other side did not choose, and the
share of queries with any."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common, compare, families
    from benchmark import reference_sparse_latent as ref
    from ray_tpu.llm import EngineConfig
    from ray_tpu.llm.config import resolve_llama_config
    from ray_tpu.models import patterned

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=2147483111)
    args = parser.parse_args()
    config = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))
    family = families.load(config)
    run = config["run"]
    cfg = resolve_llama_config(family.served_model(config, 0), EngineConfig(
        dtype=run["dtype"], **run["engine"]))
    k, eps = cfg.index_topk, float(config["rms_norm_eps"])
    dtype = jnp.bfloat16 if run["dtype"] == "bfloat16" else jnp.float32
    lay = patterned._Layer(patterned.plan(cfg), 0, 0, 0, 0, [0, 0, 0])
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)

    @jax.jit
    def program(params, tokens):
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
        x = params["embed"][tokens].astype(cfg.dtype)
        h = patterned._rmsnorm(x, params["attn_norm"][0], cfg.rms_eps)
        *_, cq = patterned._latent_qkv(params, lay, h, positions, cfg)
        q, w, key = patterned._index_qkw(params, lay, h, cq, positions, cfg)
        out = []
        for at in range(0, tokens.shape[1], ref.QUERY_BLOCK):  # a block of queries at a time
            rows = slice(at, at + ref.QUERY_BLOCK)
            scores = patterned._index_scores(q[:, rows], w[:, rows], key[:, :, 0])
            seen = positions[:, rows, None] >= positions[:, None, :]
            out.append(patterned._kept(jnp.where(seen, scores, -jnp.inf), k) & seen)
        return jnp.concatenate(out, axis=1)

    @jax.jit
    def reference(params, tokens):
        with jax.default_matmul_precision("highest"):
            f32 = lambda name: params[name][0].astype(jnp.float32)  # noqa: E731
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
            h = ref.rmsnorm(params["embed"][tokens].astype(jnp.float32), f32("attn_norm"), eps)
            s_q = (cfg.d_model / cfg.q_latent_rank) ** 0.5 if cfg.latent_rescale else 1.0
            c_q = s_q * ref.rmsnorm(h @ f32("wqa_latent"), f32("q_norm_latent"), eps)
            leaves = {name: f32(name) for name in (
                "index_wq", "index_wk", "index_k_norm", "index_k_bias", "index_ww")}
            q, key, w = ref.index_parts(
                h, c_q, leaves, positions, theta=float(config["rope_theta"]),
                rope=config["qk_rope_head_dim"], eps=eps, index_heads=cfg.index_heads,
                index_dim=cfg.index_head_dim)
            out = []
            for at in range(0, tokens.shape[1], ref.QUERY_BLOCK):
                rows = slice(at, at + ref.QUERY_BLOCK)
                allowed = positions[:, rows, None] >= positions[:, None, :]
                out.append(ref.chosen(ref.index_scores(q[:, rows], key, w[:, rows]), allowed, k))
            return jnp.concatenate(out, axis=1)

    for i in range(args.seeds):
        seed = (args.first_seed + 7919 * i) % common.MODEL_SEED_MOD
        params = family.make_params(seed, config, dtype)
        row = max(compare.probe_rows(seed, run["probe"]), key=len)[None]
        got, want = (np.asarray(f(params, jnp.asarray(row)))[0] for f in (program, reference))
        past = np.arange(row.shape[1]) >= k  # queries for which the choice binds
        missed = (want & ~got).sum(axis=-1)[past]
        print(json.dumps({
            "seed": seed, "queries_past_topk": int(past.sum()), "chosen_a_query": k,
            "differing_positions_mean": float(missed.mean()) if past.any() else 0.0,
            "differing_positions_largest": int(missed.max()) if past.any() else 0,
            "queries_with_any_share": float((missed > 0).mean()) if past.any() else 0.0,
        }), flush=True)
        del params


if __name__ == "__main__":
    main()
