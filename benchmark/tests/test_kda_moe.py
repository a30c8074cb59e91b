"""Family ``kda_moe`` (PR 42): its configuration file against its own
``published`` block and the catalog row, its traffic mix, its weights and int8
control, the counts of the whole model and of what a step needs against
hand-worked numbers at the published widths, the reference's delta-rule layer
written out, every reader of the new per-layer metrics on a hand-made trace
and the engine's counters, and the rehearsal cell end to end on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common, scopes, traffic, window_counts
from benchmark.families import kda_moe as family
from benchmark.tests.test_manifest import check_config_file

MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
CELL = "solar-open2-serve-long-chat"
NAME = "solar-open2-250b-serve-l4-ep8"
CONFIG = common.load_config(MANIFEST, NAME)
MIX = common.load_traffic("long-chat-closed-128")
TINY = common.load_json(os.path.join(common.BENCH_DIR, "configs", "rehearse-kda-moe-serve.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
NEW_READERS = [
    "kernel.kda_decode_hbm_share.window", "kernel.kda_prefill_roofline_share",
    "kernel.moe_decode_hbm_share.kda_moe.window", "kernel.moe_prefill_roofline_share.kda_moe",
    "kernel.decode_gated_attention_hbm_share.window", "program.decode_hbm_share.kda_moe.window",
]
APPENDED_BESIDE = ["program.prefill_chunk_ms", "program.prefill_final_chunk_ms",
                   "program.moe_held_assignment_share", "engine.state_bytes_per_slot",
                   "kernel.decode_read_efficiency.window"]
OTHER_SERVING_CELLS = ["mistral7b-serve-saturated", "laguna-xs2-serve-mixed",
                       "kanana2-serve-docs-shared", "nemotron3-super-serve-chat"]


# ------------------------------------------------------------- the data files


def test_configuration_file_passes_the_manifest_check():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED == CONFIG["reduced"] and MANIFEST["configs"][-1] is entry
    check_config_file(CONFIG, REDUCED)
    changed = {k for k, v in CONFIG["published"].items() if CONFIG[k] != v}
    assert changed == set(REDUCED)
    # layers 1-4 of 48: three delta-rule layers and the attention layer behind them
    assert (CONFIG["num_hidden_layers"], CONFIG["gqa_layers"]) == (4, [3])
    assert CONFIG["published"]["gqa_layers"] == list(range(0, 48, 4))
    whole = family.layer_rows(CONFIG["published"])
    assert (whole["kda"], whole["full"], whole["sparse"]) == (36, 12, 48)
    assert family.layer_rows(CONFIG) == {"kda": 3, "full": 1, "sparse": 4, "all": 4}
    # the chip's share: an eighth of the experts and of the vocabulary; the router keeps all 320
    assert (CONFIG["n_routed_experts"], family.router_experts(CONFIG)) == (40, 320)
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"] and CONFIG["vocab_size"] > 258
    assert CONFIG["num_experts_per_tok"] == 8 and CONFIG["run"]["experts_first"] == 0
    assert CONFIG["linear_attn_config"] == CONFIG["published"]["linear_attn_config"]
    assert set(CONFIG["assumed"]) >= {
        "kda_rank", "kda_bias", "kda_decay", "kda_beta", "kda_qk", "kda_out", "kda_precision",
        "attention_gate", "attention", "router", "selection_bias", "experts", "initialisation",
        "tokenizer", "engine"}
    assert "8 chips" in CONFIG["deployment"] and "12 pipeline stages" in CONFIG["deployment"]
    engine, probe = CONFIG["run"]["engine"], CONFIG["run"]["probe"]
    assert (engine["max_num_seqs"], engine["max_seq_len"], engine["prefill_chunk"]) == (64, 8192, 1024)
    assert set(engine) == {"max_num_seqs", "max_seq_len", "prefill_buckets", "prefill_chunk"}
    # one probe prompt is a final chunk alone, the other two middle chunks and a final one
    assert min(probe["prompt_lens"]) <= engine["prefill_buckets"][0]
    assert max(probe["prompt_lens"]) > 2 * engine["prefill_chunk"]
    assert (probe["stripe"] + probe["decode_steps"]) % 128 == 0  # whole kernel blocks
    assert probe["stripe"] % 64 == 0 and max(probe["prompt_lens"]) % 64  # whole rule chunks, a ragged prompt
    assert set(CONFIG["run"]["limits"]) == {"kv_prefill_rel_rms", "kv_decode_rel_rms", "logits_rel_rms"}
    assert probe["decode_steps"] == 192  # as PR 35 found the two decode numbers need


def test_published_block_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
    assert CONFIG["published"] == row["config"]
    assert CONFIG["source"] == row["source_url"]
    assert set(row["config"]) <= set(CONFIG)


def test_rehearsal_fixture_passes_the_check_and_cannot_pass_for_the_benchmark():
    rehearsal = common.load_manifest(os.path.join(common.BENCH_DIR, "rehearsal-kda-moe.json"))
    assert rehearsal["rehearsal"] is True
    assert not {c["name"] for c in rehearsal["workloads"]} & {c["name"] for c in MANIFEST["workloads"]}
    (entry,) = rehearsal["configs"]
    check_config_file(common.load_json(os.path.join(common.ROOT, entry["file"])), ["n_routed_experts"])
    (cell,) = rehearsal["workloads"]
    assert common.load_traffic(cell["traffic"])["kind"] == MIX["kind"] == "closed_loop"
    assert family.model_kwargs(TINY)["moe_experts_held"] == 4 and family.router_experts(TINY) == 16


def test_cell_and_metric_entries():
    cell = common.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "long-chat-closed-128", 1)
    assert MANIFEST["workloads"][-1] is cell and len(MANIFEST["workloads"]) == 6
    assert [m["name"] for m in MANIFEST["per_layer"][-6:]] == NEW_READERS
    shared = 0
    for m in MANIFEST["per_layer"]:
        assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", m["name"] + ".py"))
        if m["name"] in NEW_READERS:
            assert (m["workloads"], m["moves"], m["unit"]) == ([CELL], "serve_tok_s", "%")
        elif m.get("workloads", [])[:4] == OTHER_SERVING_CELLS:
            assert m["workloads"] == OTHER_SERVING_CELLS + [CELL]
            shared += 1
        elif m["name"] in APPENDED_BESIDE:
            assert m["workloads"][-1] == CELL
        else:
            assert CELL not in m["workloads"]
    assert shared == 19
    assert [m["name"] for m in common.metrics_for(MANIFEST, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]
    serve = next(m for m in MANIFEST["end_to_end"] if m["name"] == "serve_tok_s")
    assert serve["workloads"] == OTHER_SERVING_CELLS + [CELL] and serve["bound"] == 0.04


def test_traffic_mix_is_the_one_the_issue_names():
    assert (MIX["kind"], MIX["clients"], MIX["stream"], MIX["pool"], MIX["temperature"],
            MIX["ramp_seconds"], MIX["trace_seconds"]) == ("closed_loop", 128, False, 128, 0.0, 5, 6)
    assert MIX["prompt_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.9, "min": 128, "max": 4096}
    assert MIX["max_tokens"] == {"dist": "lognormal", "median": 160, "sigma": 0.5, "min": 64, "max": 448}
    engine = CONFIG["run"]["engine"]
    assert (engine["max_num_seqs"], MIX["clients"]) == (64, 2 * engine["max_num_seqs"])
    assert MIX["prompt_tokens"]["max"] + MIX["max_tokens"]["max"] + 1 <= engine["max_seq_len"]

    def programs_of(n):  # (middle chunks, the final chunk's width)
        mid, last = divmod(n - 1, engine["prefill_chunk"])
        return min(mid, 1), next(b for b in engine["prefill_buckets"] if last + 1 <= b)

    prompts = traffic.stratified(MIX["prompt_tokens"], MIX["pool"])
    # the warm-up reaches every final-chunk width alone and behind a middle chunk
    assert {programs_of(n) for n in MIX["warmup_prompt_tokens"]} == {programs_of(n) for n in prompts}
    assert len({programs_of(n) for n in prompts}) == 8
    answers = traffic.stratified(MIX["max_tokens"], MIX["pool"])
    assert (min(prompts), max(prompts), min(answers), max(answers)) == (128, 4096, 64, 448)
    # ISSUE 42's fallback for a spread over 2%: prompts clipped at 4,096, nothing else changed
    assert 1340 < sum(prompts) / 128 < 1440 and 170 < sum(answers) / 128 < 190
    one_chunk = sum(n <= engine["prefill_chunk"] for n in prompts)
    assert 60 <= one_chunk <= 68  # half are one final chunk
    assert max((n - 1) // engine["prefill_chunk"] for n in prompts) == 3  # at most three middle chunks
    for seed in (3, 2**31 + 12345):  # every seed the same sizes, in another order
        reqs = traffic.Requests(MIX, seed, MIX["pool"])
        assert sorted(p for p, _ in reqs.sizes) == sorted(prompts)
        assert len(reqs[7]["prompt"]) == reqs.sizes[7][0] - 1  # BOS and n - 1 bytes


# ------------------------------------------------------- weights and counts


def test_parameter_shapes_count_the_model_whole_and_the_cut():
    c = CONFIG
    shapes = family.param_shapes(c)
    assert family.param_count(c) == 3_308_353_344  # the issue's 3,308 M: 6.62 GB in bfloat16
    assert family.kda_dims(c) == {"inner": 8192, "rank": 128, "conv": 24576, "proj": 24896}
    assert shapes["kda_w_in"] == ((3, 4096, 24896), 4096) and shapes["kda_w_out"] == ((3, 8192, 4096), 8192)
    assert shapes["kda_conv_w"] == ((3, 4, 24576), 4) and shapes["kda_norm"] == ((3, 128), None)
    assert shapes["kda_w_decay"] == shapes["kda_w_gate"] == ((3, 128, 8192), 128)
    assert shapes["kda_dt_bias"] == ((3, 8192), "dt_bias") and shapes["kda_a_log"] == ((3, 64), "a_log")
    assert shapes["moe_w_gate"] == shapes["moe_w_up"] == ((4, 40, 4096, 1280), 4096)
    assert shapes["moe_w_down"] == ((4, 40, 1280, 4096), 1280)
    assert shapes["moe_router"][0] == (4, 4096, 320) and shapes["moe_router_bias"][0] == (4, 320)
    assert shapes["moe_shared_up"][0] == (4, 4096, 1280)
    assert shapes["wq_full"][0] == (1, 4096, 64, 128) and shapes["wk"][0] == (1, 4096, 8, 128)
    assert shapes["wg_full"] == ((1, 4096, 8192), 4096)  # the gate a channel
    assert shapes["attn_norm"][0] == shapes["mlp_norm"][0] == (4, 4096)
    assert shapes["embed"] == ((24576, 4096), 1.0) and shapes["unembed"][0] == (4096, 24576)
    # W_in 101.97 M, W_o 33.55 M, the low ranks' second halves, 4 taps of 24,576, dt_bias, A_log, the norm
    assert family.kda_params(c) == (101_974_016 + 33_554_432 + 2 * 1_048_576 + 98_304 + 8192 + 64
                                    + 128) == 137_732_288  # the issue's 137.7 M
    assert family.attention_params(c) == 4096 * 128 * (3 * 64 + 2 * 8) == 109_051_904  # 109.1 M
    assert family.expert_params(c) == 3 * 4096 * 1280 == 15_728_640  # the catalog's "16 M each"
    assert family.moe_fixed_params(c) == 1_310_720 + 320 + 15_728_640 == 17_039_680
    # the model whole: 250.29 B parameters, 14.74 B active a token (the published "250B-A15B")
    whole = family.whole_model_params(c)
    assert whole == family.whole_model_params(c["published"])
    assert whole["total"] == family.param_count(c["published"]) == 250_287_810_304
    assert whole["active"] == 14_735_697_664
    assert whole["total"] - whole["active"] == 48 * (320 - 8) * 15_728_640


def test_needed_bytes_and_operations():
    c = CONFIG
    assert family.state_bytes_per_slot(c) == 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2) == 13_025_280
    assert family.kv_bytes_per_token(c) == 2 * 8 * 128 * 2 == 4096
    # three mixers' weights once, 60 live rows' state and tails read and written
    assert family.kda_decode_bytes(c, 60) == 2 * 3 * 137_732_288 + 2 * 60 * 13_025_280 == 2_389_427_328
    # four layers of router and shared expert, 32 of the 40 held experts touched in each
    banks = family.moe_needed_bytes(c, 4, 4 * 32)
    assert banks == 2 * (4 * 17_039_680 + 128 * 15_728_640) == 4_162_849_280
    assert family.moe_needed_bytes(c, 4, 4 * 40) > banks
    # the one attention layer's five projections, 100,000 live tokens' keys and values
    assert family.attention_decode_bytes(c, 100_000) == 2 * 109_051_904 + 100_000 * 4096 == 627_703_808
    # with eight layer norms, the final one and the head; no embedding table
    step = family.decode_step_bytes(c, 60, 32, 100_000)
    assert step == 2_389_427_328 + 627_703_808 + banks + 2 * (8 * 4096 + 4096 + 24576 * 4096)
    assert step == 7_381_380_736  # the issue's 7.6 GB at 64 rows and 108,800 tokens
    # a token of the rule in chunks of 64: 31.5 and 32.5 pair weights of 128 products, the solve
    # applied to 31.5 rows of V + K, three passes of K V over the state, 32.5 rows of V read
    a_token = 31.5 * 128 + 32.5 * 128 + 31.5 * 256 + 3 * 128 * 128 + 32.5 * 128
    assert family.kda_scan_flops(c, 1) == 2 * 64 * a_token == 8_904_704
    assert family.kda_scan_flops(c, 1024) == 1024 * 8_904_704  # 9.1 GFLOP a layer: the issue's 29 for three
    # q k v in bfloat16, the decay a channel and beta a head in float32, o out; two rows' state twice
    assert family.kda_scan_bytes(c, 1000, 2) == 1000 * (49_152 + 33_024 + 32_768) + 4 * 4_194_304 == 131_721_216
    # an expert layer run: router over all 320 and the shared expert a token, an expert a held assignment
    assert family.moe_needed_flops(c, 4, 300, 300) == 2 * 4 * (300 * (1_310_720 + 15_728_640) + 300 * 15_728_640)


def test_weights_from_a_seed_and_the_int8_control():
    import jax
    import jax.numpy as jnp

    a = family.make_params(11, TINY, jnp.float32)
    b = family.make_params(11, TINY, jnp.float32)
    c = family.make_params(12, TINY, jnp.float32)
    assert {k: v.shape for k, v in a.items()} == {k: s for k, (s, _) in family.param_shapes(TINY).items()}
    assert all(np.array_equal(a[k], b[k]) for k in a) and not np.array_equal(a["kda_w_in"], c["kda_w_in"])
    assert abs(float(jnp.std(a["moe_w_down"])) / 32 ** -0.5 - 1) < 0.05
    assert abs(float(jnp.std(a["kda_w_out"])) / 64 ** -0.5 - 1) < 0.05
    assert abs(float(jnp.std(a["embed"])) - 1) < 0.05  # unit embedding rows
    step = np.asarray(jax.nn.softplus(a["kda_dt_bias"]))
    assert (step >= 0.001 * 0.999).all() and (step <= 0.1 * 1.001).all()
    rate = np.exp(np.asarray(a["kda_a_log"]))
    assert (rate >= 1).all() and (rate <= 16).all()
    bank = np.asarray(a["moe_w_up"])
    cut = family.int8_roundtrip(jax.tree.map(jnp.copy, a))
    for left_alone in ("attn_norm", "kda_norm", "moe_router_bias", "kda_dt_bias", "kda_a_log"):
        assert np.array_equal(cut[left_alone], a[left_alone]), left_alone
    err = np.abs(np.asarray(cut["moe_w_up"]) - bank)
    scale = np.abs(bank).max(axis=2, keepdims=True) / 127.0  # one scale an expert and output column
    assert (err <= 0.5 * scale + 1e-7).all() and err.max() > 0
    for cut_too in ("kda_w_in", "kda_w_out", "kda_conv_w", "kda_w_decay", "kda_w_gate", "wg_full",
                    "moe_shared_up", "wk", "embed"):
        assert not np.array_equal(cut[cut_too], a[cut_too]), cut_too


# ---------------------------------------------------------- the reference


def test_reference_delta_rule_layer_is_the_recurrence_written_out():
    """``kda_part`` on six tokens against the same in NumPy, loop by loop:
    the convolutions' zeros before the first token, unit keys, the decay a
    channel, ``S'^T k`` taken out before the write, the norm before the gate."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_kda_moe as ref

    H, d, e, rank, T = 2, 4, 8, 4, 6
    rng = np.random.default_rng(0)
    w = {
        "norm": np.ones(e, np.float32),
        "kda_w_in": rng.normal(size=(e, 3 * H * d + 2 * rank + H)).astype(np.float32) * 0.5,
        "kda_conv_w": rng.normal(size=(4, 3 * H * d)).astype(np.float32) * 0.5,
        "kda_w_decay": rng.normal(size=(rank, H * d)).astype(np.float32),
        "kda_dt_bias": rng.normal(size=(H * d,)).astype(np.float32),
        "kda_a_log": np.log(rng.uniform(1, 16, size=(H,))).astype(np.float32),
        "kda_w_gate": rng.normal(size=(rank, H * d)).astype(np.float32),
        "kda_norm": rng.uniform(0.5, 1.5, size=(d,)).astype(np.float32),
        "kda_w_out": rng.normal(size=(H * d, e)).astype(np.float32) * 0.3,
    }
    x = rng.normal(size=(1, T, e)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.kda_part(jnp.asarray(x), {k: jnp.asarray(v) for k, v in w.items()},
                                      heads=H, head_dim=d, eps=1e-5))

    def silu(a):
        return a / (1 + np.exp(-a))

    def sigmoid(a):
        return 1 / (1 + np.exp(-a))

    u = x[0] / np.sqrt((x[0] ** 2).mean(-1, keepdims=True) + 1e-5)
    proj = u @ w["kda_w_in"]
    inner = H * d
    qkv, f, z, b = np.split(proj, [3 * inner, 3 * inner + rank, 3 * inner + 2 * rank], axis=1)
    padded = np.concatenate([np.zeros((3, 3 * inner), np.float32), qkv])
    qkv = silu(sum(w["kda_conv_w"][j] * padded[j:j + T] for j in range(4)))
    q, k, v = (qkv[:, j * inner:(j + 1) * inner].reshape(T, H, d) for j in range(3))
    q = q / np.sqrt((q ** 2).sum(-1, keepdims=True) + 1e-6) * d ** -0.5
    k = k / np.sqrt((k ** 2).sum(-1, keepdims=True) + 1e-6)
    g = -np.exp(w["kda_a_log"])[:, None] * np.log1p(np.exp(f @ w["kda_w_decay"] + w["kda_dt_bias"])).reshape(T, H, d)
    beta = 2 * sigmoid(b)
    S = np.zeros((H, d, d))
    out = np.zeros((T, H, d))
    for t in range(T):
        for h in range(H):
            Sp = np.exp(g[t, h])[:, None] * S[h]
            S[h] = Sp + beta[t, h] * np.outer(k[t, h], v[t, h] - Sp.T @ k[t, h])
            out[t, h] = S[h].T @ q[t, h]
    out = out / np.sqrt((out ** 2).mean(-1, keepdims=True) + 1e-5) * w["kda_norm"]
    out = out.reshape(T, inner) * sigmoid(z @ w["kda_w_gate"])
    np.testing.assert_allclose(got[0], x[0] + out @ w["kda_w_out"], atol=2e-5, rtol=1e-4)


# ------------------------------------------------------------- the readers


def synthetic():
    """Two decode steps, one middle and one final chunk inside a 1 s window,
    milliseconds in round numbers; and the window's own counters: 10 decode
    steps over 60 live rows and 100,000 live tokens each, touching 32 of the
    40 held experts of each of 4 layers; 5 middle launches of 2 rows and 1,800
    real tokens, 4 final chunks of 300."""
    d, m, f = "jit(decode_fn)/", "jit(chunk_mid)/", "jit(chunk_final)/"
    ops = []

    def add(start, ms, op_name):
        ops.append((start, start + ms * 1e-3, "fusion", op_name))
        return start + ms * 1e-3

    for step_start in (0.0, 0.1):
        t = step_start
        t = add(t, 1.0, d + "attn_qkv/kda_mixer/bte,ef->btf/dot_general")
        t = add(t, 0.5, d + "attn_core/kda_mixer/kda_conv/mul")
        t = add(t, 3.0, d + "attn_core/kda_mixer/kda_step/kda_step")
        t = add(t, 0.5, d + "attn_out/kda_mixer/btf,fe->bte/dot_general")
        t = add(t, 0.3, d + "attn_qkv/bte,ehd->bthd/dot_general")
        t = add(t, 0.5, d + "attn_core/global/decode_attention")
        t = add(t, 0.2, d + "attn_out/gate/dot_general")
        t = add(t, 0.2, d + "kv_write/scatter")
        t = add(t, 6.0, d + "moe_ffn/experts/gmm")
        t = add(t, 0.5, d + "moe_ffn/router/dot_general")
        t = add(t, 0.5, d + "moe_ffn/shared_expert/dot_general")
        t = add(t, 0.3, d + "lm_head/dot_general")
    t = add(0.2, 4.0, m + "attn_core/kda_mixer/kda_scan/dot_general")
    t = add(t, 8.0, m + "moe_ffn/experts/gmm")
    t = add(0.3, 1.0, f + "attn_core/kda_mixer/kda_scan/dot_general")
    t = add(t, 0.3, f + "attn_core/kda_mixer/kda_conv/mul")
    t = add(t, 10.0, f + "moe_ffn/experts/gmm")
    parsed = {
        "window": (0.0, 1.0), "spans": [],
        "modules": [(0.0, 0.0135, "jit_decode_fn"), (0.1, 0.1135, "jit_decode_fn"),
                    (0.2, 0.212, "jit_chunk_mid"), (0.3, 0.3113, "jit_chunk_final")],
        "ops": sorted(ops),
    }
    counters = {
        "decode_steps": 10, "decode_slot_steps": 10 * 60, "decode_kv_tokens_global": 10 * 100_000,
        "prefill_chunks": {"mid": 10, "final": 4}, "prefill_programs": {"mid": 5, "final": 4},
        "prefill_query_tokens": {"chunk_mid": 5 * 1800, "chunk_final": 4 * 300},
        "moe_layer_steps": {"decode": 40, "chunk_mid": 20, "chunk_final": 16},
        "moe_assignments": {"decode": 40 * 480, "chunk_mid": 20 * 16384, "chunk_final": 16 * 512 * 8},
        "moe_assignments_held": {"decode": 40 * 60, "chunk_mid": 20 * 2048, "chunk_final": 16 * 512},
        "moe_experts_touched": {"decode": 40 * 32, "chunk_mid": 20 * 40, "chunk_final": 16 * 40},
        "moe_max_expert_load_sum": {"decode": 40 * 4, "chunk_mid": 20 * 80, "chunk_final": 16 * 30},
    }
    ctx = {
        "cell": {"name": CELL}, "config": CONFIG, "device_kind": "TPU v5 lite",
        "trace": {"busy_s": 0.0503, "window_s": 1.0, "modules": {
            "jit_decode_fn": {"count": 2, "total_s": 0.027},
            "jit_chunk_mid": {"count": 1, "total_s": 0.012},
            "jit_chunk_final": {"count": 1, "total_s": 0.0113}}},
        "extra": {"stats_at_end": {"counters": counters, "max_num_seqs": 64,
                                   "pools": [{"state_bytes_per_slot": 13_025_280}]}},
        "samples": [],
    }
    return parsed, ctx


def test_new_readers_on_a_hand_made_trace(monkeypatch):
    parsed, ctx = synthetic()
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: parsed)
    # the window's own counts are the context's: no .xplane.pb behind a hand-made trace
    monkeypatch.setattr(window_counts, "windowed", lambda c: c)
    read = {name: common.load_reader(name)(ctx) for name in NEW_READERS}
    bw, peak = 819e9, 197e12
    # three mixers' weights and 60 live rows' state, both ways, in the 5 ms under kda_mixer
    assert read["kernel.kda_decode_hbm_share.window"] == pytest.approx(100 * 2_389_427_328 / bw / 5e-3)
    # the 7 ms under moe_ffn against router, shared expert and 32 touched experts a layer
    assert read["kernel.moe_decode_hbm_share.kda_moe.window"] == pytest.approx(100 * 4_162_849_280 / bw / 7e-3)
    # the attention layer's own three scopes: 1.0 ms, nothing of kda_mixer's 5 or of kv_write
    assert read["kernel.decode_gated_attention_hbm_share.window"] == pytest.approx(100 * 627_703_808 / bw / 1e-3)
    assert read["program.decode_hbm_share.kda_moe.window"] == pytest.approx(100 * 7_381_380_736 / bw / 13.5e-3)
    # a middle launch of 2 rows and 1,800 real tokens, a final chunk of 300: the bytes bind both
    mid = 3 * max(1800 * 8_904_704 / peak, (1800 * 114_944 + 4 * 4_194_304) / bw)
    final = 3 * max(300 * 8_904_704 / peak, (300 * 114_944 + 2 * 4_194_304) / bw)
    assert 1800 * 8_904_704 / peak < (1800 * 114_944 + 4 * 4_194_304) / bw
    assert read["kernel.kda_prefill_roofline_share"] == pytest.approx(100 * (mid + final) / 5e-3)
    # a final chunk's 300 real tokens, an eighth of their 8 choices held: all 40 experts' bytes bind
    flops = 2 * 4 * (300 * 17_039_360 + 300 * 15_728_640)
    every = 2 * (4 * 17_039_680 + 160 * 15_728_640)
    assert flops / peak < every / bw
    assert read["kernel.moe_prefill_roofline_share.kda_moe"] == pytest.approx(100 * every / bw / 10e-3)
    assert all(0 < read[n] <= 100 for n in NEW_READERS)
    # the accepted readers this cell was appended to hold for it unedited
    assert common.load_reader("engine.state_bytes_per_slot")(ctx) == 13_025_280
    assert common.load_reader("program.moe_held_assignment_share")(ctx) == pytest.approx(12.5)
    assert common.load_reader("program.prefill_chunk_ms")(ctx) == pytest.approx(12.0)
    assert common.load_reader("program.prefill_final_chunk_ms")(ctx) == pytest.approx(11.3)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_scopes_and_counters(name, monkeypatch):
    """The parent commit on another model's trace: no ``kda_mixer`` scope, no
    routing counters, no window events. The result line then leaves the metric
    out; nothing raises."""
    parsed, ctx = synthetic()
    ctx["extra"]["stats_at_end"] = {"counters": {"decode_steps": 10}, "pools": [{"stripe_len": 1024}]}
    flat = [(a, b, n, op.replace("/kda_mixer", "").replace("/kda_scan", "").replace("/kda_step", "")
             .replace("moe_ffn/", "ffn/").replace("attn_", "mix_"))
            for a, b, n, op in parsed["ops"]]
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: dict(parsed, ops=flat))
    monkeypatch.setattr(window_counts, "windowed", lambda c: c)
    ctx["trace"]["modules"] = {}
    assert common.load_reader(name)(ctx) is None
    # a trace that holds no ``engine.counts`` event, and no trace and no stats at all
    monkeypatch.setattr(window_counts, "windowed", lambda c: None)
    if name.endswith(".window"):
        assert common.load_reader(name)(synthetic()[1]) is None
    monkeypatch.undo()
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: None)
    ctx["extra"] = {}
    assert common.load_reader(name)(ctx) is None


# ------------------------------------------------------ the cell, end to end


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``benchmark/run.py`` on the tiny twin of the cell: the replica behind
    the program's router and proxy, the family's weights from the seed, the
    comparison with the reference through the engine's own loop and cache
    (float32: limits of 0.001), the repeated greedy request, a closed loop of
    six clients, and a result line that can never pass for a chip's."""
    out = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"), "--manifest",
         os.path.join(common.BENCH_DIR, "rehearsal-kda-moe.json"), "--workload",
         "rehearse-kda-moe-chat", "--seed", str(2**31 + 77), "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=900, cwd=common.ROOT,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is True and result["rehearsal"] is True and "metrics" not in result
    assert result["rehearsal_metrics"]["serve_tok_s"]["value"] > 0
    compared = next(line["compared"] for line in lines if "compared" in line)
    assert set(compared) == {"kv_prefill_rel_rms", "kv_decode_rel_rms", "logits_rel_rms"}
    assert all(c["ok"] and c["value"] < 1e-4 for c in compared.values())
    stats = next(line["stats_at_end"] for line in lines if "stats_at_end" in line)
    assert stats["pools"][0]["state_bytes_per_slot"] == family.state_bytes_per_slot(TINY, dtype_bytes=4)
    counters = stats["counters"]
    assert counters["prefix_bypassed_stateful"] > 0 and stats["prefix_cache_entries"] == 0
    assert counters["prefill_chunks"]["mid"] > 0  # prompts of several chunks are in the timed path
    made, held = (sum(counters[k].values()) for k in ("moe_assignments", "moe_assignments_held"))
    assert 0.1 < held / made < 0.4  # 4 of 16 experts held
