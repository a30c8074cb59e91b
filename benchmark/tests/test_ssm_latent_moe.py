"""Family ``ssm_latent_moe`` (PR 35): its configuration file against its own
``published`` block and the catalog row, its traffic mix, its weights and int8
control, the counts of what a step needs against hand-worked numbers at the
published widths, the reference's pieces, every reader of the new per-layer
metrics on a hand-made trace and the engine's counters, and the rehearsal
cell end to end on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common, scopes, traffic
from benchmark.families import ssm_latent_moe as family
from benchmark.tests.test_manifest import check_config_file

MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
CELL = "nemotron3-super-serve-chat"
NAME = "nemotron-3-super-120b-a12b-serve-l11-ep4"
CONFIG = common.load_config(MANIFEST, NAME)
MIX = common.load_traffic("chat-closed-128")
TINY = common.load_json(os.path.join(common.BENCH_DIR, "configs", "rehearse-ssm-latent-moe-serve.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
NEW_READERS = [
    "kernel.ssm_decode_hbm_share", "kernel.ssm_prefill_roofline_share",
    "kernel.moe_decode_hbm_share.ssm_moe", "program.decode_hbm_share.ssm_moe",
    "program.moe_held_assignment_share", "engine.state_bytes_per_slot",
    "kernel.moe_prefill_roofline_share.ssm_moe",
]
# an accepted reader of ``jit_chunk_final`` that held one cell before this one
FINAL_CHUNK_MS = "program.prefill_final_chunk_ms"
SHARED_WITH_EVERY_SERVING_CELL = [
    "entry.replica_start_s", "entry.engine_init_s", "engine.batch_occupancy",
    "engine.queue_wait_p50_ms", "engine.prefill_p50_ms", "engine.token_gap_ms",
    "engine.useful_token_share", "engine.device_wait_share", "engine.prefill_rows_per_program",
    "program.decode_step_ms", "program.scope_coverage.serve", "kernel.decode_kv_write_ms",
    "kernel.decode_sampling_ms",
]


# ------------------------------------------------------------- the data files


def test_configuration_file_passes_the_manifest_check():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED == CONFIG["reduced"] and MANIFEST["configs"][-1] is entry
    check_config_file(CONFIG, REDUCED)
    changed = {k for k, v in CONFIG["published"].items() if CONFIG[k] != v}
    assert changed == set(REDUCED)
    assert (CONFIG["num_hidden_layers"], CONFIG["hybrid_override_pattern"]) == (11, "MEMEMEM*EME")
    # the first 11 blocks of the published pattern, in its ratio of 40 : 40 : 8
    assert CONFIG["published"]["hybrid_override_pattern"].startswith(CONFIG["hybrid_override_pattern"])
    whole = family.layer_rows(CONFIG["published"])
    assert (whole["ssm"], whole["sparse"], whole["full"]) == (40, 40, 8)
    assert family.layer_rows(CONFIG) == {"ssm": 5, "sparse": 5, "full": 1, "mixer": 6, "all": 11}
    # the chip's share: a quarter of the experts and of the vocabulary; the router keeps all 512
    assert (CONFIG["n_routed_experts"], family.router_experts(CONFIG)) == (128, 512)
    assert CONFIG["vocab_size"] * 4 == CONFIG["published"]["vocab_size"] and CONFIG["vocab_size"] > 258
    assert CONFIG["num_experts_per_tok"] == 22 and CONFIG["run"]["experts_first"] == 0
    assert set(CONFIG["assumed"]) >= {
        "ssm_precision", "time_step", "a_log", "conv", "gated_norm", "latent_experts", "router",
        "selection_bias", "experts", "attention", "initialisation", "tokenizer", "engine"}
    assert "multi_token_prediction" in CONFIG["left_out"]
    assert "4 chips" in CONFIG["deployment"] and "8 pipeline stages" in CONFIG["deployment"]
    engine, probe = CONFIG["run"]["engine"], CONFIG["run"]["probe"]
    assert (engine["max_num_seqs"], engine["max_seq_len"]) == (64, 2048)
    assert set(engine) == {"max_num_seqs", "max_seq_len", "prefill_buckets", "prefill_chunk"}
    # the probe's longer prompt has a middle chunk, so state crosses a chunk boundary on the chip
    assert max(probe["prompt_lens"]) > engine["prefill_chunk"] >= MIX["prompt_tokens"]["max"]
    assert (probe["stripe"] + probe["decode_steps"]) % 128 == 0  # whole kernel blocks
    assert probe["stripe"] % CONFIG["chunk_size"]  # and no whole number of scan chunks
    assert set(CONFIG["run"]["limits"]) == {"kv_prefill_rel_rms", "kv_decode_rel_rms", "logits_rel_rms"}
    assert probe["decode_steps"] == 192  # at 64 the two decode numbers followed single swapped experts (limits_from)


def test_published_block_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert CONFIG["published"] == row["config"]
    assert CONFIG["source"] == row["source_url"]


def test_rehearsal_fixture_passes_the_check_and_cannot_pass_for_the_benchmark():
    rehearsal = common.load_manifest(os.path.join(common.BENCH_DIR, "rehearsal-ssm-latent-moe.json"))
    assert rehearsal["rehearsal"] is True
    assert not {c["name"] for c in rehearsal["workloads"]} & {c["name"] for c in MANIFEST["workloads"]}
    (entry,) = rehearsal["configs"]
    check_config_file(common.load_json(os.path.join(common.ROOT, entry["file"])), ["n_routed_experts"])
    (cell,) = rehearsal["workloads"]
    assert common.load_traffic(cell["traffic"])["kind"] == MIX["kind"] == "closed_loop"
    assert family.model_kwargs(TINY)["moe_experts_held"] == 4 and family.router_experts(TINY) == 16


def test_cell_and_metric_entries():
    cell = common.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "chat-closed-128", 1)
    assert MANIFEST["workloads"][-1] is cell
    reported = {m["name"] for m in common.metrics_for(MANIFEST, "per_layer", CELL)}
    assert reported == set(NEW_READERS) | set(SHARED_WITH_EVERY_SERVING_CELL) | {FINAL_CHUNK_MS}
    assert [m["name"] for m in MANIFEST["per_layer"][-7:]] == NEW_READERS
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW_READERS:
            assert (m["workloads"], m["moves"]) == ([CELL], "serve_tok_s")
            assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", m["name"] + ".py"))
        elif m["name"] in SHARED_WITH_EVERY_SERVING_CELL:
            assert m["workloads"][-1] == CELL and len(m["workloads"]) == 4
        elif m["name"] == FINAL_CHUNK_MS:
            assert m["workloads"] == ["kanana2-serve-docs-shared", CELL]
    assert [m["name"] for m in common.metrics_for(MANIFEST, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]


def test_traffic_mix_is_the_one_the_issue_names():
    assert (MIX["kind"], MIX["clients"], MIX["stream"], MIX["pool"], MIX["temperature"],
            MIX["ramp_seconds"]) == ("closed_loop", 128, False, 128, 0.0, 5)
    assert MIX["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 64, "max": 1024}
    assert MIX["max_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 0.5, "min": 64, "max": 512}
    engine = CONFIG["run"]["engine"]
    # ISSUE 35's floor of slots and twice as many clients: the router passes 64 + 2
    # at a time, the proxy admits 128 in flight a deployment (_private/config.py)
    assert (engine["max_num_seqs"], MIX["clients"]) == (64, 2 * engine["max_num_seqs"])
    assert MIX["clients"] <= 128 and MIX["pool"] == MIX["clients"]
    assert MIX["prompt_tokens"]["max"] + MIX["max_tokens"]["max"] + 1 <= engine["max_seq_len"]
    # the warm-up reaches every final-chunk width a prompt can fall into
    def width(n):
        return next(b for b in engine["prefill_buckets"] if n <= b)

    prompts = traffic.stratified(MIX["prompt_tokens"], MIX["pool"])
    assert {width(n) for n in MIX["warmup_prompt_tokens"]} == {width(n) for n in prompts} == {128, 256, 512, 1024}
    answers = traffic.stratified(MIX["max_tokens"], MIX["pool"])
    assert (min(prompts), max(prompts), min(answers), max(answers)) == (64, 1024, 64, 512)
    assert 300 < sum(prompts) / 128 < 340 and 205 < sum(answers) / 128 < 225
    for seed in (3, 2**31 + 12345):  # every seed the same sizes, in another order
        reqs = traffic.Requests(MIX, seed, MIX["pool"])
        assert sorted(p for p, _ in reqs.sizes) == sorted(prompts)
        assert len(reqs[7]["prompt"]) == reqs.sizes[7][0] - 1  # BOS and n - 1 bytes


# ------------------------------------------------------- weights and counts


def test_parameter_shapes_count_the_cut_and_its_bytes():
    c = CONFIG
    shapes = family.param_shapes(c)
    assert family.param_count(c) == 4_648_163_712  # the issue's 4,648 M: 9.30 GB in bfloat16
    assert family.ssm_dims(c) == {"inner": 8192, "bc": 1024, "conv": 10240, "proj": 18560}
    assert shapes["ssm_w_in"] == ((5, 4096, 18560), 4096) and shapes["ssm_w_out"] == ((5, 8192, 4096), 8192)
    assert shapes["ssm_conv_w"][0] == (5, 4, 10240) and shapes["ssm_norm"] == ((5, 8192), None)
    assert shapes["moe_w_up"] == ((5, 128, 1024, 2688), 1024)
    assert shapes["moe_w_down"] == ((5, 128, 2688, 1024), 2688) and "moe_w_gate" not in shapes
    assert shapes["moe_router"][0] == (5, 4096, 512) and shapes["moe_router_bias"][0] == (5, 512)
    assert shapes["moe_latent_down"][0] == (5, 4096, 1024) and shapes["moe_latent_up"][0] == (5, 1024, 4096)
    assert shapes["moe_shared_up"][0] == (5, 4096, 5376) and "moe_shared_gate" not in shapes
    assert shapes["wq_full"][0] == (1, 4096, 32, 128) and shapes["wk"][0] == (1, 4096, 2, 128)
    assert shapes["attn_norm"][0] == (6, 4096) and shapes["mlp_norm"][0] == (5, 4096)
    assert shapes["embed"] == ((32768, 4096), 1.0) and shapes["unembed"][0] == (4096, 32768)
    # W_in 76.02 M, W_out 33.55 M, convolution and its bias, three values a head, the norm's scale
    assert family.ssm_params(c) == 76_021_760 + 33_554_432 + 5 * 10240 + 3 * 128 + 8192 == 109_635_968
    assert family.expert_params(c) == 2 * 1024 * 2688 == 5_505_024  # the catalog's "5.5 M each"
    assert family.moe_fixed_params(c) == 2_097_152 + 512 + 8_388_608 + 44_040_192 == 54_526_464
    assert family.attention_params(c) == 4096 * 128 * (2 * 32 + 2 * 2) == 35_651_584
    # the model whole: 120.67 B parameters (the published "120B")
    assert family.param_count(c["published"]) == 120_668_707_840


def test_needed_bytes_and_operations():
    c = CONFIG
    assert family.state_bytes_per_slot(c) == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2) == 21_278_720
    assert family.kv_bytes_per_token(c) == 2 * 2 * 128 * 2 == 1024
    # five mixers' weights once, 96 slots' state and tail read and written
    assert family.ssm_decode_bytes(c, 96) == 2 * 5 * 109_635_968 + 2 * 96 * 21_278_720 == 5_181_873_920
    # five blocks of router, latent projections and shared expert, all 128 held experts of each
    every = family.moe_needed_bytes(c, 5, 5 * 128)
    assert every == 2 * (5 * 54_526_464 + 640 * 5_505_024) == 7_591_695_360
    assert family.moe_needed_bytes(c, 5, 5 * 90) < every
    # mixers, the attention block, 11 norms and the final one, the head; no embedding table
    weights = family.decode_weight_bytes(c, 128)
    assert weights == 2 * (5 * 109_635_968 + 35_651_584 + 11 * 4096 + 4096 + 32768 * 4096) + every
    assert weights == 9_027_891_968  # the issue's 9.03 GB
    step = family.decode_step_bytes(c, 96, 128, 96 * 400)
    assert step == weights + 2 * 96 * 21_278_720 + 38400 * 1024 == 13_152_727_808  # 13.1 GB
    # a token of the scan: 64.5 causal pairs (a score a group, a weight a head), its state in and out
    assert family.ssm_scan_flops(c, 1) == 2 * (64.5 * (8 * 128 + 128 * 64) + 2 * 128 * 64 * 128) == 5_383_168
    assert family.ssm_scan_flops(c, 300) == 300 * 5_383_168
    # x B C in bfloat16, a float32 step a head in, a float32 y out; a row's state twice
    assert family.ssm_scan_bytes(c, 300, 1) == 300 * (10240 * 2 + 128 * 4 + 8192 * 4) + 2 * 4_194_304


def test_weights_from_a_seed_and_the_int8_control():
    import jax
    import jax.numpy as jnp

    a = family.make_params(11, TINY, jnp.float32)
    b = family.make_params(11, TINY, jnp.float32)
    c = family.make_params(12, TINY, jnp.float32)
    assert {k: v.shape for k, v in a.items()} == {k: s for k, (s, _) in family.param_shapes(TINY).items()}
    assert all(np.array_equal(a[k], b[k]) for k in a) and not np.array_equal(a["ssm_w_in"], c["ssm_w_in"])
    assert abs(float(jnp.std(a["moe_w_down"])) / 48 ** -0.5 - 1) < 0.05
    assert abs(float(jnp.std(a["ssm_w_out"])) / 128 ** -0.5 - 1) < 0.05
    assert abs(float(jnp.std(a["embed"])) - 1) < 0.05  # unit embedding rows
    step = np.asarray(jax.nn.softplus(a["ssm_dt_bias"]))
    assert (step >= 0.001 * 0.999).all() and (step <= 0.1 * 1.001).all()
    rate = np.exp(np.asarray(a["ssm_a_log"]))
    assert (rate >= 1).all() and (rate <= 16).all() and np.array_equal(a["ssm_d"], np.ones((5, 8), np.float32))
    bank = np.asarray(a["moe_w_up"])
    cut = family.int8_roundtrip(jax.tree.map(jnp.copy, a))
    for left_alone in ("attn_norm", "ssm_norm", "moe_router_bias", "ssm_conv_b", "ssm_dt_bias",
                       "ssm_a_log", "ssm_d"):
        assert np.array_equal(cut[left_alone], a[left_alone]), left_alone
    err = np.abs(np.asarray(cut["moe_w_up"]) - bank)
    scale = np.abs(bank).max(axis=2, keepdims=True) / 127.0  # one scale an expert and output column
    assert (err <= 0.5 * scale + 1e-7).all() and err.max() > 0
    for cut_too in ("ssm_w_in", "ssm_w_out", "ssm_conv_w", "moe_latent_down", "moe_shared_up", "wk", "embed"):
        assert not np.array_equal(cut[cut_too], a[cut_too]), cut_too


# ---------------------------------------------------------- the reference


def test_reference_state_space_block_is_the_recurrence_written_out():
    """``ssm_part`` against the block's equations in numpy loops, a token and
    a head at a time: convolution over the last four inputs, the state's decay
    and update, the skip, the gate before the grouped norm."""
    import jax.numpy as jnp

    from benchmark import reference_ssm_latent_moe as ref

    rng = np.random.default_rng(0)
    e, H, P, G, N, K, T = 16, 4, 4, 2, 3, 4, 9
    inner, bc = H * P, G * N
    w = {"norm": np.ones(e), "ssm_w_in": rng.normal(size=(e, 2 * inner + 2 * bc + H)) / 4,
         "ssm_conv_w": rng.normal(size=(K, inner + 2 * bc)) / 2, "ssm_conv_b": rng.normal(size=inner + 2 * bc) / 10,
         "ssm_dt_bias": rng.normal(size=H) - 2, "ssm_a_log": np.log(rng.uniform(1, 4, size=H)),
         "ssm_d": rng.normal(size=H), "ssm_norm": rng.uniform(0.5, 1.5, size=inner),
         "ssm_w_out": rng.normal(size=(inner, e)) / 4}
    w = {k: v.astype(np.float32) for k, v in w.items()}
    x = rng.normal(size=(1, T, e)).astype(np.float32)
    got = np.asarray(ref._highest(ref.ssm_part)(
        jnp.asarray(x), w, heads=H, head_dim=P, groups=G, state=N, eps=1e-5))
    u = x[0] / np.sqrt((x[0] ** 2).mean(-1, keepdims=True) + 1e-5)
    proj = u @ w["ssm_w_in"]
    z, xbc, dt = proj[:, :inner], proj[:, inner:-H], proj[:, -H:]
    pad = np.concatenate([np.zeros((K - 1, xbc.shape[1])), xbc])
    conv = np.stack([w["ssm_conv_b"] + sum(w["ssm_conv_w"][j] * pad[t + j] for j in range(K)) for t in range(T)])
    act = conv / (1 + np.exp(-conv))
    dt = np.log1p(np.exp(dt + w["ssm_dt_bias"]))
    S = np.zeros((H, P, N))
    y = np.zeros((T, inner))
    for t in range(T):
        for h in range(H):
            g = h // (H // G)
            xs = act[t, h * P:(h + 1) * P]
            B, C = act[t, inner + g * N: inner + (g + 1) * N], act[t, inner + bc + g * N: inner + bc + (g + 1) * N]
            S[h] = np.exp(-dt[t, h] * np.exp(w["ssm_a_log"][h])) * S[h] + dt[t, h] * np.outer(xs, B)
            y[t, h * P:(h + 1) * P] = S[h] @ C + w["ssm_d"][h] * xs
    y = (y * (z / (1 + np.exp(-z)))).reshape(T, G, inner // G)
    y = (y / np.sqrt((y ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(T, inner) * w["ssm_norm"]
    assert np.allclose(got[0], x[0] + y @ w["ssm_w_out"], atol=2e-5)


def test_reference_router_weights_and_a_share_of_the_experts():
    import jax.numpy as jnp

    from benchmark import reference_ssm_latent_moe as ref

    rng = np.random.default_rng(1)
    e, t = 32, 10
    x = jnp.asarray(rng.normal(size=(1, t, e)).astype(np.float32))
    w = {"norm": np.ones(e, np.float32), "moe_router": (rng.normal(size=(e, 16)) / 4).astype(np.float32),
         "moe_router_bias": np.zeros(16, np.float32),
         "moe_latent_down": (rng.normal(size=(e, 8)) / 4).astype(np.float32)}
    u, latent, weights, idx = ref.route(x, w, top_k=6, scale=5.0, eps=1e-5)
    assert latent.shape == (1, t, 8) and idx.shape == (1, t, 6)
    # k nonzero weights a token that sum to the scale; the bias moves choices, never a weight
    assert np.allclose(np.asarray(weights).sum(-1), 5.0, atol=1e-5)
    assert ((np.asarray(weights) > 0).sum(-1) == 6).all()
    pushed = dict(w, moe_router_bias=np.where(np.arange(16) == 3, 10.0, 0.0).astype(np.float32))
    _, _, w3, idx3 = ref.route(x, pushed, top_k=6, scale=5.0, eps=1e-5)
    assert (np.asarray(idx3) == 3).any(-1).all()
    scores = 1 / (1 + np.exp(-(np.asarray(u) @ w["moe_router"])))
    chosen = np.take_along_axis(scores, np.asarray(idx3), -1)
    assert np.allclose(np.take_along_axis(np.asarray(w3), np.asarray(idx3), -1),
                       5.0 * chosen / chosen.sum(-1, keepdims=True), atol=1e-5)
    # a block of experts adds its own experts' part: two halves give the whole
    up = jnp.asarray((rng.normal(size=(16, 8, 12)) / 3).astype(np.float32))
    down = jnp.asarray((rng.normal(size=(16, 12, 8)) / 3).astype(np.float32))
    whole = ref.expert_block(latent, up, down, weights)
    halves = (ref.expert_block(latent, up[:8], down[:8], weights[..., :8])
              + ref.expert_block(latent, up[8:], down[8:], weights[..., 8:]))
    assert np.allclose(whole, halves, atol=1e-5)
    one = np.square(np.maximum(np.asarray(latent)[0, 0] @ np.asarray(up[2]), 0)) @ np.asarray(down[2])
    only = np.zeros((1, t, 16), np.float32)
    only[0, 0, 2] = 1.0
    assert np.allclose(np.asarray(ref.expert_block(latent, up, down, jnp.asarray(only)))[0, 0], one, atol=1e-5)


# ------------------------------------------------------------- the readers


def synthetic():
    """Two decode steps and one final chunk inside a 1 s window, milliseconds
    in round numbers; and the counters of an engine that ran 10 decode steps
    over 90 live rows and 36,000 live tokens each, touching all 128 held
    experts of each of 5 blocks, and 4 final chunks of 300 real tokens."""
    d, f = "jit(decode_fn)/", "jit(chunk_final)/"
    ops = []

    def add(start, ms, op_name):
        ops.append((start, start + ms * 1e-3, "fusion", op_name))
        return start + ms * 1e-3

    for step_start in (0.0, 0.1):
        t = step_start
        t = add(t, 1.0, d + "while/body/attn_qkv/ssm_mixer/bte,ef->btf/dot_general")
        t = add(t, 0.5, d + "while/body/attn_core/ssm_mixer/ssm_conv/mul")
        t = add(t, 6.0, d + "while/body/attn_core/ssm_mixer/ssm_step/mul")
        t = add(t, 0.5, d + "while/body/attn_out/ssm_mixer/btf,fe->bte/dot_general")
        t = add(t, 8.0, d + "while/body/moe_ffn/experts/gmm")
        t = add(t, 0.5, d + "while/body/moe_ffn/router/dot_general")
        t = add(t, 0.5, d + "while/body/moe_ffn/moe_latent_proj/dot_general")
        t = add(t, 1.0, d + "while/body/moe_ffn/shared_expert/dot_general")
        t = add(t, 0.1, d + "attn_core/global/decode_attention")
        t = add(t, 0.4, d + "lm_head/dot_general")
    t = add(0.2, 2.0, f + "while/body/attn_core/ssm_mixer/ssm_scan/dot_general")
    t = add(t, 0.3, f + "while/body/attn_core/ssm_mixer/ssm_conv/mul")
    t = add(t, 10.0, f + "while/body/moe_ffn/experts/gmm")
    parsed = {
        "window": (0.0, 1.0), "spans": [],
        "modules": [(0.0, 0.023, "jit_decode_fn"), (0.1, 0.123, "jit_decode_fn"),
                    (0.2, 0.215, "jit_chunk_final")],
        "ops": sorted(ops),
    }
    rows = 96 * 22
    counters = {
        "decode_steps": 10, "decode_slot_steps": 10 * 90, "decode_kv_tokens_global": 10 * 36_000,
        "prefill_chunks": {"mid": 0, "final": 4},
        "prefill_query_tokens": {"chunk_mid": 0, "chunk_final": 4 * 300},
        "moe_layer_steps": {"decode": 50, "chunk_mid": 0, "chunk_final": 20},
        "moe_assignments": {"decode": 50 * rows, "chunk_mid": 0, "chunk_final": 20 * 512 * 22},
        "moe_assignments_held": {"decode": 50 * rows // 4, "chunk_mid": 0, "chunk_final": 20 * 512 * 22 // 4},
        "moe_experts_touched": {"decode": 50 * 128, "chunk_mid": 0, "chunk_final": 20 * 128},
        "moe_max_expert_load_sum": {"decode": 50 * 9, "chunk_mid": 0, "chunk_final": 20 * 40},
    }
    ctx = {
        "cell": {"name": CELL}, "config": CONFIG, "device_kind": "TPU v5 lite",
        "trace": {"busy_s": 0.0613, "window_s": 1.0, "modules": {
            "jit_decode_fn": {"count": 2, "total_s": 0.046},
            "jit_chunk_final": {"count": 1, "total_s": 0.015}}},
        "extra": {"stats_at_end": {"counters": counters, "max_num_seqs": 96,
                                   "pools": [{"state_bytes_per_slot": 21_278_720}]}},
        "samples": [],
    }
    return parsed, ctx


def test_new_readers_on_a_hand_made_trace(monkeypatch):
    parsed, ctx = synthetic()
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: parsed)
    read = {name: common.load_reader(name)(ctx) for name in NEW_READERS}
    bw = 819e9
    # five mixers' weights and 90 live rows' state, both ways, in the 8 ms under ssm_mixer
    assert read["kernel.ssm_decode_hbm_share"] == pytest.approx(
        100 * (2 * 5 * 109_635_968 + 2 * 90 * 21_278_720) / bw / 8e-3)
    # 300 real tokens a chunk: the bytes bind (a row's state twice is a third of them), 2 ms under ssm_scan
    by_bytes = 5 * (300 * 53_760 + 2 * 4_194_304) / bw
    assert by_bytes > 5 * 300 * 5_383_168 / 197e12
    assert read["kernel.ssm_prefill_roofline_share"] == pytest.approx(100 * by_bytes / 2e-3)
    # the 10 ms under moe_ffn (experts, router, latent projections, shared expert) against every held bank
    assert read["kernel.moe_decode_hbm_share.ssm_moe"] == pytest.approx(100 * 7_591_695_360 / bw / 10e-3)
    whole = 9_027_891_968 + 2 * 90 * 21_278_720 + 36_000 * 1024
    assert read["program.decode_hbm_share.ssm_moe"] == pytest.approx(100 * whole / bw / 23e-3)
    assert read["program.moe_held_assignment_share"] == pytest.approx(25.0)
    # a final chunk's 300 real tokens, a quarter of their 22 choices held: XX
    flops = 2 * 5 * (300 * 54_525_952 + 300 * 22 / 4 * 5_505_024)
    assert flops / 197e12 < 7_591_695_360 / bw
    assert read["kernel.moe_prefill_roofline_share.ssm_moe"] == pytest.approx(100 * 7_591_695_360 / bw / 10e-3)
    assert read["engine.state_bytes_per_slot"] == 21_278_720
    assert all(0 < read[n] <= 100 for n in NEW_READERS if n != "engine.state_bytes_per_slot")


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_scopes_and_counters(name, monkeypatch):
    """The parent commit on another model's trace: no ``ssm_mixer`` scope, no
    held-assignment counter, no state a slot, no ``jit_chunk_final`` in the
    window. The result line then leaves the metric out; nothing raises."""
    parsed, ctx = synthetic()
    ctx["extra"]["stats_at_end"] = {"counters": {"decode_steps": 10}, "pools": [{"stripe_len": 1024}]}
    flat = [(a, b, n, op.replace("/ssm_mixer", "").replace("/ssm_scan", "").replace("/ssm_step", "")
             .replace("moe_ffn/", "ffn/"))
            for a, b, n, op in parsed["ops"]]
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: dict(parsed, ops=flat))
    ctx["trace"]["modules"] = {"jit_decode_fn": ctx["trace"]["modules"]["jit_decode_fn"]}
    assert common.load_reader(name)(ctx) is None
    # and with no trace and no stats at all
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: None)
    ctx["trace"]["modules"] = {}
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
         os.path.join(common.BENCH_DIR, "rehearsal-ssm-latent-moe.json"), "--workload",
         "rehearse-ssm-latent-moe-chat", "--seed", str(2**31 + 77), "--seconds", "2", "--trace", "0"],
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
    made, held = (sum(counters[k].values()) for k in ("moe_assignments", "moe_assignments_held"))
    assert 0.1 < held / made < 0.4  # 4 of 16 experts held
