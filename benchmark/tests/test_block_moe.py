"""Family ``block_moe`` (PR 55): its configuration file against its own
``published`` block and the catalog row, the cell's and the metrics' entries,
its weights and int8 control, the counts of the cut and of what a block step
needs against hand-worked numbers at the published widths, the schedule's
count of forwards, every reader of the new per-layer metrics on a hand-made
trace and the engine's counters, and the rehearsal cell end to end on the
CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common, scopes, window_counts
from benchmark.families import block_moe as family
from benchmark.kinds import block_closed_loop as kind
from benchmark.tests.test_manifest import check_config_file

MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
CELL = "sdar-30b-a3b-serve-block-chat"
NAME = "sdar-30b-a3b-chat-serve-l6"
CONFIG = common.load_config(MANIFEST, NAME)
TRAFFIC = common.load_traffic("block-chat-closed-128")
TINY = common.load_json(os.path.join(common.BENCH_DIR, "configs", "rehearse-block-moe-serve.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = [
    "program.block_step_ms", "program.decode_hbm_share.block_moe.window",
    "kernel.block_attention_hbm_share.window", "kernel.moe_decode_hbm_share.block_moe.window",
    "kernel.moe_prefill_roofline_share.block_moe", "kernel.block_confidence_ms",
    "engine.tokens_per_forward.window", "engine.block_commit_share.window",
]
# accepted metrics whose readers say of this cell what their names say
JOINED = ["entry.replica_start_s", "entry.engine_init_s", "entry.engine_warm_programs_s",
          "entry.programs_restored_share", "engine.batch_occupancy", "engine.queue_wait_p50_ms",
          "engine.prefill_p50_ms", "engine.prefill_rows_per_program",
          "engine.prefill_rows_per_program.window", "engine.device_wait_share",
          "engine.loop_longest_pass_ms", "engine.decode_live_rows.window",
          "kernel.decode_read_efficiency.window", "program.scope_coverage.serve",
          "program.prefill_chunk_ms", "program.prefill_final_chunk_ms",
          "program.moe_experts_touched_share"]
# and those that take a step for a token or read ``jit_decode_fn``: not this cell's
NOT_JOINED = ["engine.token_gap_ms", "engine.useful_token_share", "engine.useful_token_share.window",
              "program.decode_step_ms", "kernel.decode_kv_write_ms", "kernel.decode_sampling_ms",
              "program.carried_step_own_ms", "kernel.carried_attn_core_ms",
              "engine.carried_step_dead_share.window", "engine.decode_in_chunk_share.window"]
EXPERT = 3 * 2048 * 768
ATTENTION = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128
LAYER = ATTENTION + 2048 * 128 + 128 * EXPERT + 2 * 2048 + 2 * 128
TABLE = 151936 * 2048


def test_configuration_file_passes_the_manifest_check():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"] == CONFIG["reduced"]
    assert MANIFEST["configs"][-1] is entry
    check_config_file(CONFIG, ["num_hidden_layers"])
    assert {k for k, v in CONFIG["published"].items() if CONFIG[k] != v} == {"num_hidden_layers"}
    assert [CONFIG[k] for k in ("num_hidden_layers", "hidden_size", "num_attention_heads",
                                "num_key_value_heads", "head_dim", "moe_intermediate_size",
                                "num_experts", "num_experts_per_tok", "vocab_size")] == [
        6, 2048, 32, 4, 128, 768, 128, 8, 151936]
    assert set(CONFIG["assumed"]) >= {
        "qk_norm", "rope", "logits", "block_length", "denoise_steps", "confidence_threshold",
        "mask_token_id", "prompt_tail", "commit", "mask", "engine"}
    assert CONFIG["generation"] == {"block_length": 4, "denoise_steps": 4,
                                    "confidence_threshold": 0.9, "mask_token_id": 151669}
    assert "first of eight pipeline stages" in CONFIG["deployment"]
    run = CONFIG["run"]
    assert run["engine"] == {"max_num_seqs": 64, "max_seq_len": 4096,
                             "prefill_buckets": [128, 256, 512, 1024], "prefill_chunk": 1024}
    assert set(run["limits"]) == {"kv_prefill_rel_rms", "kv_commit_rel_rms", "x0_logit_gap",
                                  "confidence_order_err", "unmasked_per_forward_err"}
    assert run["limits"]["unmasked_per_forward_err"] == 0.0
    # the probe binds every slot, at both denoising steps, behind prompts of
    # one, two and three chunks, and each request has forwards enough to judge
    probe = run["probe"]
    requests = kind.probe_requests(7, probe, 4)
    assert len(requests) == run["engine"]["max_num_seqs"] == 64
    assert {r["denoise_steps"] for r in requests} == {2, 4}
    assert [-(-len(r["ids"]) // run["engine"]["prefill_chunk"]) for r in requests[:3]] == [3, 2, 1]
    assert {len(r["ids"]) % 4 for r in requests} == {0, 1, 2, 3}
    assert all((len(r["ids"]) + r["max_tokens"]) % 4 == 0 for r in requests)
    assert len({len(r["ids"]) + r["max_tokens"] for r in requests}) == 5  # lengths the reference compiles
    assert all(kind.expected_forwards(len(r["ids"]), r["max_tokens"], r["denoise_steps"], 4)[
        "denoise"] >= probe["judged_forwards"] for r in requests)
    # the longest prompt and answer of the traffic fit a stripe
    assert TRAFFIC["prompt_tokens"]["max"] + TRAFFIC["max_tokens"]["max"] <= 4096


def test_published_block_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not beside this checkout")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
    assert CONFIG["published"] == row["config"] and CONFIG["source"] == row["source_url"]
    assert next(c for c in MANIFEST["configs"] if c["name"] == NAME)["source"] == row["source_url"]


def test_rehearsal_fixture_cannot_pass_for_the_benchmark():
    rehearsal = common.load_manifest(os.path.join(common.BENCH_DIR, "rehearsal-block-moe.json"))
    assert rehearsal["rehearsal"] is True and TINY["source"].startswith("none")
    assert family.model_kwargs(TINY)["block_length"] == 4


def test_cell_traffic_and_metric_entries():
    cell = common.find_cell(MANIFEST, CELL)
    assert MANIFEST["workloads"][-1] is cell and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (NAME, "block-chat-closed-128")
    assert len(MANIFEST["workloads"]) == 10 and all(w["chips"] == 1 for w in MANIFEST["workloads"])
    assert (TRAFFIC["kind"], TRAFFIC["clients"], TRAFFIC["pool"], TRAFFIC["stream"]) == (
        "block_closed_loop", 128, 128, False)
    assert TRAFFIC["prompt_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.9,
                                        "min": 128, "max": 3072}
    assert TRAFFIC["max_tokens"] == {"dist": "lognormal", "median": 224, "sigma": 0.5,
                                     "min": 64, "max": 512}
    assert (TRAFFIC["denoise_steps"], TRAFFIC["temperature"], TRAFFIC["trace_seconds"]) == (
        [4, 2], 0.0, 6)
    body = kind.body_of("m", {"prompt": "p", "max_tokens": 7}, TRAFFIC, 3)
    assert (body["ignore_eos"], body["denoise_steps"], body["max_tokens"]) == (True, 2, 7)
    assert kind.body_of("m", {"prompt": "p", "max_tokens": 7}, TRAFFIC, 4)["denoise_steps"] == 4
    e2e = {m["name"] for m in common.metrics_for(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"serve_tok_s", "setup_s"}
    per_layer = {m["name"]: m for m in common.metrics_for(MANIFEST, "per_layer", CELL)}
    assert [m["name"] for m in MANIFEST["per_layer"][-len(NEW_READERS):]] == NEW_READERS
    for name in NEW_READERS:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", name + ".py"))
    for name in JOINED:
        assert per_layer[name]["workloads"][-1] == CELL, name
    assert set(per_layer) == set(NEW_READERS) | set(JOINED)
    assert not set(NOT_JOINED) & set(per_layer)


def test_parameter_shapes_count_the_cut():
    assert family.attention_params(CONFIG) == ATTENTION == 18_874_368
    assert family.expert_params(CONFIG) == EXPERT == 4_718_592
    assert family.param_count(CONFIG) == 6 * LAYER + 2 * TABLE + 2048 == 4_361_055_744
    assert 8.72e9 < 2 * family.param_count(CONFIG) < 8.73e9  # bfloat16
    assert 48 * LAYER + 2 * TABLE + 2048 == pytest.approx(30.5e9, rel=0.01)  # its '30B'
    active = ATTENTION + 2048 * 128 + 8 * EXPERT
    assert 48 * active + TABLE == pytest.approx(3.04e9, rel=0.01)  # its 'A3B', the head alone
    shapes = {k: shape for k, (shape, _) in family.param_shapes(CONFIG).items()}
    assert shapes["moe_w_up"] == (6, 128, 2048, 768) and shapes["wq_full"] == (6, 2048, 32, 128)
    assert shapes["q_head_norm"] == shapes["k_head_norm"] == (6, 128)
    assert shapes["unembed"] == (2048, 151936) and "moe_shared_up" not in shapes
    # keys and values: 12,288 bytes a token at 6 layers; 64 slots of 4,096 are 3.22 GB
    assert 6 * family.kv_bytes_per_token_layer(CONFIG) == 12_288
    assert 64 * 4096 * 12_288 == pytest.approx(3.22e9, rel=0.005)
    assert (2 * family.param_count(CONFIG) + 64 * 4096 * 12_288) / 16e9 > 0.7


def test_needed_bytes_and_operations():
    c = CONFIG
    # a block step at 64 slots of 1,100 positions, every expert touched
    step = family.step_needed_bytes(c, 128, 64 * 1100)
    banks = 2 * 6 * 128 * EXPERT
    assert family.moe_needed_bytes(c, 6, 6 * 128) == banks + 2 * 6 * 2048 * 128
    kv = 64 * 1100 * 12_288
    assert step == (2 * (6 * (ATTENTION + 2 * 2048 + 2 * 128) + 2048 + TABLE)
                    + banks + 2 * 6 * 2048 * 128 + kv)
    # the issue's reckoning: 9 GB a step, 11 ms at 819 GB/s; the banks are four fifths
    assert 8.9e9 < step < 9.1e9 and 10.8e-3 < step / 819e9 < 11.2e-3
    assert 0.78 < banks / step < 0.82 and 0.09 < kv / step < 0.1
    assert family.moe_needed_flops(c, 6, 1024) == 2 * 6 * 1024 * (2048 * 128 + 8 * EXPERT)
    # a 1,024-token chunk behind 1,024 cached tokens: 0.3 GFLOP a token and layer... of six
    flops = family.chunk_needed_flops(c, 1024, 1024 * 1536, False)
    assert flops == 6 * (2 * 1024 * (ATTENTION + 2048 * 128 + 8 * EXPERT)
                         + 4 * 32 * 128 * 1024 * 1536)
    assert family.chunk_needed_flops(c, 1024, 1024 * 1536, True) == flops + 2 * TABLE


@pytest.mark.parametrize("prompt,answer,steps,want", [
    (16, 8, 4, (8, 2, 8)), (16, 8, 2, (4, 2, 8)), (13, 7, 4, (7, 2, 7)), (3, 5, 2, (3, 2, 5)),
    (21, 9, 3, (8, 3, 11)), (40, 1, 1, (1, 1, 4)),
], ids=["4-steps", "2-steps", "tail", "shorter-than-a-block", "3-steps-cut", "1-step"])
def test_a_request_costs_steps_plus_one_forwards_a_block(prompt, answer, steps, want):
    got = kind.expected_forwards(prompt, answer, steps, 4)
    assert (got["denoise"], got["commit"], got["unmasked"]) == want


def test_weights_from_a_seed_and_the_int8_control():
    import jax.numpy as jnp

    a, b = family.make_params(7, TINY, jnp.float32), family.make_params(7, TINY, jnp.float32)
    other = family.make_params(8, TINY, jnp.float32)
    assert set(a) == set(family.param_shapes(TINY))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq_full"], other["wq_full"])
    assert all(a[k].shape == shape for k, (shape, _) in family.param_shapes(TINY).items())
    for name in ("attn_norm", "mlp_norm", "q_head_norm", "k_head_norm", "final_norm"):
        assert np.all(np.asarray(a[name]) == 1.0)
    assert np.std(np.asarray(a["embed"])) == pytest.approx(family.EMBED_STD, rel=0.05)
    # the mask token's own row at the fan-in scale: a masked position holds no token
    assert np.std(np.asarray(a["embed"])[300]) == pytest.approx(64 ** -0.5, rel=0.3)
    assert np.std(np.asarray(a["moe_w_down"])) == pytest.approx(32 ** -0.5, rel=0.05)
    cut = family.int8_roundtrip(b)  # donates what it cuts: b is a's twin
    same = {k for k in a if np.array_equal(np.asarray(a[k]), np.asarray(cut[k]))}
    assert same == {k for k in a if "norm" in k}
    err = np.abs(np.asarray(cut["moe_w_up"]) - np.asarray(a["moe_w_up"])).max()
    assert 0 < err < np.abs(np.asarray(a["moe_w_up"])).max() / 100


# ------------------------------------------------------------- the readers


def synthetic():
    """Two block steps and one final chunk inside a 1 s window, milliseconds
    in round numbers; and the window's own counters: 10 steps over 60 live
    slots reading 70,000 positions each, touching 120 of the 128 experts of
    each of 6 layers; 4 final chunks of 700 real tokens; 500 denoise forwards
    and 100 commits of live slots that emitted 390 tokens."""
    d, f = "jit(block_step)/", "jit(chunk_final)/"
    ops = []

    def add(start, ms, op_name):
        ops.append((start, start + ms * 1e-3, "fusion", op_name))
        return start + ms * 1e-3

    for step_start in (0.0, 0.1):
        t = step_start
        t = add(t, 0.5, d + "while/body/attn_qkv/bte,ehd->bthd/dot_general")
        t = add(t, 0.3, d + "while/body/kv_write/scatter")
        t = add(t, 1.5, d + "while/body/attn_core/block/decode_attention")
        t = add(t, 0.4, d + "while/body/attn_out/dot_general")
        t = add(t, 0.6, d + "while/body/moe_ffn/router/dot_general")
        t = add(t, 8.0, d + "while/body/moe_ffn/experts/gmm")
        t = add(t, 1.2, d + "lm_head/dot_general")
        t = add(t, 1.0, d + "sampling/confidence/reduce")
        t = add(t, 0.1, d + "sampling/unmask/select")
    t = add(0.2, 4.0, f + "while/body/attn_core/global/dot_general")
    t = add(t, 1.0, f + "while/body/moe_ffn/router/dot_general")
    t = add(t, 11.0, f + "while/body/moe_ffn/experts/gmm")
    parsed = {
        "window": (0.0, 1.0), "spans": [],
        "modules": [(0.0, 0.0136, "jit_block_step"), (0.1, 0.1136, "jit_block_step"),
                    (0.2, 0.216, "jit_chunk_final")],
        "ops": sorted(ops),
    }
    counters = {
        "decode_steps": 10, "decode_slot_steps": 10 * 60, "decode_kv_tokens_global": 10 * 70_000,
        "decode_kv_positions_read": 10 * 80_000,
        "prefill_chunks": {"mid": 2, "final": 4}, "prefill_programs": {"mid": 2, "final": 4},
        "prefill_query_tokens": {"chunk_mid": 2 * 1024, "chunk_final": 4 * 700},
        "moe_layer_steps": {"decode": 60, "chunk_mid": 12, "chunk_final": 24},
        "moe_assignments": {"decode": 60 * 2048, "chunk_mid": 12 * 8192, "chunk_final": 24 * 8192},
        "moe_experts_touched": {"decode": 60 * 120, "chunk_mid": 12 * 128, "chunk_final": 24 * 128},
        "moe_max_expert_load_sum": {"decode": 60 * 30, "chunk_mid": 12 * 90, "chunk_final": 24 * 70},
        "block_forwards": {"denoise": 500, "commit": 100}, "block_tokens_emitted": 390,
        "block_tokens_unmasked": 400, "blocks_committed": 100,
    }
    ctx = {
        "cell": {"name": CELL}, "config": CONFIG, "device_kind": "TPU v5 lite",
        "trace": {"busy_s": 0.0432, "window_s": 1.0, "modules": {
            "jit_block_step": {"count": 2, "total_s": 0.0272},
            "jit_chunk_final": {"count": 1, "total_s": 0.016}}},
        "extra": {"stats_at_end": {"counters": counters, "max_num_seqs": 64, "pools": [{}]}},
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
    assert read["program.block_step_ms"] == pytest.approx(13.6)
    assert read["kernel.block_confidence_ms"] == pytest.approx(1.0)
    # 70,000 positions of 12,288 bytes in the 1.5 ms under attn_core/block
    assert read["kernel.block_attention_hbm_share.window"] == pytest.approx(
        100 * 70_000 * 12_288 / bw / 1.5e-3)
    # router and 120 touched experts a layer, 6 layers, in the 8.6 ms under moe_ffn
    moe = 2 * (6 * 2048 * 128 + 6 * 120 * EXPERT)
    assert read["kernel.moe_decode_hbm_share.block_moe.window"] == pytest.approx(
        100 * moe / bw / 8.6e-3)
    step = 2 * (6 * (ATTENTION + 2 * 2048 + 2 * 128) + 2048 + TABLE) + moe + 70_000 * 12_288
    assert read["program.decode_hbm_share.block_moe.window"] == pytest.approx(
        100 * step / bw / 13.6e-3)
    # a final chunk of 700 real tokens: 44 rows an expert against its 9.4 MB: the bytes bind
    flops, every = 2 * 6 * 700 * (2048 * 128 + 8 * EXPERT), 2 * (6 * 2048 * 128 + 6 * 128 * EXPERT)
    assert flops / peak < every / bw
    assert read["kernel.moe_prefill_roofline_share.block_moe"] == pytest.approx(
        100 * every / bw / 12e-3)
    assert read["engine.tokens_per_forward.window"] == pytest.approx(390 / 600)
    assert read["engine.block_commit_share.window"] == pytest.approx(100 / 6)
    assert all(0 < read[n] <= 100 for n in NEW_READERS if "share" in n)
    # accepted readers this cell was appended to hold for it unedited
    assert common.load_reader("program.prefill_final_chunk_ms")(ctx) == pytest.approx(16.0)
    assert common.load_reader("program.moe_experts_touched_share")(ctx) == pytest.approx(
        100 * (60 * 120 + 36 * 128) / (96 * 128))
    assert common.load_reader("engine.decode_live_rows.window")(ctx) == pytest.approx(60.0)
    # and those it was not appended to find nothing to read
    assert common.load_reader("program.decode_step_ms")(ctx) is None
    assert common.load_reader("kernel.decode_sampling_ms")(ctx) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_scopes_and_counters(name, monkeypatch):
    """The parent commit on another model's trace: no ``jit_block_step``, no
    block counters, no window events. The result line then leaves the metric
    out; nothing raises."""
    parsed, ctx = synthetic()
    ctx["extra"]["stats_at_end"] = {"counters": {"decode_steps": 10}, "pools": [{"stripe_len": 1024}]}
    flat = [(a, b, n, op.replace("block_step", "decode_fn").replace("moe_ffn/", "ffn/")
             .replace("sampling/", "")) for a, b, n, op in parsed["ops"]]
    modules = [(a, b, n.replace("block_step", "decode_fn")) for a, b, n in parsed["modules"]]
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: dict(parsed, ops=flat, modules=modules))
    monkeypatch.setattr(window_counts, "windowed", lambda c: c)
    ctx["trace"]["modules"] = {}
    assert common.load_reader(name)(ctx) is None
    monkeypatch.setattr(window_counts, "windowed", lambda c: None)
    if name.endswith(".window"):
        assert common.load_reader(name)(synthetic()[1]) is None
    monkeypatch.undo()
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: None)
    ctx["extra"] = {}
    assert common.load_reader(name)(ctx) is None


# ------------------------------------------------------ the cell, end to end


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``benchmark/run.py`` on the tiny twin of the cell: the replica with
    this kind's check behind the program's router and proxy, the family's
    weights from the seed, the probe through the engine's own loop and its
    block step's hand-outs under the reference's judgement (float32: limits of
    0.001, the schedule's counts exact), the repeated greedy request, a closed loop of
    requests at 4 and 2 denoising steps, and a result line that can never pass
    for a chip's."""
    out = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"), "--manifest",
         os.path.join(common.BENCH_DIR, "rehearsal-block-moe.json"), "--workload",
         "rehearse-block-moe-chat", "--seed", str(2**31 + 55), "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=900, cwd=common.ROOT,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is True and result["rehearsal"] is True and "metrics" not in result
    assert result["failed"] == 0 and result["rehearsal_metrics"]["serve_tok_s"]["value"] > 0
    checked = next(line for line in lines if "compared" in line)
    assert set(checked["compared"]) == set(TINY["run"]["limits"])
    assert all(c["ok"] and c["value"] < 1e-4 for c in checked["compared"].values())
    assert checked["not_limited"]["judged"]["forwards"] == 16
    forwards = checked["not_limited"]["block_forwards"]
    assert {k: forwards["counted"][k] for k in forwards["by_schedule"]} == forwards["by_schedule"]
    window = next(line for line in lines if "blocks_in_window" in line)
    assert window["answers_whole"] is True and window["compiles_in_window"] == 0
    blocks = window["blocks_in_window"]
    assert blocks["block_forwards"]["commit"] > 0 and 0.3 < blocks["tokens_per_forward"] < 1.4
    counters = window["stats_at_end"]["counters"]
    assert counters["decode_steps_in_chunk"] == 0 and counters["first_tokens"] == 0
    assert counters["block_prompt_tail_tokens"] > 0 and counters["tokens_generated"] == counters[
        "block_tokens_emitted"]
