"""Family ``cca_moe`` (PR 48): its configuration file against its own
``published`` block and the catalog row, the cell's and the metrics' entries,
its weights and int8 control, the counts of the whole model and of what a step
needs against hand-worked numbers at the published widths, every reader of the
new per-layer metrics on a hand-made trace and the engine's counters, and the
rehearsal cell end to end on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common, scopes, window_counts
from benchmark.families import cca_moe as family
from benchmark.tests.test_manifest import check_config_file

MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
CELL = "zaya1-8b-serve-long-chat"
NAME = "zaya1-8b-serve-l20-ep2"
CONFIG = common.load_config(MANIFEST, NAME)
TINY = common.load_json(os.path.join(common.BENCH_DIR, "configs", "rehearse-cca-moe-serve.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts"]
NEW_READERS = [
    "program.decode_hbm_share.cca_moe.window", "kernel.moe_decode_hbm_share.cca_moe.window",
    "kernel.decode_cca_attention_hbm_share.window", "kernel.decode_cca_conv_ms",
    "kernel.decode_router_ms", "kernel.moe_prefill_roofline_share.cca_moe",
]
APPENDED_BESIDE = ["program.moe_held_assignment_share", "program.moe_single_pass_share",
                   "program.prefill_chunk_ms", "program.prefill_final_chunk_ms",
                   "engine.state_bytes_per_slot", "kernel.decode_read_efficiency.window"]
EXPERT = 3 * 2048 * 2048
ATTENTION, ROUTER, LAYER = 5_575_682, 660_512, 207_575_074


# ------------------------------------------------------------- the data files


def test_configuration_file_passes_the_manifest_check():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED == CONFIG["reduced"] and MANIFEST["configs"][-1] is entry
    check_config_file(CONFIG, REDUCED)
    changed = {k for k, v in CONFIG["published"].items() if CONFIG[k] != v}
    assert changed == set(REDUCED)
    # layers 0-19 of 40, experts 0-7 of the router's 16; every width as published
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"]) == (20, 8)
    assert (family.router_experts(CONFIG), CONFIG["run"]["experts_first"]) == (16, 0)
    assert [CONFIG[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                                "head_dim", "moe_intermediate_size", "router_hidden_size",
                                "vocab_size", "num_experts_per_tok")] == [
        2048, 8, 2, 128, 2048, 256, 262272, 1]
    assert set(CONFIG["layer_types"]) == {"hybrid"} and len(CONFIG["layer_types"]) == 40
    assert set(CONFIG["assumed"]) >= {
        "cca_order", "cca_temperature", "cca_mean", "cca_convolutions", "cca_values", "router",
        "router_margin", "selection_bias", "residual", "initialisation", "engine"}
    assert "skip_choice" in CONFIG["left_out"] and "chip 0 of stage 0" in CONFIG["deployment"]
    run = CONFIG["run"]
    assert run["engine"] == {"max_num_seqs": 64, "max_seq_len": 4608,
                             "prefill_buckets": [128, 256, 512, 1024], "prefill_chunk": 1024}
    assert run["probe"] == {"prompt_lens": [100, 2150], "decode_steps": 192, "stripe": 2240}
    assert set(run["limits"]) == {"kv_prefill_rel_rms", "kv_decode_rel_rms", "logits_rel_rms"}
    assert 4096 + 448 + 1 <= run["engine"]["max_seq_len"] == 9 * 512


def test_published_block_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not beside this checkout")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    assert CONFIG["published"] == row["config"] and CONFIG["source"] == row["source_url"]
    assert next(c for c in MANIFEST["configs"] if c["name"] == NAME)["source"] == row["source_url"]


def test_rehearsal_fixture_cannot_pass_for_the_benchmark():
    rehearsal = common.load_manifest(os.path.join(common.BENCH_DIR, "rehearsal-cca-moe.json"))
    assert rehearsal["rehearsal"] is True and TINY["source"].startswith("none")
    assert family.model_kwargs(TINY)["moe_experts_held"] == 4


def test_cell_and_metric_entries():
    cell = common.find_cell(MANIFEST, CELL)
    assert MANIFEST["workloads"][-1] is cell and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (NAME, "long-chat-closed-128")
    assert len(cell["why"]) <= 200
    e2e = {m["name"] for m in common.metrics_for(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"serve_tok_s", "setup_s"}
    per_layer = {m["name"]: m for m in common.metrics_for(MANIFEST, "per_layer", CELL)}
    assert [m["name"] for m in MANIFEST["per_layer"][-len(NEW_READERS):]] == NEW_READERS
    for name in NEW_READERS:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", name + ".py"))
    for name in APPENDED_BESIDE:
        assert per_layer[name]["workloads"][-1] == CELL
    # every metric that listed all six serving cells lists this one too
    six = common.metrics_for(MANIFEST, "per_layer", "granite4-h-micro-serve-sessions")
    shared = [m for m in six if "mistral7b-serve-saturated" in m["workloads"]
              and "nemotron3-super-serve-chat" in m["workloads"]]
    assert shared and all(m["workloads"][-1] == CELL for m in shared)


# ------------------------------------------------------ shapes, bytes, operations


def test_parameter_shapes_count_the_model_whole_and_the_cut():
    assert family.cca_params(CONFIG) == ATTENTION == (
        2048 * 1024 + 2 * 2048 * 256 + 1024 * 2048 + (2 + 1) * 1280 + 10 * 2 * 128 * 128 + 1280 + 2)
    assert family.router_params(CONFIG) == ROUTER == (
        2048 * 256 + 2 * 256 + 2 * (256 * 256 + 256) + 256 * 16 + 2 * 16)
    assert family.expert_params(CONFIG) == EXPERT == 12_582_912
    whole = family.whole_model_params(CONFIG)
    table = 262272 * 2048
    assert whole["layer"] == LAYER == ATTENTION + ROUTER + 6 * 2048 + 16 * EXPERT
    assert whole["layer_active"] == 18_831_394  # x 40 = 0.753 B: the family's 'A0.76B'
    assert whole["total"] == 40 * LAYER + table + 2048 == 8_840_138_064
    assert 8.29e9 < whole["total"] - table < 8.31e9  # its '8.3B', the table apart
    # the cut: 20 layers at 8 of 16 experts and the table, 5.35 GB in bfloat16
    assert family.param_count(CONFIG) == 20 * (LAYER - 8 * EXPERT) + table + 2048 == 2_675_370_664
    assert CONFIG["bytes"]["weights_gb_bf16"] == pytest.approx(2 * 2_675_370_664 / 1e9, abs=1e-3)
    assert family.cca_dims(CONFIG) == {"heads": 10, "qk": 1280, "vprev": 128, "tail": 2688}
    assert family.kv_bytes_per_token(CONFIG) == 20 * 1024 == CONFIG["bytes"]["kv_bytes_per_token"]
    assert family.cca_tail_bytes(CONFIG) == 20 * 2688 * 2 == CONFIG["bytes"]["tail_bytes_per_slot"]
    pool = 64 * 4608 * family.kv_bytes_per_token(CONFIG)
    assert CONFIG["bytes"]["pool_kv_gb"] == pytest.approx(pool / 1e9, abs=5e-3)
    assert (2 * 2_675_370_664 + pool) / 16e9 > 0.7  # of the chip, before temporaries
    shapes = {k: shape for k, (shape, _) in family.param_shapes(CONFIG).items()}
    assert shapes["moe_w_up"] == (20, 8, 2048, 2048) and shapes["moe_router_w3"] == (20, 256, 16)
    assert shapes["cca_conv1_w"] == (20, 10, 256, 128) and shapes["wv"] == (20, 2048, 2, 128)
    assert "moe_router" not in shapes and "unembed" not in shapes


def test_needed_bytes_and_operations():
    c = CONFIG
    assert family.bank_bytes(c, 20 * 8) == 2 * 160 * EXPERT == 4_026_531_840
    # a decode step at 64 rows and 1,480 live tokens a row, every held expert touched
    rows, tokens = 64, 64 * 1480
    attention = 2 * 20 * ATTENTION + tokens * 20480 + 2 * rows * 107_520
    assert family.attention_decode_bytes(c, rows, tokens) == attention
    step = family.decode_step_bytes(c, rows, 8, tokens)
    assert step == attention + 4_026_531_840 + 2 * (20 * (ROUTER + 6 * 2048) + 2048 + 262272 * 2048)
    # the issue's reckoning: banks 55%, the compressed cache 27%, the table 15%
    assert 0.53 < 4_026_531_840 / step < 0.57 and 0.25 < tokens * 20480 / step < 0.28
    assert 0.14 < 2 * 262272 * 2048 / step < 0.16 and 7.2e9 < step < 7.4e9
    assert family.moe_needed_bytes(c, 20, 160) == 2 * 20 * ROUTER + 4_026_531_840
    assert family.moe_needed_flops(c, 20, 1024, 512) == 2 * 20 * (1024 * ROUTER + 512 * EXPERT)
    # a 1,024-token chunk behind 1,024 cached tokens: about 0.6 GFLOP a token
    flops = family.chunk_flops(c, 1024, 1536, 0.5)
    assert flops == 2 * 20 * 1024 * (ATTENTION + ROUTER + 0.5 * EXPERT + 2 * 8 * 128 * 1536)
    assert 0.55e9 < flops / 1024 < 0.65e9


def test_weights_from_a_seed_and_the_int8_control():
    import jax.numpy as jnp

    a, b = family.make_params(7, TINY, jnp.float32), family.make_params(7, TINY, jnp.float32)
    other = family.make_params(8, TINY, jnp.float32)
    assert set(a) == set(family.param_shapes(TINY))
    assert all(np.array_equal(a[k], b[k]) for k in a) and not np.array_equal(
        a["wq_cca"], other["wq_cca"])
    assert all(a[k].shape == shape for k, (shape, _) in family.param_shapes(TINY).items())
    for name in ("attn_scale", "mlp_scale", "cca_temp", "attn_norm", "moe_router_norm"):
        assert np.all(np.asarray(a[name]) == 1.0)
    assert np.all(np.asarray(a["moe_router_gamma"]) == family.GAMMA)
    R = TINY["router_hidden_size"]
    assert np.std(np.asarray(a["moe_router_w3"])) == pytest.approx(
        family.ROUTER_GAIN * R ** -0.5, rel=0.15)
    assert np.std(np.asarray(a["embed"])) == pytest.approx(1.0, rel=0.05)
    cut = family.int8_roundtrip(b)  # donates what it cuts: b is a's twin
    same = {k for k in a if np.array_equal(np.asarray(a[k]), np.asarray(cut[k]))}
    assert same == {k for k in a if "norm" in k} | set(family.VECTORS)
    err = np.abs(np.asarray(cut["moe_w_up"]) - np.asarray(a["moe_w_up"])).max()
    assert 0 < err < np.abs(np.asarray(a["moe_w_up"])).max() / 100


# ------------------------------------------------------------- the readers


def synthetic():
    """Two decode steps and one middle chunk inside a 1 s window, milliseconds
    in round numbers; and the window's own counters: 10 decode steps over 60
    live rows and 90,000 live tokens each, touching 7 of the 8 held experts of
    each of 20 layers; 5 middle launches of 2 rows and 1,800 real tokens, half
    of whose choices are held."""
    d, m = "jit(decode_fn)/", "jit(chunk_mid)/"
    ops = []

    def add(start, ms, op_name):
        ops.append((start, start + ms * 1e-3, "fusion", op_name))
        return start + ms * 1e-3

    for step_start in (0.0, 0.1):
        t = step_start
        t = add(t, 0.4, d + "while/body/attn_qkv/bte,ehd->bthd/dot_general")
        t = add(t, 0.6, d + "while/body/attn_qkv/cca_conv/mul")
        t = add(t, 0.2, d + "while/body/attn_qkv/cca_conv/bthc,hcd->bthd/dot_general")
        t = add(t, 0.2, d + "while/body/kv_write/scatter")
        t = add(t, 3.0, d + "while/body/attn_core/global/decode_attention")
        t = add(t, 0.3, d + "while/body/attn_out/dot_general")
        t = add(t, 0.7, d + "while/body/moe_ffn/router/dot_general")
        t = add(t, 6.0, d + "while/body/moe_ffn/experts/gmm")
        t = add(t, 1.5, d + "lm_head/dot_general")
    t = add(0.2, 2.0, m + "while/body/attn_qkv/cca_conv/mul")
    t = add(t, 5.0, m + "while/body/attn_core/global/dot_general")
    t = add(t, 1.0, m + "while/body/moe_ffn/router/dot_general")
    t = add(t, 12.0, m + "while/body/moe_ffn/experts/gmm")
    parsed = {
        "window": (0.0, 1.0), "spans": [],
        "modules": [(0.0, 0.0129, "jit_decode_fn"), (0.1, 0.1129, "jit_decode_fn"),
                    (0.2, 0.22, "jit_chunk_mid")],
        "ops": sorted(ops),
    }
    counters = {
        "decode_steps": 10, "decode_slot_steps": 10 * 60, "decode_kv_tokens_global": 10 * 90_000,
        "decode_kv_positions_read": 10 * 120_000,
        "prefill_chunks": {"mid": 10, "final": 4}, "prefill_programs": {"mid": 5, "final": 4},
        "prefill_query_tokens": {"chunk_mid": 5 * 1800, "chunk_final": 4 * 300},
        "moe_layer_steps": {"decode": 200, "chunk_mid": 100, "chunk_final": 80},
        "moe_assignments": {"decode": 200 * 64, "chunk_mid": 100 * 2048, "chunk_final": 80 * 512},
        "moe_assignments_held": {"decode": 200 * 32, "chunk_mid": 100 * 1024, "chunk_final": 80 * 256},
        "moe_experts_touched": {"decode": 200 * 7, "chunk_mid": 100 * 8, "chunk_final": 80 * 8},
        "moe_max_expert_load_sum": {"decode": 200 * 9, "chunk_mid": 100 * 300, "chunk_final": 80 * 70},
        "moe_passes": {"decode": 200, "chunk_mid": 100, "chunk_final": 80},
    }
    ctx = {
        "cell": {"name": CELL}, "config": CONFIG, "device_kind": "TPU v5 lite",
        "trace": {"busy_s": 0.0458, "window_s": 1.0, "modules": {
            "jit_decode_fn": {"count": 2, "total_s": 0.0258},
            "jit_chunk_mid": {"count": 1, "total_s": 0.02}}},
        "extra": {"stats_at_end": {"counters": counters, "max_num_seqs": 64,
                                   "pools": [{"state_bytes_per_slot": 107_520}]}},
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
    # 7 touched experts a layer, 20 layers, in the 6 ms under moe_ffn/experts
    banks = 2 * 140 * EXPERT
    assert read["kernel.moe_decode_hbm_share.cca_moe.window"] == pytest.approx(100 * banks / bw / 6e-3)
    # 90,000 live tokens of 20,480 bytes in the 3 ms under attn_core
    assert read["kernel.decode_cca_attention_hbm_share.window"] == pytest.approx(
        100 * 90_000 * 20480 / bw / 3e-3)
    step = (2 * 20 * ATTENTION + 90_000 * 20480 + 2 * 60 * 107_520 + banks
            + 2 * (20 * (ROUTER + 6 * 2048) + 2048 + 262272 * 2048))
    assert read["program.decode_hbm_share.cca_moe.window"] == pytest.approx(100 * step / bw / 12.9e-3)
    assert read["kernel.decode_cca_conv_ms"] == pytest.approx(0.8)
    assert read["kernel.decode_router_ms"] == pytest.approx(0.7)
    # a middle launch of 1,800 real tokens, half of them on the 8 held experts of 20 layers:
    # 112 rows an expert against its 25 MB: the banks' bytes bind, not the operations
    flops, every = 2 * 20 * 900 * EXPERT, 2 * 160 * EXPERT
    assert flops / peak < every / bw
    assert read["kernel.moe_prefill_roofline_share.cca_moe"] == pytest.approx(
        100 * every / bw / 12e-3)
    assert all(0 < read[n] <= 100 for n in NEW_READERS if n.endswith(("share", "window", "cca_moe")))
    # the accepted readers this cell was appended to hold for it unedited
    assert common.load_reader("engine.state_bytes_per_slot")(ctx) == 107_520
    assert common.load_reader("program.moe_held_assignment_share")(ctx) == pytest.approx(50.0)
    assert common.load_reader("program.moe_single_pass_share")(ctx) == pytest.approx(100.0)
    assert common.load_reader("program.prefill_chunk_ms")(ctx) == pytest.approx(20.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_scopes_and_counters(name, monkeypatch):
    """The parent commit on another model's trace: no ``cca_conv`` scope, no
    ``experts`` under ``moe_ffn``, no routing counters, no window events. The
    result line then leaves the metric out; nothing raises."""
    parsed, ctx = synthetic()
    ctx["extra"]["stats_at_end"] = {"counters": {"decode_steps": 10}, "pools": [{"stripe_len": 1024}]}
    flat = [(a, b, n, op.replace("/cca_conv", "").replace("moe_ffn/", "ffn/")
             .replace("attn_", "mix_")) for a, b, n, op in parsed["ops"]]
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


def test_the_router_reader_is_silent_on_another_familys_router(monkeypatch):
    """Solar's decode step has ``moe_ffn/router`` too and no ``cca_conv``:
    ``kernel.decode_router_ms`` is this family's and reads nothing there."""
    parsed, ctx = synthetic()
    other = [(a, b, n, op.replace("/cca_conv", "")) for a, b, n, op in parsed["ops"]]
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: dict(parsed, ops=other))
    assert common.load_reader("kernel.decode_router_ms")(ctx) is None


# ------------------------------------------------------ the cell, end to end


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``benchmark/run.py`` on the tiny twin of the cell: the replica behind
    the program's router and proxy, the family's weights from the seed, the
    comparison with the reference through the engine's own loop and cache
    (float32: limits of 0.001; the probe's long prompt crosses two chunk
    programs with its tails), the repeated greedy request, a closed loop, and
    a result line that can never pass for a chip's."""
    out = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"), "--manifest",
         os.path.join(common.BENCH_DIR, "rehearsal-cca-moe.json"), "--workload",
         "rehearse-cca-moe-chat", "--seed", str(2**31 + 77), "--seconds", "2", "--trace", "0"],
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
    assert counters["snapshots_stored"] > 0 and counters["decode_steps_in_chunk"] > 0
    assert counters["prefill_chunks"]["mid"] > 0  # prompts of several chunks are in the timed path
    made, held = (sum(counters[k].values()) for k in ("moe_assignments", "moe_assignments_held"))
    # 4 of 8 experts held; a seeded router's load is uneven (a fifth here), never all or none
    assert 0.1 < held / made < 0.9
