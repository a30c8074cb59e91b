"""Family ``sparse_latent`` (PR 51): its configuration file against its own
``published`` block and the catalog row, the cell's and the metrics' entries,
its weights and int8 control, the counts of the cut and of what a step needs
against hand-worked numbers at the published widths, every reader of the new
per-layer metrics on a hand-made trace and the engine's counters, and the
rehearsal cell end to end on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common, scopes, window_counts
from benchmark.families import sparse_latent as family
from benchmark.tests.test_manifest import check_config_file

MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
CELL = "dots3-note-serve-docs-shared"
NAME = "dots3-note-prev-serve-l5-ep16"
CONFIG = common.load_config(MANIFEST, NAME)
TINY = common.load_json(os.path.join(common.BENCH_DIR, "configs", "rehearse-sparse-latent-serve.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
NEW_READERS = [
    "program.decode_hbm_share.sparse_latent.window",
    "kernel.decode_sparse_latent_attention_hbm_share.window",
    "kernel.decode_index_hbm_share.window", "kernel.decode_index_select_ms",
    "kernel.decode_window_latent_attention_hbm_share.window",
    "kernel.moe_decode_hbm_share.sparse_latent.window",
    "kernel.prefill_sparse_latent_attention_roofline_share",
    "engine.index_selected_share.window",
]
APPENDED_BESIDE = ["program.prefill_final_chunk_ms", "kernel.prefix_seed_ms",
                   "engine.prefix_hit_token_share", "kernel.decode_read_efficiency.window"]
LEFT_TO_KANANA = ["kernel.decode_latent_attention_hbm_share",
                  "kernel.prefill_latent_attention_roofline_share",
                  "kernel.moe_decode_hbm_share.moe_latent", "program.decode_hbm_share.moe_latent",
                  "kernel.moe_decode_hbm_share.moe_latent.window"]
E = 5120
FULL = (E * 1024 + 1024 * 128 * 192 + E * 576 + 128 * 256 * 512 + 128 * 128 * E + E * 128
        + 1024 * 64 * 128 + E * 128 + E * 64)
SLIDING = E * 1024 + 1024 * 64 * 256 + E * 1088 + 64 * 320 * 1024 + 64 * 128 * E + E * 64
EXPERT = 3 * E * 1536
ROUTER = E * 256


# ------------------------------------------------------------- the data files


def test_configuration_file_passes_the_manifest_check():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED == CONFIG["reduced"] and MANIFEST["configs"][-1] is entry
    check_config_file(CONFIG, REDUCED)
    changed = {k for k, v in CONFIG["published"].items() if CONFIG[k] != v}
    assert changed == set(REDUCED)
    # layers 0-4 of 46, experts 0-15 of the router's 256, an eighth of the vocabulary
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"], CONFIG["vocab_size"]) == (
        5, 16, 19008) and 8 * 19008 == CONFIG["published"]["vocab_size"]
    assert CONFIG["layer_types"] == CONFIG["published"]["layer_types"][:5] == [
        "full_attention", "full_attention"] + ["sliding_attention"] * 3
    assert (family.router_experts(CONFIG), CONFIG["run"]["experts_first"]) == (256, 0)
    assert [CONFIG[k] for k in (
        "hidden_size", "q_lora_rank", "kv_lora_rank", "swa_kv_lora_rank", "qk_nope_head_dim",
        "swa_qk_nope_head_dim", "index_n_heads", "index_head_dim", "index_topk",
        "sliding_window_size", "moe_intermediate_size", "num_experts_per_tok")] == [
        5120, 1024, 512, 1024, 128, 192, 64, 128, 2048, 513, 1536, 8]
    assert set(CONFIG["assumed"]) >= {
        "mla_qkv_lora_rescale", "window", "rope", "indexer", "gate", "selection_bias", "router",
        "left_out", "cache_row", "initialisation", "engine"}
    assert "sixteen chips share each layer" in CONFIG["deployment"]
    run = CONFIG["run"]
    assert run["engine"]["max_num_seqs"] == 16 and run["engine"]["max_seq_len"] == 24576
    assert run["engine"]["prefill_buckets"] == [32, 64, 128, 256, 12288, 20480]
    # the store holds 12 documents at every bucket they cover, of every stripe
    stored = 6 * 12288 + 6 * (12288 + 20480)
    assert stored == 270336
    assert stored * family.held_bytes_per_token(CONFIG) < run["engine"]["prefix_cache_max_bytes"]
    assert run["probe"] == {"prompt_lens": [100, 4400], "decode_steps": 64, "stripe": 4544}
    assert (4544 + 64) % 512 == 0 and 4400 > CONFIG["index_topk"] + 8 * 256
    assert set(run["limits"]) <= {"kv_prefill_rel_rms", "kv_decode_rel_rms", "logits_rel_rms"}


def test_published_block_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not beside this checkout")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "dots3-note-prev")
    assert CONFIG["published"] == row["config"] and CONFIG["source"] == row["source_url"]
    assert next(c for c in MANIFEST["configs"] if c["name"] == NAME)["source"] == row["source_url"]


def test_rehearsal_fixture_cannot_pass_for_the_benchmark():
    rehearsal = common.load_manifest(os.path.join(common.BENCH_DIR, "rehearsal-sparse-latent.json"))
    assert rehearsal["rehearsal"] is True and TINY["source"].startswith("none")
    assert family.model_kwargs(TINY)["moe_experts_held"] == 8


def test_cell_and_metric_entries():
    cell = common.find_cell(MANIFEST, CELL)
    assert MANIFEST["workloads"][-1] is cell and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (NAME, "docs-shared-closed-32")
    entry = MANIFEST["configs"][-1]
    assert entry["name"] == NAME
    # (the driver holds a configuration's ``why`` to the 200 of a cell's; test_manifest.py does not)
    assert all(1 <= len(e["why"]) <= 200 and e["why"].isprintable() for e in (cell, entry))
    traffic, kanana = (common.load_traffic(n) for n in (
        "docs-shared-closed-32", "docs-shared-closed-48"))
    differ = {k for k in traffic if traffic[k] != kanana[k]}
    assert differ == {"clients", "why"} and traffic["clients"] == 32
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
    assert not set(LEFT_TO_KANANA) & set(per_layer)
    # every metric that lists all seven serving cells lists this one too
    seven = common.metrics_for(MANIFEST, "per_layer", "zaya1-8b-serve-long-chat")
    shared = [m for m in seven if "mistral7b-serve-saturated" in m["workloads"]
              and "kanana2-serve-docs-shared" in m["workloads"]
              and "nemotron3-super-serve-chat" in m["workloads"]]
    assert shared and all(m["workloads"][-1] == CELL for m in shared)


# ------------------------------------------------------ shapes, bytes, operations


def test_parameter_shapes_count_the_cut():
    """ISSUE 51's reckoning, from the shapes built."""
    assert family.attention_params(CONFIG, "full") == FULL and round(FULL / 1e6, 2) == 144.05
    assert family.attention_params(CONFIG, "sliding") == SLIDING and round(SLIDING / 1e6, 2) == 90.83
    assert family.expert_params(CONFIG) == EXPERT and round(EXPERT / 1e6, 2) == 23.59
    assert family.moe_fixed_params(CONFIG) == ROUTER + 256 + EXPERT
    assert family.layer_rows(CONFIG) == {"all": 5, "full": 2, "sliding": 3, "dense": 1, "sparse": 4}
    norms = 2 * (1024 + 512) + 3 * (1024 + 1024) + 2 * 2 * 128 + 10 * E + E
    total = (2 * FULL + 3 * SLIDING + 3 * E * 13824 + 4 * (ROUTER + 256 + 17 * EXPERT)
             + 2 * 19008 * E + norms)
    assert family.param_count(CONFIG) == total and round(total / 1e6, 1) == 2577.2
    assert round(2 * total / 1e9, 2) == 5.15
    # what the program builds, leaf for leaf
    from ray_tpu.llm import EngineConfig, ModelConfig
    from ray_tpu.llm.config import resolve_llama_config
    from ray_tpu.models.patterned import _param_shapes

    model = ModelConfig(model_id="dots3-note-prev", model_kwargs=family.model_kwargs(CONFIG))
    cfg = resolve_llama_config(model, EngineConfig(**CONFIG["run"]["engine"]))
    assert {k: s for k, (s, _) in family.param_shapes(CONFIG).items()} == _param_shapes(cfg)
    assert cfg.moe_experts == 256 and cfg.moe_experts_held == 16 and cfg.index_topk == 2048


def test_needed_bytes_and_operations():
    assert family.cached_bytes(CONFIG, "full") == 1152
    assert family.cached_bytes(CONFIG, "sliding") == 2176
    assert family.index_key_bytes(CONFIG) == 256
    assert family.held_bytes_per_token(CONFIG) == 2 * 1536 + 3 * 2304 == 9984
    # 16 rows of 17,000 live positions: 2,048 selected, 513 in a window
    live, rows = 16 * 17_000, 16
    parts = family.decode_attention_bytes(CONFIG, live, rows * 2048, rows * 513)
    assert parts == {"index": 2 * live * 256, "sparse": 2 * rows * 2048 * 1152,
                     "window": 3 * rows * 513 * 2176}
    weights = 2 * (2 * FULL + 3 * SLIDING + 11 * E + 19008 * E + 3 * E * 13824
                   + 4 * (ROUTER + 256 + EXPERT) + 4 * 6 * EXPERT)
    assert family.decode_weight_bytes(CONFIG, 6) == weights
    assert family.decode_step_bytes(CONFIG, 6, live, rows * 2048, rows * 513) == (
        weights + sum(parts.values()))
    assert 3.2e9 < weights + sum(parts.values()) < 3.6e9  # ISSUE 51: about 3.4 GB a step
    # a 100-token chunk behind 16,000 positions, each query attending 2,048: absorbed is cheaper
    pairs = 100 * 2048
    absorbed = 2 * 128 * (100 * 128 * 512 + pairs * (2 * 512 + 64))
    expanded = 2 * 128 * (16_100 * 512 * 256 + pairs * (128 + 64 + 128))
    assert family.sparse_attention_flops(CONFIG, 100, pairs, 16_100) == absorbed < expanded
    assert family.selected_positions(CONFIG, 900) == 900
    assert family.window_positions(CONFIG, 900) == 513


def test_weights_from_a_seed_and_the_int8_control():
    import jax.numpy as jnp

    a, b, other = (family.make_params(s, TINY, jnp.float32) for s in (3, 3, 4))
    assert all(np.array_equal(a[k], b[k]) for k in a) and not np.array_equal(a["embed"], other["embed"])
    assert {k: v.shape for k, v in a.items()} == {k: s for k, (s, _) in family.param_shapes(TINY).items()}
    assert a["moe_w_up"].shape[1] == 8 and a["moe_router"].shape[-1] == 16
    assert float(jnp.std(a["embed"])) == pytest.approx(1.0, rel=0.05)
    assert float(jnp.std(a["index_k_bias"])) == pytest.approx(0.02, rel=0.3)
    cut = family.int8_roundtrip(dict(a))
    for name in a:
        same = np.array_equal(cut[name], a[name])
        assert same == ("norm" in name or name in family.VECTORS), name


# ------------------------------------------------------------------ the readers


def synthetic():
    """Two decode steps and one final chunk inside a 1 s window, milliseconds
    in round numbers; and the window's own counters: 10 decode steps over 16
    rows of 17,000 live positions, touching 6 of the 16 held experts of each
    of 4 layers; 4 final chunks of 100 real tokens behind 16,000 positions."""
    d, f = "jit(decode_fn)/", "jit(chunk_final)/"
    ops = []

    def add(start, ms, op_name):
        ops.append((start, start + ms * 1e-3, "fusion", op_name))
        return start + ms * 1e-3

    for step_start in (0.0, 0.1):
        t = step_start
        t = add(t, 1.0, d + "attn_qkv/bte,er->btr/dot_general")
        t = add(t, 0.1, d + "attn_qkv/attn_index/btr,rf->btf/dot_general")
        t = add(t, 0.2, d + "kv_write/scatter")
        t = add(t, 0.4, d + "attn_core/attn_index/bthd,bsd->bths/dot_general")
        t = add(t, 0.8, d + "attn_core/attn_select/top_k")
        t = add(t, 5.0, d + "attn_core/latent_sparse/gather")
        t = add(t, 0.5, d + "while/body/attn_core/latent_window/latent_decode_attention")
        t = add(t, 0.9, d + "attn_out/dot_general")
        t = add(t, 0.3, d + "while/body/moe_ffn/router/dot_general")
        t = add(t, 1.2, d + "while/body/moe_ffn/experts/gmm")
        t = add(t, 0.5, d + "while/body/moe_ffn/shared_expert/dot_general")
        t = add(t, 0.3, d + "lm_head/dot_general")
    t = add(0.2, 3.0, f + "attn_core/attn_index/dot_general")
    t = add(t, 1.0, f + "attn_core/attn_select/while/body/compare")
    t = add(t, 8.0, f + "attn_core/latent_sparse/while/body/dot_general")
    t = add(t, 2.0, f + "while/body/attn_core/latent_window/while/body/dot_general")
    t = add(t, 6.0, f + "while/body/moe_ffn/experts/gmm")
    parsed = {
        "window": (0.0, 1.0), "spans": [],
        "modules": [(0.0, 0.0112, "jit_decode_fn"), (0.1, 0.1112, "jit_decode_fn"),
                    (0.2, 0.22, "jit_chunk_final")],
        "ops": sorted(ops),
    }
    live = 16 * 17_000
    counters = {
        "decode_steps": 10, "decode_slot_steps": 160, "decode_kv_tokens_latent": 10 * live,
        "decode_kv_positions_read_latent": 160 * 24576,
        "decode_kv_tokens_window": 160 * 513, "decode_kv_positions_read_window": 160 * 1024,
        "index_positions_scored": 10 * live, "index_positions_selected": 160 * 2048,
        "prefill_chunks": {"mid": 0, "final": 4}, "prefill_programs": {"mid": 0, "final": 4},
        "prefill_query_tokens": {"chunk_mid": 0, "chunk_final": 400},
        "prefill_attended_positions": {"chunk_mid": 0, "chunk_final": 4 * (100 * 16_000 + 5050)},
        "moe_layer_steps": {"decode": 40, "chunk_mid": 0, "chunk_final": 16},
        "moe_assignments": {"decode": 40 * 128, "chunk_mid": 0, "chunk_final": 16 * 800},
        "moe_assignments_held": {"decode": 40 * 8, "chunk_mid": 0, "chunk_final": 16 * 50},
        "moe_experts_touched": {"decode": 40 * 6, "chunk_mid": 0, "chunk_final": 16 * 15},
        "moe_max_expert_load_sum": {"decode": 40 * 3, "chunk_mid": 0, "chunk_final": 16 * 9},
        "moe_passes": {"decode": 40, "chunk_mid": 0, "chunk_final": 16},
    }
    ctx = {
        "cell": {"name": CELL}, "config": CONFIG, "device_kind": "TPU v5 lite",
        "trace": {"busy_s": 0.0424, "window_s": 1.0, "modules": {
            "jit_decode_fn": {"count": 2, "total_s": 0.0224},
            "jit_chunk_final": {"count": 1, "total_s": 0.02}}},
        "extra": {"stats_at_end": {"counters": counters, "max_num_seqs": 16, "pools": [{}]}},
        "samples": [],
    }
    return parsed, ctx


def test_new_readers_on_a_hand_made_trace(monkeypatch):
    parsed, ctx = synthetic()
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: parsed)
    # the window's own counts are the context's: no .xplane.pb behind a hand-made trace
    monkeypatch.setattr(window_counts, "windowed", lambda c: c)
    read = {name: common.load_reader(name)(ctx) for name in NEW_READERS}
    bw, peak, live = 819e9, 197e12, 16 * 17_000
    # 16 rows x 2,048 selected x 1,152 bytes x 2 layers in the 5 ms under latent_sparse
    assert read["kernel.decode_sparse_latent_attention_hbm_share.window"] == pytest.approx(
        100 * 2 * 16 * 2048 * 1152 / bw / 5e-3)
    # the live positions' index keys, 2 layers, in the 0.1 + 0.4 ms under attn_index
    assert read["kernel.decode_index_hbm_share.window"] == pytest.approx(
        100 * 2 * live * 256 / bw / 0.5e-3)
    assert read["kernel.decode_index_select_ms"] == pytest.approx(0.8)
    assert read["kernel.decode_window_latent_attention_hbm_share.window"] == pytest.approx(
        100 * 3 * 16 * 513 * 2176 / bw / 0.5e-3)
    # router, bias, shared expert and 6 touched experts a layer, 4 layers, in the 2 ms under moe_ffn
    banks = 2 * 4 * (ROUTER + 256 + EXPERT + 6 * EXPERT)
    assert read["kernel.moe_decode_hbm_share.sparse_latent.window"] == pytest.approx(
        100 * banks / bw / 2e-3)
    step = family.decode_step_bytes(CONFIG, 6, live, 16 * 2048, 16 * 513)
    assert read["program.decode_hbm_share.sparse_latent.window"] == pytest.approx(
        100 * step / bw / 11.2e-3)
    assert read["engine.index_selected_share.window"] == pytest.approx(100 * 2048 / 17_000)
    # a final chunk of 100 queries, each 2,048 of some 16,050 positions, 2 layers, in 8 ms:
    # the absorbed form's operations bind, not the 204,800 selected positions' bytes
    flops = family.sparse_attention_flops(CONFIG, 100, 100 * 2048, 16_100)
    assert flops / peak > 100 * 2048 * 1152 / bw / 100
    assert read["kernel.prefill_sparse_latent_attention_roofline_share"] == pytest.approx(
        100 * 2 * max(flops / peak, 16_100 * 1152 / bw) / 8e-3, rel=0.02)
    assert all(0 < read[n] <= 100 for n in NEW_READERS if not n.endswith("_ms"))
    # accepted readers this cell was appended to hold for it unedited
    assert common.load_reader("program.prefill_final_chunk_ms")(ctx) == pytest.approx(20.0)
    assert common.load_reader("program.decode_step_ms")(ctx) == pytest.approx(11.2)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_scopes_and_counters(name, monkeypatch):
    """The parent commit on another model's trace (Kanana's cell with this
    PR's benchmark files laid over it): no ``attn_index``, ``attn_select``,
    ``latent_sparse`` or ``latent_window`` scope, no index counters, no window
    events. The result line then leaves the metric out; nothing raises."""
    parsed, ctx = synthetic()
    ctx["extra"]["stats_at_end"] = {"counters": {"decode_steps": 10}, "pools": [{"stripe_len": 1024}]}
    flat = [(a, b, n, op.replace("/attn_index", "").replace("/attn_select", "")
             .replace("latent_sparse", "latent").replace("latent_window", "latent")
             .replace("moe_ffn/", "ffn/")) for a, b, n, op in parsed["ops"]]
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


# what benchmark/tools/record_sparse_latent_trace.py ran: the attention as
# published in a narrow model of three layers, 256 positions a query, a window of 129
RECORDED = dict(CONFIG, hidden_size=512, intermediate_size=1024, num_hidden_layers=3,
                layer_types=CONFIG["layer_types"][:3], vocab_size=2048, index_topk=256,
                sliding_window_size=129)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_on_the_recorded_trace(name, tmp_path, monkeypatch):
    """``data/sparse_latent``: the program's own engine on a v5e, six requests
    behind a stored 1,024-token document on four slots (the tool's docstring).
    Every new reader finds its scope and its counters in the trace, and no
    share passes the chip's peak: the bytes and operations are counted at the
    recording's own widths."""
    from benchmark.tests.test_decode_in_chunk import recorded_ctx

    ctx = dict(recorded_ctx(tmp_path, monkeypatch, "sparse_latent", CELL), config=RECORDED)
    own = window_counts.window_counts(ctx)
    assert own["index_positions_selected"] == 256 * own["decode_slot_steps"]
    assert own["decode_kv_tokens_window"] == 129 * own["decode_slot_steps"]
    assert own["index_positions_scored"] > 1024 * own["decode_slot_steps"]
    value = common.load_reader(name)(ctx)
    assert isinstance(value, float) and value > 0, name
    if not name.endswith("_ms"):
        assert value <= 100.0


# ------------------------------------------------------ the cell, end to end


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``benchmark/run.py`` on the tiny twin of the cell: the replica behind
    the program's router and proxy, the family's weights from the seed, the
    comparison with the reference through the engine's own loop and cache
    (float32: limits of 0.001; the probe's 70-token prompt passes 8 positions
    and 14 windows), each document's miss and hit answered alike with every
    stripe seeded, a closed loop, and a result line that can never pass for a
    chip's."""
    out = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"), "--manifest",
         os.path.join(common.BENCH_DIR, "rehearsal-sparse-latent.json"), "--workload",
         "rehearse-sparse-latent-docs", "--seed", str(2**31 + 51), "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=900, cwd=common.ROOT,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is True and result["rehearsal"] is True and "metrics" not in result
    assert result["rehearsal_metrics"]["serve_tok_s"]["value"] > 0
    compared = next(line["compared"] for line in lines if "compared" in line)
    assert set(compared) == {"kv_prefill_rel_rms", "logits_rel_rms"}
    assert all(c["ok"] and c["value"] < 1e-4 for c in compared.values())
    built = next(line["built"] for line in lines if "built" in line)
    assert all(built["same_on_hit"]) and built["hit_pass_tokens_from_prefix"] == built["document_tokens"]
    stats = next(line["stats_at_end"] for line in lines if "stats_at_end" in line)
    assert stats["pools"][0]["kv_bytes_per_token"] == family.held_bytes_per_token(TINY, dtype_bytes=4)
    counters = stats["counters"]
    assert counters["index_positions_scored"] > counters["index_positions_selected"] > 0
    assert counters["decode_kv_tokens_window"] > 0 and counters["decode_steps_in_chunk"] == 0
    made, held = (sum(counters[k].values()) for k in ("moe_assignments", "moe_assignments_held"))
    assert 0.2 < held / made < 0.8  # 8 of 16 experts held
