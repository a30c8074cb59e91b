"""Family ``looped_dense`` (PR 57): its configuration file against its own
``published`` block and the catalog row (every key equal, nothing reduced),
the cell's, the traffic's and the metrics' entries, the parameter count and
the bytes a token holds from the family's functions against hand-worked
numbers at the published sizes, its weights and int8 control, every reader of
the new per-layer metrics on a hand-made trace and the engine's counters, and
the rehearsal cell end to end on the CPU, sound and under the int8 control."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common, scopes, window_counts
from benchmark.families import looped_dense as family
from benchmark.tests.test_manifest import check_config_file

MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
CELL = "ouro-2.6b-serve-reason-chat"
NAME = "ouro-2.6b-serve-l48"
CONFIG = common.load_config(MANIFEST, NAME)
TRAFFIC = common.load_traffic("reason-chat-closed-24")
TINY = common.load_json(os.path.join(common.BENCH_DIR, "configs",
                                     "rehearse-looped-dense-serve.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = [
    "program.decode_hbm_share.looped.window", "kernel.decode_matmul_hbm_share.looped",
    "kernel.decode_attention_hbm_share.looped.window",
    "engine.loop_passes_per_forward.window", "engine.kv_bytes_per_token",
]
# the three dense shares count a layer's weights once a token: not this cell's
NOT_JOINED = ["program.decode_hbm_share", "kernel.decode_matmul_hbm_share",
              "kernel.decode_attention_hbm_share", "program.prefill_chunk_ms"]
LAYER_MATMULS = 4 * 2048 * 2048 + 3 * 2048 * 5632
LAYER = LAYER_MATMULS + 4 * 2048
TABLE = 49152 * 2048


def test_configuration_file_holds_every_published_key_and_reduces_none():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] == CONFIG["reduced"] and MANIFEST["configs"][-1] is entry
    check_config_file(CONFIG, [])
    assert all(CONFIG[k] == v for k, v in CONFIG["published"].items())
    assert [CONFIG[k] for k in ("num_hidden_layers", "hidden_size", "num_attention_heads",
                                "num_key_value_heads", "head_dim", "intermediate_size",
                                "vocab_size", "total_ut_steps", "early_exit_threshold")] == [
        48, 2048, 16, 16, 128, 5632, 49152, 4, 1]
    assert set(CONFIG["assumed"]) >= {
        "branch_norms", "final_norm_in_loop", "cache_row", "exit_gate", "attention",
        "initialisation", "tokenizer", "engine"}
    assert "whole on one chip" in CONFIG["deployment"] and "12 decode slots" in CONFIG["deployment"]
    run = CONFIG["run"]
    assert run["engine"] == {"max_num_seqs": 12, "max_seq_len": 384,
                             "prefill_buckets": [64, 128, 256], "prefill_chunk": 256,
                             "max_concurrent_admissions": 1}
    # the sums over all rows are the last pass's: the early passes have limits of their own
    assert set(run["limits"]) == {"kv_prefill_rel_rms", "kv_decode_rel_rms", "logits_rel_rms",
                                  "kv_pass0_rel_rms", "kv_pass1_rel_rms"}
    assert run["limits"]["kv_pass0_rel_rms"] < run["limits"]["kv_pass1_rel_rms"] < min(
        run["limits"]["kv_prefill_rel_rms"], run["limits"]["kv_decode_rel_rms"])
    # the probe binds every slot, behind a prompt of each bucket, and its
    # rows are of few lengths (the reference compiles a program a length)
    probe = run["probe"]
    assert len(probe["prompt_lens"]) == run["engine"]["max_num_seqs"]
    buckets = run["engine"]["prefill_buckets"]
    assert {min(b for b in buckets if b >= n) for n in probe["prompt_lens"]} == set(buckets)
    assert len(set(probe["prompt_lens"])) == 3 and probe["decode_steps"] >= 12
    assert max(probe["prompt_lens"][:probe["logit_rows"]]) <= probe["stripe"]
    # the longest row's steps pass position 256: the decode kernel's third block is read
    assert 256 + 8 <= max(probe["prompt_lens"]) + probe["decode_steps"] <= 384
    assert max(probe["prompt_lens"]) == TRAFFIC["prompt_tokens"]["max"]
    # the longest prompt and answer of the traffic fit a stripe, and a prompt is one final chunk
    assert TRAFFIC["prompt_tokens"]["max"] + TRAFFIC["max_tokens"]["max"] <= 384
    assert TRAFFIC["prompt_tokens"]["max"] <= run["engine"]["prefill_chunk"]


def test_published_block_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not beside this checkout")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert CONFIG["published"] == row["config"] and CONFIG["source"] == row["source_url"]
    assert next(c for c in MANIFEST["configs"] if c["name"] == NAME)["source"] == row["source_url"]


def test_rehearsal_fixture_cannot_pass_for_the_benchmark():
    rehearsal = common.load_manifest(os.path.join(common.BENCH_DIR, "rehearsal-looped-dense.json"))
    assert rehearsal["rehearsal"] is True and TINY["source"].startswith("none")
    assert family.model_kwargs(TINY)["loop_passes"] == 3


def test_cell_traffic_and_metric_entries():
    cell = common.find_cell(MANIFEST, CELL)
    assert MANIFEST["workloads"][-1] is cell and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (NAME, "reason-chat-closed-24")
    assert len(MANIFEST["workloads"]) == 11 and all(w["chips"] == 1 for w in MANIFEST["workloads"])
    assert (TRAFFIC["kind"], TRAFFIC["clients"], TRAFFIC["pool"], TRAFFIC["stream"],
            TRAFFIC["ignore_eos"]) == ("looped_closed_loop", 24, 24, False, True)
    assert TRAFFIC["prompt_tokens"] == {"dist": "lognormal", "median": 96, "sigma": 0.6,
                                        "min": 32, "max": 224}
    assert TRAFFIC["max_tokens"]["dist"] == "lognormal" and TRAFFIC["max_tokens"]["sigma"] == 0.5
    assert (TRAFFIC["temperature"], TRAFFIC["ramp_seconds"], TRAFFIC["trace_seconds"]) == (0.0, 5, 6)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m["name"] for m in MANIFEST["per_layer"][-len(NEW_READERS):]] == NEW_READERS
    for name in NEW_READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert len(name) <= 64 and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", name + ".py"))
    assert all(by_name[n]["unit"] == "%" for n in NEW_READERS if "share" in n)
    assert all(CELL not in by_name[n]["workloads"] for n in NOT_JOINED)
    assert CELL in next(m for m in MANIFEST["end_to_end"] if m["name"] == "serve_tok_s")["workloads"]
    joined = [m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [])]
    assert {"program.scope_coverage.serve", "program.prefill_final_chunk_ms",
            "engine.decode_in_chunk_share.window", "kernel.decode_read_efficiency.window",
            "entry.programs_restored_share", "program.decode_step_ms"} <= set(joined)
    assert all(m["workloads"][-1] == CELL for m in MANIFEST["per_layer"]
               if CELL in m.get("workloads", []))  # appended: nothing before it moved


def test_parameter_count_and_bytes_a_token_at_the_published_sizes():
    assert family.layer_params(CONFIG) == LAYER == 51_388_416
    assert family.param_count(CONFIG) == 48 * LAYER + 2 * TABLE + 2048 + 2048 + 1 == 2_667_974_657
    # 2 x 16 heads x 128 x 2 bytes a row, 48 layers x 4 passes = 192 rows
    assert family.kv_bytes_per_token(CONFIG) == 8192 * 192 == 1_572_864
    assert family.step_matmul_bytes(CONFIG) == 2 * (4 * 48 * LAYER_MATMULS + TABLE)
    weights = family.step_weight_bytes(CONFIG)
    assert weights == pytest.approx(19.93e9, rel=1e-3)
    assert family.step_needed_bytes(CONFIG, 2400) == weights + 2400 * 1_572_864
    assert family.passes(TINY) == 3 and family.kv_bytes_per_token(TINY) == 2 * 4 * 16 * 2 * 2 * 3


def test_weights_from_a_seed_and_the_int8_control():
    import jax.numpy as jnp

    a, b = family.make_params(7, TINY, jnp.float32), family.make_params(7, TINY, jnp.float32)
    other = family.make_params(8, TINY, jnp.float32)
    assert set(a) == set(family.param_shapes(TINY))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq_full"], other["wq_full"])
    assert all(a[k].shape == shape for k, (shape, _) in family.param_shapes(TINY).items())
    for name in ("attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm", "final_norm"):
        assert np.all(np.asarray(a[name]) == 1.0)
    assert not np.asarray(a["exit_b"]).any()
    assert np.std(np.asarray(a["embed"])) == pytest.approx(family.EMBED_STD, rel=0.05)
    assert np.std(np.asarray(a["exit_w"])) == pytest.approx(64 ** -0.5, rel=0.4)
    assert np.std(np.asarray(a["w_down"])) == pytest.approx(128 ** -0.5, rel=0.05)
    cut = family.int8_roundtrip(b)  # donates what it cuts: b is a's twin
    same = {k for k in a if np.array_equal(np.asarray(a[k]), np.asarray(cut[k]))}
    assert same == {k for k in a if "norm" in k} | {"exit_b"}
    assert np.isfinite(np.asarray(cut["exit_w"])).all()
    err = np.abs(np.asarray(cut["w_up"]) - np.asarray(a["w_up"])).max()
    assert 0 < err < np.abs(np.asarray(a["w_up"])).max() / 100


# ------------------------------------------------------------- the readers


def synthetic():
    """Two decode steps and one final chunk inside a 1 s window, milliseconds
    in round numbers; and the window's own counters: 10 steps over 12 live
    slots reading 2,400 positions each; 10 decode launches and 2 final chunks
    that reported 4 passes a forward."""
    d, f = "jit(decode_fn)/while/body/closed_call/", "jit(chunk_final)/while/body/closed_call/"
    ops = []

    def add(start, ms, op_name):
        ops.append((start, start + ms * 1e-3, "fusion", op_name))
        return start + ms * 1e-3

    for step_start in (0.0, 0.1):
        t = step_start
        t = add(t, 3.0, d + "while/body/closed_call/attn_qkv/bte,ehd->bthd/dot_general")
        t = add(t, 2.0, d + "while/body/closed_call/kv_write/scatter")
        t = add(t, 8.0, d + "while/body/closed_call/attn_core/global/decode_attention/pallas_call")
        t = add(t, 2.0, d + "while/body/closed_call/attn_out/dot_general")
        t = add(t, 0.5, d + "while/body/closed_call/attn_out/norm/mul")
        t = add(t, 20.0, d + "while/body/closed_call/ffn/dot_general")
        t = add(t, 0.4, d + "norm/loop_exit/norm/mul")
        t = add(t, 0.1, d + "norm/loop_exit/select_n")
        t = add(t, 0.3, "jit(decode_fn)/lm_head/dot_general")
        t = add(t, 0.2, "jit(decode_fn)/sampling/argmax")
    add(0.2, 40.0, f + "while/body/closed_call/ffn/dot_general")
    parsed = {
        "window": (0.0, 1.0), "spans": [],
        "modules": [(0.0, 0.0365, "jit_decode_fn"), (0.1, 0.1365, "jit_decode_fn"),
                    (0.2, 0.24, "jit_chunk_final")],
        "ops": sorted(ops),
    }
    counters = {
        "decode_steps": 10, "decode_slot_steps": 120, "decode_steps_in_chunk": 2,
        "decode_kv_tokens_global": 10 * 2400, "decode_kv_positions_read": 10 * 3072,
        "prefill_chunks": {"mid": 0, "final": 2}, "prefill_programs": {"mid": 0, "final": 2},
        "loop_forwards": 10, "loop_stack_passes": 40,
        "loop_exit_rows": {"0": 0, "1": 0, "2": 0, "3": 122},
    }
    ctx = {
        "cell": {"name": CELL}, "config": CONFIG, "device_kind": "TPU v5 lite",
        "trace": {"busy_s": 0.113, "window_s": 1.0, "modules": {
            "jit_decode_fn": {"count": 2, "total_s": 0.073},
            "jit_chunk_final": {"count": 1, "total_s": 0.04}}},
        "extra": {"stats_at_end": {"counters": counters, "max_num_seqs": 12,
                                   "pools": [{"kv_bytes_per_token": 1572864.0}]}},
        "samples": [],
    }
    return parsed, ctx


def test_new_readers_on_a_hand_made_trace(monkeypatch):
    parsed, ctx = synthetic()
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: parsed)
    # the window's own counts are the context's: no .xplane.pb behind a hand-made trace
    monkeypatch.setattr(window_counts, "windowed", lambda c: c)
    read = {name: common.load_reader(name)(ctx) for name in NEW_READERS}
    bw = 819e9
    # 2,400 live positions in all 192 rows, in the 8 ms under attn_core
    assert read["kernel.decode_attention_hbm_share.looped.window"] == pytest.approx(
        100 * 2400 * 1_572_864 / bw / 8e-3)
    # the layers' matmuls four times and the head, under attn_qkv, attn_out, ffn, lm_head
    matmuls = 2 * (4 * 48 * LAYER_MATMULS + TABLE)
    assert read["kernel.decode_matmul_hbm_share.looped"] == pytest.approx(
        100 * matmuls / bw / 25.8e-3)
    step = matmuls + 2 * 4 * (48 * 4 * 2048 + 2 * 2048 + 1) + 2400 * 1_572_864
    assert read["program.decode_hbm_share.looped.window"] == pytest.approx(
        100 * step / bw / 36.5e-3)
    assert read["engine.loop_passes_per_forward.window"] == 4.0
    assert read["engine.kv_bytes_per_token"] == 1_572_864
    assert all(0 < read[n] <= 100 for n in NEW_READERS if "share" in n)
    # accepted readers this cell was appended to hold for it unedited
    assert common.load_reader("program.decode_step_ms")(ctx) == pytest.approx(36.5)
    assert common.load_reader("program.prefill_final_chunk_ms")(ctx) == pytest.approx(40.0)
    assert common.load_reader("kernel.decode_kv_write_ms")(ctx) == pytest.approx(2.0)
    # the loop_exit scope lies inside a name the accepted readers know
    assert scopes.scope_of(parsed["ops"][6][3]) == "norm"


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_scope_and_counters(name, monkeypatch):
    """The parent commit on another model's trace: a decode program with no
    ``loop_exit`` part, no ``loop_*`` counter, no window events. The result
    line then leaves the metric out; nothing raises."""
    parsed, ctx = synthetic()
    ctx["extra"]["stats_at_end"] = {"counters": {"prefill_chunks": {"mid": 0, "final": 2}},
                                    "pools": [{"stripe_len": 1024, "kv_bytes_per_token": 65536.0}]}
    flat = [(a, b, n, op.replace("decode_fn", "block_step").replace("norm/loop_exit/", ""))
            for a, b, n, op in parsed["ops"]]
    modules = [(a, b, n.replace("decode_fn", "block_step")) for a, b, n in parsed["modules"]]
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


def _rehearse(*more):
    out = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"), "--manifest",
         os.path.join(common.BENCH_DIR, "rehearsal-looped-dense.json"), "--workload",
         "rehearse-looped-dense-chat", "--seed", str(2**31 + 57), "--seconds", "2", "--trace", "0",
         *more],
        capture_output=True, text=True, timeout=900, cwd=common.ROOT,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``benchmark/run.py`` on the tiny twin of the cell: the replica with
    this kind's check behind the program's router and proxy, the family's
    weights from the seed, the probe through the engine's own loop with every
    slot bound and all ``passes * layers`` cache rows compared (float32: limits
    of 0.001), the repeated greedy request, a closed loop of requests whose
    answers are their ``max_tokens``, and a result line that can never pass
    for a chip's."""
    lines = _rehearse()
    result = lines[-1]
    assert result["correct"] is True and result["rehearsal"] is True and "metrics" not in result
    assert result["failed"] == 0 and result["rehearsal_metrics"]["serve_tok_s"]["value"] > 0
    checked = next(line for line in lines if "compared" in line)
    assert set(checked["compared"]) == set(TINY["run"]["limits"])
    assert all(c["ok"] and c["value"] < 1e-4 for c in checked["compared"].values())
    other = checked["not_limited"]
    assert other["kv_pass2_rel_rms"] < 1e-4 and other["exit_passes"] == [2]
    assert other["engine_generated"] == [5, 5, 5]
    assert other["loop_counts"]["loop_stack_passes"] == 3 * other["loop_counts"]["loop_forwards"] > 0
    window = next(line for line in lines if "stats_at_end" in line)
    assert window["compiles_in_window"] == 0 and window["requests"]["early_stop_share"] == 0
    counters = window["stats_at_end"]["counters"]
    assert counters["decode_steps_in_chunk"] > 0 and counters["prefill_chunks"]["mid"] == 0
    assert counters["loop_stack_passes"] == 3 * counters["loop_forwards"]
    assert window["stats_at_end"]["pools"][0]["kv_bytes_per_token"] == family.kv_bytes_per_token(
        TINY, 4)


def test_the_int8_control_is_not_correct_in_the_rehearsal_cell():
    lines = _rehearse("--control", "int8")
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    checked = next(line for line in lines if "compared" in line)
    assert not any(c["ok"] for c in checked["compared"].values())
