"""Family ``ssm_gqa_dense`` (PR 44): its configuration file against its own
``published`` block and the catalog row (nothing reduced), its traffic mix and
the session generator, its weights and int8 control, the counts of the whole
model and of what a step needs against hand-worked numbers at the published
widths, every reader of the new per-layer metrics on a hand-made trace and the
engine's counters, and the rehearsal cell end to end on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common, scopes, window_counts
from benchmark.families import ssm_gqa_dense as family
from benchmark.kinds import sessions
from benchmark.tests.test_manifest import check_config_file

MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
CELL = "granite4-h-micro-serve-sessions"
NAME = "granite-4.0-h-micro-serve-l40"
CONFIG = common.load_config(MANIFEST, NAME)
MIX = common.load_traffic("sessions-closed-48")
TINY = common.load_json(os.path.join(common.BENCH_DIR, "configs", "rehearse-ssm-gqa-dense-serve.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = [
    "kernel.ssm_decode_hbm_share.ssm_dense.window", "kernel.ssm_prefill_roofline_share.ssm_dense",
    "program.decode_hbm_share.ssm_dense.window", "kernel.snapshot_copy_hbm_share",
    "engine.snapshot_store_bytes", "kernel.decode_matmul_hbm_share.ssm_dense",
]
OTHER_SERVING_CELLS = ["mistral7b-serve-saturated", "laguna-xs2-serve-mixed",
                       "kanana2-serve-docs-shared", "nemotron3-super-serve-chat",
                       "solar-open2-serve-long-chat"]


# ------------------------------------------------------------- the data files


def test_configuration_file_passes_the_manifest_check_with_nothing_reduced():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] == CONFIG["reduced"] and MANIFEST["configs"][-1] is entry
    check_config_file(CONFIG, [])
    assert all(CONFIG[k] == v for k, v in CONFIG["published"].items())
    assert family.layer_rows(CONFIG) == {"ssm": 36, "full": 4, "all": 40}
    assert [i for i, t in enumerate(CONFIG["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
    assert set(CONFIG["assumed"]) >= {"ssm_precision", "time_step", "a_log", "conv", "gated_norm",
                                     "multipliers", "initialisation", "tokenizer", "engine"}
    assert set(CONFIG["left_out"]) >= {"rope_theta"} and CONFIG["deployment"]
    limits = CONFIG["run"]["limits"]
    assert set(limits) == {"kv_prefill_rel_rms", "kv_decode_rel_rms", "logits_rel_rms"}
    assert "my chip run" in CONFIG["run"]["limits_from"]


def test_published_block_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
    assert CONFIG["published"] == row["config"] and CONFIG["source"] == row["source_url"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["source"] == row["source_url"]


def test_rehearsal_fixture_passes_the_check_and_cannot_pass_for_the_benchmark():
    check_config_file(TINY, [])
    assert TINY["source"].startswith("none") and TINY["run"]["family"] == "ssm_gqa_dense"
    rehearsal = common.load_manifest(os.path.join(common.BENCH_DIR, "rehearsal-ssm-gqa-dense.json"))
    assert rehearsal["rehearsal"] is True
    (cell,) = rehearsal["workloads"]
    assert common.load_traffic(cell["traffic"])["kind"] == "sessions"


def test_cell_and_metric_entries():
    cell = common.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "sessions-closed-48", 1)
    assert MANIFEST["workloads"][-1] is cell and len(MANIFEST["workloads"]) == 7
    assert all(c["chips"] == 1 for c in MANIFEST["workloads"])
    assert [m["name"] for m in MANIFEST["per_layer"][-6:]] == NEW_READERS
    shared = 0
    for m in MANIFEST["per_layer"]:
        assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", m["name"] + ".py"))
        if m["name"] in NEW_READERS:
            assert (m["workloads"], m["moves"]) == ([CELL], "serve_tok_s")
        elif m.get("workloads", [])[:5] == OTHER_SERVING_CELLS:
            assert m["workloads"] == OTHER_SERVING_CELLS + [CELL]
            shared += 1
        elif CELL in m["workloads"]:
            assert m["workloads"][-1] == CELL
    assert shared == 19
    assert [m["name"] for m in common.metrics_for(MANIFEST, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]
    serve = next(m for m in MANIFEST["end_to_end"] if m["name"] == "serve_tok_s")
    assert serve["workloads"] == OTHER_SERVING_CELLS + [CELL] and serve["bound"] == 0.04


def test_traffic_mix_is_the_one_the_issue_names():
    assert (MIX["kind"], MIX["clients"], MIX["stream"], MIX["temperature"], MIX["ignore_eos"],
            MIX["scripts"], MIX["system_tokens"]) == ("sessions", 48, False, 0.0, True, 96, 512)
    assert MIX["user_tokens"] == {"dist": "lognormal", "median": 96, "sigma": 0.6, "min": 32, "max": 384}
    assert MIX["max_tokens"] == {"dist": "lognormal", "median": 96, "sigma": 0.5, "min": 32, "max": 256}
    assert (MIX["turns"], MIX["max_session_tokens"], MIX["stagger_turns"]) == ({"min": 4, "max": 8}, 4000, 4)
    assert (MIX["min_window_prefix_share"], MIX["hit_check_sessions"], MIX["hit_check_max_tokens"]) == (0.75, 8, 16)
    assert 0 < MIX["hit_check_max_kv_rel_rms"] < 1 and 0 < MIX["hit_check_max_state_rel_rms"] < 1
    engine = CONFIG["run"]["engine"]
    assert (engine["max_num_seqs"], MIX["clients"]) == (24, 2 * engine["max_num_seqs"])
    assert MIX["max_session_tokens"] + 1 <= engine["max_seq_len"] == 4096
    assert engine["prefill_chunk"] == 1024 and engine["prefill_buckets"] == [64, 128, 256, 512, 1024]
    # the store holds every client's live session, the system prompt and a few spare
    largest = family.snapshot_bytes(CONFIG, 4096)
    assert largest == 76_437_504 + 4096 * 8192 == 109_991_936
    assert engine["prefix_cache_entries"] >= MIX["clients"] + 1 + 3
    assert engine["prefix_cache_max_bytes"] >= (MIX["clients"] + 4) * family.snapshot_bytes(CONFIG, 2048)


# ------------------------------------------------------ the session generator


def test_the_scripts_are_one_set_for_every_seed_in_another_order():
    scripts = sessions.scripts(MIX)
    assert len(scripts) == 96 and all(4 <= len(s) <= 8 for s in scripts)
    assert sessions.scripts(MIX) == scripts  # nothing of it is drawn from a seed
    a, b = sessions.Sessions(MIX, 2347483651), sessions.Sessions(MIX, 2347483652)
    assert a.scripts == b.scripts == scripts and a.order != b.order
    assert sorted(a.order) == sorted(b.order) == list(range(96))
    assert a.system != b.system and len(a.system) == 511  # BOS and 511 bytes
    users = [u for s in scripts for u, _ in s]
    answers = [n for s in scripts for _, n in s]
    assert (min(users), max(users)) == (32, 384) and (min(answers), max(answers)) == (32, 256)
    assert 85 <= np.median(users) <= 105 and 85 <= np.median(answers) <= 105


def test_a_sessions_turns_grow_by_the_turn_before_and_stay_inside_the_stripe():
    made = sessions.Sessions(MIX, 2347483651)
    longest = 0
    for n in range(96):
        script, before = made.script(n), None
        for k, (user, answer) in enumerate(script):
            req = made.turn(n, k)
            assert req["max_tokens"] == answer and req["prompt_tokens"] == 1 + len(req["prompt"])
            assert req["prompt_tokens"] + answer <= 4000
            assert req["prompt"].startswith(made.system)
            if before is not None:  # the turn before, its scripted reply, this user message
                assert req["prompt"].startswith(before["prompt"])
                grown = req["prompt_tokens"] - before["prompt_tokens"]
                assert grown == before["max_tokens"] + user and 64 <= grown <= 640
            before = req
            longest = max(longest, req["prompt_tokens"])
        assert made.turn(n, len(script)) is None
        made.drop(n)
    assert 2000 < longest <= 4000
    # two sessions share the system prompt and nothing behind it
    one, other = made.turn(0, 0)["prompt"], made.turn(96, 0)["prompt"]
    assert made.script(0) == made.script(96) and one[:511] == other[:511] and one[511:] != other[511:]


def test_the_shared_share_the_scripts_predict():
    """Of the prompt tokens a pass over the 96 scripts sends, nine tenths were
    an earlier prompt of the same session or the system prompt: what a store
    that keeps every live turn serves, well over the cell's floor of 0.75."""
    tokens = [sessions.script_tokens(MIX, s) for s in sessions.scripts(MIX)]
    share = sum(t["shared_tokens"] for t in tokens) / sum(t["prompt_tokens"] for t in tokens)
    assert 0.88 < share < 0.93


def test_the_check_sessions_rule_reads_the_state_and_the_seeded_turns_own_positions():
    """``snapshot_distance`` on hand-made entries: the state leaves (the
    largest), the keys and values behind the seed's length alone (a difference
    before it, which the seed copied, is not the seeded turn's); ``within`` is
    both under the file's limits."""
    rng = np.random.default_rng(0)
    want = {"length": 12, "k": rng.normal(size=(2, 2, 16, 4)), "v": rng.normal(size=(2, 2, 16, 4)),
            "state": {"ssm_state": rng.normal(size=(3, 4, 4)), "ssm_conv": rng.normal(size=(3, 3, 6))}}
    same = {**want, "state": dict(want["state"])}
    assert sessions.snapshot_distance(same, want, 8) == {"state": 0.0, "kv": 0.0}
    moved = {**want, "k": want["k"].copy(),
             "state": dict(want["state"], ssm_conv=want["state"]["ssm_conv"] * 1.5)}
    moved["k"][:, :, :8] += 1.0  # before the seed's length: not read
    moved["k"][:, :, 12:] += 1.0  # behind the prompt's end: not read
    assert sessions.snapshot_distance(moved, want, 8) == {"state": pytest.approx(0.5), "kv": 0.0}
    moved["k"][:, :, 8:12] *= 1.1
    read = sessions.snapshot_distance(moved, want, 8)
    assert read["kv"] == pytest.approx(0.1)
    limits = {"hit_check_max_state_rel_rms": 0.6, "hit_check_max_kv_rel_rms": 0.05}
    assert not sessions.within(read, limits) and sessions.within(dict(read, kv=0.04), limits)
    assert not sessions.within({"state": 0.7, "kv": 0.0}, limits)


# ------------------------------------------------------------------ the counts


def test_parameter_shapes_count_the_model_whole():
    assert family.param_count(CONFIG) == 3_191_396_096  # 6.38 GB in bfloat16
    assert round(2 * family.param_count(CONFIG) / 1e9, 2) == CONFIG["bytes"]["weights_gb_bf16"].__round__(2)
    assert family.ssm_dims(CONFIG) == {"inner": 4096, "bc": 128, "conv": 4352, "proj": 8512}
    assert family.ssm_params(CONFIG) == 2048 * 8512 + 4096 * 2048 + 5 * 4352 + 3 * 64 + 4096 == 25_847_232
    assert family.attention_params(CONFIG) == 2048 * 64 * (2 * 32 + 2 * 8) == 10_485_760
    assert family.ffn_params(CONFIG) == 3 * 2048 * 8192 == 50_331_648
    assert family.state_bytes_per_slot(CONFIG) == CONFIG["bytes"]["state_bytes_per_slot"] == 76_437_504
    assert family.kv_bytes_per_token(CONFIG) == CONFIG["bytes"]["kv_bytes_per_token"] == 8_192
    shapes = {k: s for k, (s, _) in family.param_shapes(CONFIG).items()}
    assert shapes["ssm_w_in"] == (36, 2048, 8512) and shapes["wq_full"] == (4, 2048, 32, 64)
    assert shapes["embed"] == (100_352, 2048) and "unembed" not in shapes


def test_needed_bytes_and_operations():
    c = CONFIG
    # 24 live rows: 36 layers' float32 state of 2 MB, in and out
    assert family.ssm_state_bytes(c, 24) == 2 * 24 * 36 * 2_097_152 == 3_623_878_656
    weights = 2 * (36 * 25_847_232 + 4 * 10_485_760 + 40 * (50_331_648 + 4096) + 2048 + 100_352 * 2048)
    assert family.decode_weight_bytes(c) == weights == 6_382_792_192
    assert family.decode_step_bytes(c, 24, 30_000) == weights + 2 * 24 * 76_437_504 + 30_000 * 8192
    # a 200-token final chunk: one group's pair scores once (128), not 64 times
    pairs = 100.5
    assert family.ssm_scan_flops(c, 200) == 200 * 2 * (pairs * (128 + 4096) + 2 * 64 * 64 * 128)
    assert family.ssm_scan_flops(c, 1000) == 1000 * 2 * (128.5 * (128 + 4096) + 2 * 64 * 64 * 128)
    assert family.ssm_scan_bytes(c, 200, 1) == 200 * (4352 * 2 + 64 * 4 + 4096 * 4) + 2 * 2_097_152


def test_weights_from_a_seed_and_the_int8_control():
    import jax
    import jax.numpy as jnp

    a = family.make_params(5, TINY, jnp.float32)
    b = family.make_params(5, TINY, jnp.float32)
    other = family.make_params(6, TINY, jnp.float32)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["ssm_w_in"], other["ssm_w_in"])
    # the looked-up rows enter the stream at unit scale: entries of 1 / embedding_multiplier
    assert abs(float(jnp.std(a["embed"])) * TINY["embedding_multiplier"] - 1) < 0.05
    step = np.asarray(jax.nn.softplus(a["ssm_dt_bias"]))
    assert (step >= 0.001 * 0.999).all() and (step <= 0.1 * 1.001).all()
    rate = np.exp(np.asarray(a["ssm_a_log"]))
    assert (rate >= 1).all() and (rate <= 16).all() and np.array_equal(a["ssm_d"], np.ones_like(a["ssm_d"]))
    cut = family.int8_roundtrip(jax.tree.map(jnp.copy, a))
    for left_alone in ("attn_norm", "mlp_norm", "ssm_norm", "ssm_dt_bias", "ssm_a_log", "ssm_conv_b"):
        assert np.array_equal(cut[left_alone], a[left_alone]), left_alone
    w = np.asarray(a["w_gate"])
    err = np.abs(np.asarray(cut["w_gate"]) - w)
    scale = np.abs(w).max(axis=1, keepdims=True) / 127.0  # one scale a layer and output column
    assert (err <= 0.5 * scale + 1e-7).all() and err.max() > 0
    for cut_too in ("ssm_w_in", "ssm_w_out", "ssm_conv_w", "wq_full", "wk", "w_down", "embed"):
        assert not np.array_equal(cut[cut_too], a[cut_too]), cut_too


# ------------------------------------------------------------- the readers


def synthetic():
    """Two decode steps and one final chunk, four stores and three seeds
    inside a 1 s window, milliseconds in round numbers; and the window's own
    counters: 10 decode steps over 20 live rows and 30,000 live tokens each, 4
    final chunks of 200 real tokens, 4 snapshots stored at 2,048 positions and
    3 seeded from."""
    d, f = "jit(decode_fn)/", "jit(chunk_final)/"
    ops = []

    def add(start, ms, op_name):
        ops.append((start, start + ms * 1e-3, "fusion", op_name))
        return start + ms * 1e-3

    for step_start in (0.0, 0.1):
        t = step_start
        t = add(t, 2.0, d + "attn_qkv/ssm_mixer/bte,ef->btf/dot_general")
        t = add(t, 0.5, d + "attn_core/ssm_mixer/ssm_conv/mul")
        t = add(t, 6.0, d + "attn_core/ssm_mixer/ssm_step/ssm_step")
        t = add(t, 1.0, d + "attn_out/ssm_mixer/btf,fe->bte/dot_general")
        t = add(t, 0.3, d + "attn_core/global/decode_attention")
        t = add(t, 4.4, d + "ffn/dot_general")
        t = add(t, 0.6, d + "lm_head/dot_general")
    t = add(0.3, 5.0, f + "attn_core/ssm_mixer/ssm_scan/dot_general")
    t = add(t, 0.4, f + "attn_core/ssm_mixer/ssm_conv/mul")
    t = add(t, 6.0, f + "ffn/dot_general")
    parsed = {
        "window": (0.0, 1.0), "spans": [],
        "modules": [(0.0, 0.0148, "jit_decode_fn"), (0.1, 0.1148, "jit_decode_fn"),
                    (0.3, 0.3114, "jit_chunk_final")],
        "ops": sorted(ops),
    }
    entry = 76_437_504 + 2048 * 8192
    counters = {
        "decode_steps": 10, "decode_slot_steps": 10 * 20, "decode_kv_tokens_global": 10 * 30_000,
        "prefill_chunks": {"mid": 0, "final": 4}, "prefill_programs": {"mid": 0, "final": 4},
        "prefill_query_tokens": {"chunk_mid": 0, "chunk_final": 4 * 200},
        "snapshots_stored": 4, "snapshots_hit": 3, "snapshots_evicted": 2,
        "snapshot_store_bytes": 4 * entry, "snapshot_seed_bytes": 3 * entry,
    }
    ctx = {
        "cell": {"name": CELL}, "config": CONFIG, "device_kind": "TPU v5 lite",
        "trace": {"busy_s": 0.04, "window_s": 1.0, "modules": {
            "jit_decode_fn": {"count": 2, "total_s": 0.0296},
            "jit_chunk_final": {"count": 1, "total_s": 0.0114},
            "jit_store_snapshot": {"count": 4, "total_s": 0.0004},
            "jit_seed_prefix": {"count": 3, "total_s": 0.0012}}},
        "extra": {"stats_at_end": {"counters": counters, "max_num_seqs": 24,
                                   "prefix_cache_bytes": 4_650_000_000,
                                   "pools": [{"state_bytes_per_slot": 76_437_504}]}},
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
    # 20 live rows' state in 36 layers, both ways, in the 6 ms under ssm_step
    assert read["kernel.ssm_decode_hbm_share.ssm_dense.window"] == pytest.approx(
        100 * 2 * 20 * 36 * 2_097_152 / bw / 6e-3)
    step = 6_382_792_192 + 2 * 20 * 76_437_504 + 30_000 * 8192
    assert read["program.decode_hbm_share.ssm_dense.window"] == pytest.approx(100 * step / bw / 14.8e-3)
    # a final chunk of 200 real tokens: the bytes of a row's state bind, 36 layers
    flops, moved = family.ssm_scan_flops(CONFIG, 200), family.ssm_scan_bytes(CONFIG, 200, 1)
    assert flops / peak < moved / bw
    assert read["kernel.ssm_prefill_roofline_share.ssm_dense"] == pytest.approx(100 * 36 * moved / bw / 5e-3)
    # 4 stores cut 2,048 positions of keys and values, 3 seeds copy a whole entry; each read and written
    moved = 2 * 4 * 2048 * 8192 + 2 * 3 * (76_437_504 + 2048 * 8192)
    assert read["kernel.snapshot_copy_hbm_share"] == pytest.approx(100 * moved / bw / 1.6e-3)
    assert read["engine.snapshot_store_bytes"] == 4_650_000_000
    # the weights once in the 2 + 1 + 4.4 + 0.6 ms a step spends under the matmuls' scopes
    assert read["kernel.decode_matmul_hbm_share.ssm_dense"] == pytest.approx(
        100 * 6_382_792_192 / bw / 8e-3)
    assert all(0 < read[n] <= 100 for n in NEW_READERS if n != "engine.snapshot_store_bytes")
    # the accepted readers this cell was appended to hold for it unedited
    assert common.load_reader("engine.state_bytes_per_slot")(ctx) == 76_437_504
    assert common.load_reader("program.prefill_final_chunk_ms")(ctx) == pytest.approx(11.4)
    assert common.load_reader("kernel.prefix_seed_ms")(ctx) == pytest.approx(0.4)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_scopes_and_counters(name, monkeypatch):
    """The parent commit on another model's trace: no snapshot counters, no
    ``ssm_step`` scope, no window events. The result line then leaves the
    metric out; nothing raises."""
    parsed, ctx = synthetic()
    ctx["extra"]["stats_at_end"] = {"counters": {"decode_steps": 10}, "pools": [{"stripe_len": 1024}]}
    flat = [(a, b, n, op.replace("/ssm_mixer", "").replace("/ssm_scan", "").replace("/ssm_step", ""))
            for a, b, n, op in parsed["ops"]]
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: dict(parsed, ops=flat))
    monkeypatch.setattr(window_counts, "windowed", lambda c: c)
    ctx["trace"]["modules"] = {}
    # (the matmuls' scopes are every program's: their reader needs the trace alone)
    matmuls = name == "kernel.decode_matmul_hbm_share.ssm_dense"
    assert matmuls or common.load_reader(name)(ctx) is None
    # a trace that holds no ``engine.counts`` event, and no trace and no stats at all
    monkeypatch.setattr(window_counts, "windowed", lambda c: None)
    if name != "engine.snapshot_store_bytes" and not matmuls:
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
    (float32: limits of 0.001), the repeated greedy request, the check
    sessions (turn 2 cold, turn 1, turn 2 seeded from exactly turn 1's
    length, what the two leave within the file's limits), the system prompt, six staggered clients
    playing sessions, and a result line that can never pass for a chip's."""
    out = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"), "--manifest",
         os.path.join(common.BENCH_DIR, "rehearsal-ssm-gqa-dense.json"), "--workload",
         "rehearse-ssm-gqa-dense-sessions", "--seed", str(2**31 + 77), "--seconds", "3", "--trace", "0"],
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
    checked = next(line["checked"] for line in lines if "checked" in line)
    assert checked["ok"] and len(checked["sessions"]) == 2
    for s in checked["sessions"]:
        assert s["from_prefix"] == [0, 0, s["turn1_tokens"]] and s["within"]
        assert 0 < s["left"]["state"] < 1e-4 and 0 < s["left"]["kv"] < 1e-4  # float32
    window = next(line for line in lines if "prefix_in_window" in line)
    assert window["prefix_in_window"]["share"] >= 0.5 and window["compiles_in_window"] == 0
    assert set(window["turn_mix"]) == {"first", "last"} and len(window["turn_mix"]["first"]) >= 3
    stats = window["stats_at_end"]
    assert stats["pools"][0]["state_bytes_per_slot"] == family.state_bytes_per_slot(TINY, dtype_bytes=4)
    counters = stats["counters"]
    assert counters["snapshots_stored"] > counters["snapshots_hit"] > 0
    assert counters["snapshots_evicted"] > 0 and stats["prefix_cache_entries"] <= 12
    assert counters["prompt_tokens_from_prefix"] == counters["prefix_seed_tokens"] > 0
