"""Family ``moe_window_gqa`` (PR 28): its configuration file against its own
``published`` block and the catalog row, its traffic mix, its weights and
int8 control, the counts of what a step needs, the reference's pieces, and
every reader of the new per-layer metrics on a hand-made trace and the
engine's counters."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark import common, moe_window, scopes, traffic
from benchmark.families import moe_window_gqa as family
from benchmark.tests.test_manifest import check_config_file
from benchmark.tests.test_scopes import DATA, SERVE, recorded_ctx, uses_scopes

MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
CELL = "laguna-xs2-serve-mixed"
CONFIG = common.load_config(MANIFEST, "laguna-xs.2-serve-l5")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"]
NEW_READERS = [
    "kernel.moe_decode_hbm_share", "kernel.moe_prefill_roofline_share",
    "kernel.decode_window_attention_hbm_share", "kernel.decode_global_attention_hbm_share",
    "program.decode_hbm_share.moe_window", "program.prefill_chunk_ms",
    "program.moe_experts_touched_share",
]


# ------------------------------------------------------------- the data files


def test_configuration_file_passes_the_manifest_check():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "laguna-xs.2-serve-l5")
    assert entry["reduced"] == REDUCED
    check_config_file(CONFIG, REDUCED)
    n = CONFIG["num_hidden_layers"]
    assert n == 5
    for key in REDUCED[1:]:  # the per-layer lists are the published ones' first entries
        assert CONFIG[key] == CONFIG["published"][key][:n]
    assert CONFIG["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert set(CONFIG["assumed"]) >= {"gate", "router", "shared_expert", "qk_norm", "window"}
    # every number of the comparison is limited: the prompt's keys and values hold the
    # precision, the decode program's and the head's hold a gross fault (limits_from)
    assert set(CONFIG["run"]["limits"]) == {"kv_prefill_rel_rms", "kv_decode_rel_rms", "logits_rel_rms"}
    assert CONFIG["run"]["probe"]["decode_steps"] >= 32


def test_published_block_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    assert CONFIG["published"] == row["config"]
    assert CONFIG["source"] == row["source_url"]


def test_rehearsal_fixture_passes_the_check_and_cannot_pass_for_the_benchmark():
    rehearsal = common.load_manifest(os.path.join(common.BENCH_DIR, "rehearsal-moe-window.json"))
    assert rehearsal["rehearsal"] is True
    assert not {c["name"] for c in rehearsal["workloads"]} & {c["name"] for c in MANIFEST["workloads"]}
    for entry in rehearsal["configs"]:
        check_config_file(common.load_json(os.path.join(common.ROOT, entry["file"])), [])


def test_cell_and_metric_entries():
    cell = common.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("laguna-xs.2-serve-l5", "mixed-closed-64", 1)
    reported = {m["name"] for m in common.metrics_for(MANIFEST, "per_layer", CELL)}
    assert set(NEW_READERS) <= reported
    assert not {"program.decode_hbm_share", "kernel.decode_matmul_hbm_share",
                "kernel.decode_attention_hbm_share"} & reported  # a dense model's counts
    assert [m["name"] for m in common.metrics_for(MANIFEST, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]


def test_traffic_mix_is_the_one_the_issue_names():
    mix = common.load_traffic("mixed-closed-64")
    assert (mix["kind"], mix["clients"], mix["stream"], mix["pool"], mix["temperature"]) == (
        "closed_loop", 64, False, 64, 0.0)
    prompts = traffic.stratified(mix["prompt_tokens"], mix["pool"])
    answers = traffic.stratified(mix["max_tokens"], mix["pool"])
    window, engine = CONFIG["sliding_window"], CONFIG["run"]["engine"]
    assert sum(p < window for p in prompts) == 22 and sum(p > 2048 for p in prompts) == 10
    assert round(sum(prompts) / 64) == 1114 and round(sum(answers) / 64) == 151
    assert (min(prompts), max(prompts), min(answers), max(answers)) == (68, 3584, 32, 448)
    assert max(prompts) + max(answers) + 1 <= engine["max_seq_len"]
    # the warm-up reaches every final-chunk width alone and behind a middle chunk
    chunk, widths = 256, set()
    for p in mix["warmup_prompt_tokens"]:
        last = p % chunk or chunk
        widths.add((next(b for b in (32, 64, 128, 256) if last <= b), p > chunk))
    assert widths >= {(64, False), (128, False), (256, False),
                      (32, True), (64, True), (128, True), (256, True)}
    assert max(mix["warmup_prompt_tokens"]) == max(prompts)


# ------------------------------------------------------- weights and counts


def test_parameter_shapes_count_the_cut_and_its_bytes():
    shapes = family.param_shapes(CONFIG)
    count = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert abs(count / 3.87e9 - 1) < 5e-3 and abs(2 * count / 7.74e9 - 1) < 5e-3
    assert shapes["wq_full"][0] == (2, 2048, 48, 128) and shapes["wq_sliding"][0] == (3, 2048, 64, 128)
    assert shapes["wg_sliding"][0] == (3, 2048, 64) and shapes["wk"][0] == (5, 2048, 8, 128)
    assert shapes["moe_w_down"] == ((4, 256, 512, 2048), 512) and shapes["w_gate"][0] == (1, 2048, 8192)
    whole = dict(CONFIG["published"])
    full = sum(int(np.prod(s)) for s, _ in family.param_shapes(whole).values())
    assert abs(full / 33.44e9 - 1) < 1e-3  # the published 33.4B


def test_needed_bytes_and_operations():
    c = CONFIG
    assert family.expert_params(c) == 3 * 2048 * 512
    assert family.moe_fixed_params(c) == 2048 * 256 + 3 * 2048 * 512
    every = family.moe_needed_bytes(c, 4, 4 * 256)
    assert abs(every / 6.47e9 - 1) < 5e-3  # four whole banks, routers and shared experts
    assert family.moe_needed_bytes(c, 4, 4 * 128) < every
    # a 256-token chunk: 8 of 256 experts a token is a 32nd of every-expert work
    needed = family.moe_needed_flops(c, 1, 256)
    assert abs(needed / (2 * 256 * (8 * 3 * 2048 * 512 + 2048 * 256 + 3 * 2048 * 512)) - 1) < 1e-9
    assert family.kv_bytes_per_token_layer(c) == 4096
    assert 20 * 1024 == family.kv_bytes_per_token_layer(c) * c["num_hidden_layers"]
    # all experts touched: every weight but the embedding table, 7.33 GB
    assert abs(family.decode_weight_bytes(c, 256) / 7.33e9 - 1) < 5e-3
    assert family.attention_params(c, "sliding") == 37_879_808  # the issue's 37.9 M


def test_weights_from_a_seed_and_the_int8_control():
    import jax
    import jax.numpy as jnp

    tiny = common.load_json(os.path.join(common.BENCH_DIR, "configs", "rehearse-moe-window-serve.json"))
    a = family.make_params(11, tiny, jnp.float32)
    b = family.make_params(11, tiny, jnp.float32)
    c = family.make_params(12, tiny, jnp.float32)
    assert {k: v.shape for k, v in a.items()} == {k: s for k, (s, _) in family.param_shapes(tiny).items()}
    assert all(np.array_equal(a[k], b[k]) for k in a) and not np.array_equal(a["wk"], c["wk"])
    assert abs(float(jnp.std(a["moe_w_down"])) / 32 ** -0.5 - 1) < 0.05
    assert abs(float(jnp.std(a["wo_sliding"])) / (8 * 16) ** -0.5 - 1) < 0.05
    bank = np.asarray(a["moe_w_gate"])
    cut = family.int8_roundtrip(jax.tree.map(jnp.copy, a))
    assert np.array_equal(cut["attn_norm"], a["attn_norm"])
    err = np.abs(np.asarray(cut["moe_w_gate"]) - bank)
    # one scale per layer, expert and output column: half a step of that column's range
    step = np.abs(bank).max(axis=2, keepdims=True) / 127.0
    assert (err <= 0.5 * step + 1e-7).all() and err.max() > 0


# ---------------------------------------------------------- the reference


def test_reference_window_router_and_rope_pieces():
    import jax.numpy as jnp

    from benchmark import reference_moe_window as ref

    rng = np.random.default_rng(0)
    e, heads, kv, d, t = 32, 4, 2, 8, 12
    w = {"attn_norm": np.ones(e, np.float32),
         "wq": 0.1 * rng.normal(size=(e, heads, d)).astype(np.float32),  # diffuse attention
         "wk": 0.1 * rng.normal(size=(e, kv, d)).astype(np.float32),
         "wv": rng.normal(size=(e, kv, d)).astype(np.float32),
         "wo": rng.normal(size=(heads, d, e)).astype(np.float32),
         "wg": rng.normal(size=(e, heads)).astype(np.float32)}
    x = rng.normal(size=(1, t, e)).astype(np.float32)
    pos = np.arange(t, dtype=np.int32)[None]
    inv = (1.0 / 10000 ** (np.arange(0, d, 2) / d)).astype(np.float32)
    kw = dict(kv_heads=kv, inv_freq=inv, factor=1.0, eps=1e-6)
    base, _, _ = ref.attention_part(jnp.asarray(x), w, pos, window=4, **kw)
    moved = x.copy()
    moved[0, 2] += 1.0  # position 2 is outside the window of positions 6 and later
    got, _, _ = ref.attention_part(jnp.asarray(moved), w, pos, window=4, **kw)
    assert np.allclose(got[0, 6:], base[0, 6:], atol=1e-5) and not np.allclose(got[0, 5], base[0, 5])
    causal, _, _ = ref.attention_part(jnp.asarray(moved), w, pos, window=None, **kw)
    whole, _, _ = ref.attention_part(jnp.asarray(x), w, pos, window=None, **kw)
    assert not np.allclose(causal[0, 11], whole[0, 11])
    # routing weights: k nonzeros a token, summing to one
    h, weights, idx = ref.route(
        jnp.asarray(x), {"mlp_norm": np.ones(e, np.float32),
                         "moe_router": rng.normal(size=(e, 16)).astype(np.float32)}, top_k=4, eps=1e-6)
    assert np.allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    assert ((np.asarray(weights) > 0).sum(-1) == 4).all() and idx.shape == (1, t, 4)
    # YaRN at the published settings: the highest frequency kept, the lowest divided by 64
    table, factor = ref.rope_tables(CONFIG, "full_attention")
    plain = 1.0 / 500000.0 ** (np.arange(0, 64, 2) / 64)
    assert len(table) == 32 and factor == 1.4158883083359672
    assert np.isclose(table[0], plain[0]) and np.isclose(table[-1], plain[-1] / 64, rtol=1e-5)
    sliding, one = ref.rope_tables(CONFIG, "sliding_attention")
    assert len(sliding) == 64 and one == 1.0 and np.isclose(sliding[1], 10000.0 ** (-2 / 128))


# ------------------------------------------------------------- the readers


def test_inner_scope_of_an_op_name():
    path = "jit(decode_fn)/while/body/attn_core/window/bktgs,bksd->btkgd/dot_general"
    assert moe_window.inner_of(path, "attn_core") == "window"
    assert scopes.scope_of(path) == "attn_core"  # the old readers book it where they did
    assert moe_window.inner_of("jit(chunk_mid)/moe_ffn/experts/ragged_dot", "moe_ffn") == "experts"
    assert moe_window.inner_of("jit(decode_fn)/attn_out/gate/logistic", "attn_out") == "gate"
    assert moe_window.inner_of("jit(decode_fn)/ffn/mul", "attn_core") is None
    assert moe_window.inner_of("jit(attn_core)/window/mul", "attn_core") is None


def synthetic():
    """Two decode steps and one middle chunk inside a 1 s window, milliseconds
    in round numbers; and the counters of an engine that ran 10 decode steps
    of 32 slots and 5 chunk runs over 4 expert layers."""
    d, m = "jit(decode_fn)/", "jit(chunk_mid)/"
    ops, t = [], 0.0

    def add(start, ms, op_name):
        ops.append((start, start + ms * 1e-3, "fusion", op_name))
        return start + ms * 1e-3

    for step_start in (0.0, 0.1):
        t = step_start
        t = add(t, 8.0, d + "moe_ffn/experts/dot_general")
        t = add(t, 1.0, d + "moe_ffn/router/dot_general")
        t = add(t, 1.0, d + "moe_ffn/shared_expert/dot_general")
        t = add(t, 2.0, d + "attn_core/global/dot_general")
        t = add(t, 1.0, d + "attn_core/window/gather")
        t = add(t, 3.0, d + "attn_qkv/dot_general")
    t = add(0.2, 10.0, m + "moe_ffn/experts/ragged_dot")
    t = add(t, 2.0, m + "attn_core/global/dot_general")
    parsed = {
        "window": (0.0, 1.0), "spans": [],
        "modules": [(0.0, 0.016, "jit_decode_fn"), (0.1, 0.116, "jit_decode_fn"),
                    (0.2, 0.212, "jit_chunk_mid")],
        "ops": sorted(ops),
    }
    counters = {
        "decode_steps": 10,
        "decode_kv_tokens_global": 10 * 32 * 1000, "decode_kv_tokens_window": 10 * 32 * 400,
        "moe_layer_steps": {"decode": 40, "chunk_mid": 12, "chunk_final": 8},
        "moe_assignments": {"decode": 40 * 256, "chunk_mid": 12 * 2048, "chunk_final": 8 * 512},
        "moe_experts_touched": {"decode": 40 * 160, "chunk_mid": 12 * 256, "chunk_final": 8 * 200},
        "moe_max_expert_load_sum": {"decode": 40 * 5, "chunk_mid": 12 * 20, "chunk_final": 8 * 9},
    }
    ctx = {
        "cell": {"name": CELL}, "config": CONFIG, "device_kind": "TPU v5 lite",
        "trace": {"busy_s": 0.044, "window_s": 1.0, "modules": {
            "jit_decode_fn": {"count": 2, "total_s": 0.032},
            "jit_chunk_mid": {"count": 1, "total_s": 0.012}}},
        "extra": {"stats_at_end": {"counters": counters, "max_num_seqs": 32}}, "samples": [],
    }
    return parsed, ctx


@pytest.fixture
def ctx(monkeypatch):
    parsed, ctx = synthetic()
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: parsed)
    return ctx


def test_new_readers_on_a_hand_made_trace(ctx):
    read = {name: common.load_reader(name)(ctx) for name in NEW_READERS}
    bw, flops = 819e9, 197e12
    c = CONFIG
    # decode: 4 layers of router + shared expert + 160 touched experts, in 10 ms
    need = 2 * (4 * family.moe_fixed_params(c) + 4 * 160 * family.expert_params(c))
    assert read["kernel.moe_decode_hbm_share"] == pytest.approx(100 * need / bw / 10e-3)
    # chunk: all 256 experts touched in the 3 expert layers a middle chunk has to run (nothing reads
    # the last layer's feed-forward); bytes bound (4.85 GB / 819 GB/s > 85 GFLOP / 197 TFLOP/s)
    assert family.chunk_mid_expert_layers(c) == 3
    assert family.chunk_mid_expert_layers(dict(c, mlp_layer_types=["sparse", "sparse", "dense"])) == 2
    by_bytes = family.moe_needed_bytes(c, 3, 3 * 256) / bw
    assert by_bytes > family.moe_needed_flops(c, 3, 256) / flops
    assert read["kernel.moe_prefill_roofline_share"] == pytest.approx(100 * by_bytes / 10e-3)  # under moe_ffn
    # attention: 32 slots x 400 (window) over 3 sliding layers in 1 ms, x 1000 over 2 full in 2 ms
    assert read["kernel.decode_window_attention_hbm_share"] == pytest.approx(
        100 * 32 * 400 * 3 * 4096 / bw / 1e-3)
    assert read["kernel.decode_global_attention_hbm_share"] == pytest.approx(
        100 * 32 * 1000 * 2 * 4096 / bw / 2e-3)
    whole = family.decode_weight_bytes(c, 160) + 4096 * (32 * 400 * 3 + 32 * 1000 * 2)
    assert read["program.decode_hbm_share.moe_window"] == pytest.approx(100 * whole / bw / 16e-3)
    assert read["program.prefill_chunk_ms"] == pytest.approx(12.0)
    assert read["program.moe_experts_touched_share"] == pytest.approx(
        100 * (40 * 160 + 12 * 256 + 8 * 200) / (60 * 256))
    assert all(0 < read[n] <= 100 for n in NEW_READERS if n != "program.prefill_chunk_ms")


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_scopes_and_counters(name, monkeypatch):
    """The parent commit: no inner scope, no routing or window counter, and
    a dense model's trace. The result line then leaves the metric out."""
    parsed, ctx = synthetic()
    ctx["extra"]["stats_at_end"]["counters"] = {"decode_steps": 10}
    flat = [(a, b, n, op.replace("/window", "").replace("/global", "").replace("moe_ffn/experts", "ffn")
             .replace("moe_ffn/router", "ffn").replace("moe_ffn/shared_expert", "ffn"))
            for a, b, n, op in parsed["ops"]]
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: dict(parsed, ops=flat))
    value = common.load_reader(name)(ctx)
    assert value is None or name == "program.prefill_chunk_ms"
    # and with no trace at all
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: None)
    ctx["trace"]["modules"] = {}
    assert common.load_reader(name)(ctx) is None


# --- the shared readers, for every cell that lists them (see benchmark/conftest.py): the two
# checks of test_scopes.py that unpack one cell a metric, assertion for assertion, a cell a case

SHARED = [(m, cell) for m in MANIFEST["per_layer"] for cell in m.get("workloads", [])
          if len(m.get("workloads", [])) > 1]
SCOPED = [(m, cell) for m, cell in SHARED if uses_scopes(m)]  # those conftest.py marks


def ids(v):
    return v if isinstance(v, str) else v["name"]


def test_the_cases_marked_as_expected_failures_are_the_ones_run_here_for_each_cell():
    marked = {m["name"] for m in MANIFEST["per_layer"] if len(m.get("workloads", ())) > 1 and uses_scopes(m)}
    assert marked == {m["name"] for m, _ in SCOPED} and len(SCOPED) == 2 * len(marked)
    assert {cell for _, cell in SCOPED} == {SERVE, CELL}


@pytest.mark.parametrize("m, cell", SHARED, ids=ids)
def test_shared_reader_on_the_recorded_trace_for_each_cell(m, cell, tmp_path, monkeypatch):
    """``test_reader_on_the_recorded_trace``: the trace recorded on a v5e
    (the program's own engine at small widths), laid out as either cell's."""
    config = common.load_config(MANIFEST, common.find_cell(MANIFEST, cell)["config"])
    ctx = recorded_ctx(tmp_path, monkeypatch, SERVE)
    if cell != SERVE:
        os.rename(tmp_path / ".bench_out" / SERVE, tmp_path / ".bench_out" / cell)
    ctx.update(cell={"name": cell}, config=config, spans={"replica_start_s": 12.0})
    value = common.load_reader(m["name"])(ctx)
    assert isinstance(value, float) and value > 0, m["name"]
    if m["unit"] == "%" and not m["name"].endswith("hbm_share"):
        assert value <= 100.0


@pytest.mark.parametrize("m, cell", SCOPED, ids=ids)
def test_shared_reader_finds_nothing_in_a_program_without_scopes_spans_or_counters(
        m, cell, tmp_path, monkeypatch):
    """``test_reader_finds_nothing_in_a_program_without_scopes_spans_or_counters``:
    the trace recorded on a v5e before the names came, stats with no counters.
    The reader returns None and does not raise."""
    from benchmark import trace

    monkeypatch.setattr(common, "ROOT", str(tmp_path))
    trace_dir = tmp_path / ".bench_out" / cell / "trace"
    trace_dir.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "v5e-small-step.xplane.pb"), trace_dir / "t.xplane.pb")
    scopes.read_xplane.cache_clear()
    ctx = dict(
        cell={"name": cell}, config={}, device_kind="TPU v5 lite",
        trace=trace.reduce_dir(str(trace_dir)), samples=[],
        extra={"stats_at_end": {"active_slots": 0, "max_num_seqs": 32}},
    )
    assert common.load_reader(m["name"])(ctx) is None
    assert common.load_reader(m["name"])(dict(ctx, cell={"name": "no-trace-here"}, extra={})) is None


@pytest.mark.parametrize("m, cell", [mc for mc in SHARED if mc not in SCOPED], ids=ids)
def test_shared_reader_without_scopes_finds_nothing_without_trace_or_stats(m, cell):
    if m["name"] in ("entry.replica_start_s", "engine.batch_occupancy"):
        pytest.skip("read from the harness's own spans and samples, which are always there")
    ctx = dict(cell={"name": "no-trace-here"}, config={}, device_kind="TPU v5 lite", samples=[],
               spans={}, extra={}, trace={"busy_s": 0.0, "window_s": 1.0, "modules": {}})
    assert common.load_reader(m["name"])(ctx) is None


# ------------------------------------------ the floor of the comparison, counted


def test_routing_swaps_counts_moved_choices_and_forcing_them_moves_the_program():
    """``benchmark/tools/routing_swaps.py`` on the rehearsal configuration in
    float32: program and reference choose the same experts (no swap, and the
    forced pass is the free one); handed other choices, the program follows
    them, its keys and values after the first expert layer move, and the
    count says how many."""
    import importlib.util

    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig
    from ray_tpu.llm.config import resolve_llama_config

    from benchmark import compare

    spec = importlib.util.spec_from_file_location(
        "routing_swaps", os.path.join(common.BENCH_DIR, "tools", "routing_swaps.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    tiny = common.load_json(os.path.join(common.BENCH_DIR, "configs", "rehearse-moe-window-serve.json"))
    run = tiny["run"]
    cfg = resolve_llama_config(family.served_model(tiny, 0), EngineConfig(dtype=run["dtype"], **run["engine"]))
    params = family.make_params(7, tiny, jnp.float32)
    rows = compare.probe_rows(7, run["probe"])
    out = tool.one_seed(family.Reference(tiny), tiny, cfg, params, rows)
    for side in ("free", "forced"):
        assert all(s == {"tokens_with_a_swap": 0.0, "choices_swapped": 0.0}
                   for s in out[side]["swaps_by_expert_layer"])
        assert max(out[side]["kv_rel_rms_by_layer"]) < 1e-4 and out[side]["logits_rel_rms"] < 1e-4
    assert len(out["free"]["swaps_by_expert_layer"]) == tiny["mlp_layer_types"].count("sparse")
    # other choices: every token's experts shifted by one
    lens, width = run["probe"]["prompt_lens"], run["probe"]["stripe"]
    _, kv, mine = tool.program_pass(params, cfg, rows, lens, width)
    shifted = [(m + 1) % tiny["num_experts"] for m in mine]
    _, moved_kv, followed = tool.program_pass(params, cfg, rows, lens, width, forced=shifted)
    for b, n in enumerate(lens):
        assert all(np.array_equal(f[b, :n], s[b, :n]) for f, s in zip(followed, shifted))
        assert np.allclose(moved_kv[b][0][:2], kv[b][0][:2], atol=1e-6)  # layers 0 and 1: before any expert's output
        assert not np.allclose(moved_kv[b][0][2], kv[b][0][2], atol=1e-4)
    counted = tool.swaps(shifted[0], mine[0], lens)
    assert counted["tokens_with_a_swap"] == 1.0 and 0 < counted["choices_swapped"] <= 1.0
