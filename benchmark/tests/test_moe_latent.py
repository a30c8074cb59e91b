"""Family ``moe_latent`` (PR 33): its configuration file against its own
``published`` block and the catalog row, its traffic kind's generator, its
weights and int8 control, the counts of what a step needs, the reference's
pieces, and every reader of the new per-layer metrics on a hand-made trace and
the engine's counters."""

import json
import os

import numpy as np
import pytest

from benchmark import common, scopes, traffic
from benchmark.families import moe_latent as family
from benchmark.kinds import docs_shared
from benchmark.tests.test_manifest import check_config_file

MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
CELL = "kanana2-serve-docs-shared"
CONFIG = common.load_config(MANIFEST, "kanana-2-30b-a3b-serve-l5")
MIX = common.load_traffic("docs-shared-closed-48")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = [
    "kernel.decode_latent_attention_hbm_share", "kernel.prefill_latent_attention_roofline_share",
    "kernel.moe_decode_hbm_share.moe_latent", "program.decode_hbm_share.moe_latent",
    "program.prefill_final_chunk_ms", "kernel.prefix_seed_ms", "engine.prefix_hit_token_share",
]
SHARED_WITH_BOTH_SERVING_CELLS = [
    "entry.replica_start_s", "entry.engine_init_s", "engine.batch_occupancy",
    "engine.queue_wait_p50_ms", "engine.prefill_p50_ms", "engine.token_gap_ms",
    "engine.useful_token_share", "engine.device_wait_share", "program.decode_step_ms",
    "program.scope_coverage.serve", "kernel.decode_kv_write_ms", "kernel.decode_sampling_ms",
]


# ------------------------------------------------------------- the data files


def test_configuration_file_passes_the_manifest_check():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "kanana-2-30b-a3b-serve-l5")
    assert entry["reduced"] == ["num_hidden_layers"] == CONFIG["reduced"]
    check_config_file(CONFIG, ["num_hidden_layers"])
    assert CONFIG["num_hidden_layers"] == 5 and CONFIG["published"]["num_hidden_layers"] == 48
    changed = {k for k, v in CONFIG["published"].items() if CONFIG[k] != v}
    assert changed == {"num_hidden_layers"}
    assert set(CONFIG["assumed"]) >= {"selection_bias", "router", "shared_experts", "wkv_b", "rope",
                                      "cache_row", "initialisation", "tokenizer", "engine"}
    # the prompt's latents hold the precision and the head a gross fault; the 128 decode
    # positions follow single swapped experts and are printed, not limited (limits_from)
    assert set(CONFIG["run"]["limits"]) == {"kv_prefill_rel_rms", "logits_rel_rms"}
    engine, probe = CONFIG["run"]["engine"], CONFIG["run"]["probe"]
    assert (engine["max_num_seqs"], engine["max_seq_len"]) == (24, 24576)
    assert engine["prefill_buckets"] == [32, 64, 128, 256, 12288, 20480]
    assert probe["decode_steps"] == 64 and min(probe["prompt_lens"]) <= 256 < 2048 < max(probe["prompt_lens"])
    assert (probe["stripe"] + probe["decode_steps"]) % 512 == 0  # whole key blocks and kernel blocks


def test_published_block_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert CONFIG["published"] == row["config"]
    assert CONFIG["source"] == row["source_url"]


def test_rehearsal_fixture_passes_the_check_and_cannot_pass_for_the_benchmark():
    rehearsal = common.load_manifest(os.path.join(common.BENCH_DIR, "rehearsal-moe-latent.json"))
    assert rehearsal["rehearsal"] is True
    assert not {c["name"] for c in rehearsal["workloads"]} & {c["name"] for c in MANIFEST["workloads"]}
    for entry in rehearsal["configs"]:
        check_config_file(common.load_json(os.path.join(common.ROOT, entry["file"])), [])
    (cell,) = rehearsal["workloads"]
    assert common.load_traffic(cell["traffic"])["kind"] == MIX["kind"] == "docs_shared"


def test_cell_and_metric_entries():
    cell = common.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana-2-30b-a3b-serve-l5", "docs-shared-closed-48", 1)
    assert len(MANIFEST["workloads"]) == 4 and MANIFEST["workloads"][-1] is cell
    reported = {m["name"] for m in common.metrics_for(MANIFEST, "per_layer", CELL)}
    assert reported == set(NEW_READERS) | set(SHARED_WITH_BOTH_SERVING_CELLS)
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW_READERS:
            assert (m["workloads"], m["moves"]) == ([CELL], "serve_tok_s")
    assert [m["name"] for m in common.metrics_for(MANIFEST, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]


# ------------------------------------------------------------- the traffic kind


def test_traffic_mix_is_the_one_the_issue_names():
    assert (MIX["clients"], MIX["stream"], MIX["pool"], MIX["temperature"], MIX["ignore_eos"],
            MIX["ramp_seconds"]) == (48, False, 96, 0.0, True, 5)
    assert MIX["documents"] == [{"tokens": 12288, "count": 6}, {"tokens": 20480, "count": 6}]
    assert MIX["tail_tokens"] == {"dist": "lognormal", "median": 96, "sigma": 0.6, "min": 32, "max": 256}
    assert MIX["max_tokens"] == {"dist": "lognormal", "median": 64, "sigma": 0.5, "min": 32, "max": 128}
    assert MIX["min_window_prefix_share"] == 0.98
    for key in ("warmup_prompt_tokens", "warmup_max_tokens", "request_timeout_s", "sample_every_s",
                "trace_seconds", "why", "warmup_why", "pool_why"):  # what the harness reads of any mix
        assert key in MIX
    engine = CONFIG["run"]["engine"]
    lengths = {d["tokens"] for d in MIX["documents"]}
    assert lengths <= set(engine["prefill_buckets"])  # a document is a prefix-cache key
    assert max(lengths) + MIX["tail_tokens"]["max"] + MIX["max_tokens"]["max"] + 1 <= engine["max_seq_len"]
    # the warm-up reaches every final-chunk width a tail can fall into
    widths = {next(b for b in engine["prefill_buckets"] if t <= b) for t in MIX["warmup_tail_tokens"]}
    tails = traffic.stratified(MIX["tail_tokens"], MIX["pool"])
    assert widths == {next(b for b in engine["prefill_buckets"] if t <= b) for t in tails} == {32, 64, 128, 256}
    # the store holds every document at every bucket it covers, and is never full
    held = sum(b for d in MIX["documents"] for b in engine["prefill_buckets"]
               if b < d["tokens"] + 1 for _ in range(d["count"]))
    assert sum(b for d in MIX["documents"] for b in lengths if b <= d["tokens"] for _ in range(d["count"])) == 270336
    row = 2 * (128 + CONFIG["kv_lora_rank"]) * CONFIG["num_hidden_layers"]  # bytes a token held
    assert held * row < engine["prefix_cache_max_bytes"] and 6 * 12 + 64 < engine["prefix_cache_entries"]


@pytest.mark.parametrize("seed", [3, 2147483000, 2**31 + 12345])
def test_every_seed_asks_the_same_amount_of_work_about_its_own_documents(seed):
    reqs = docs_shared.Requests(MIX, seed)
    other = docs_shared.Requests(MIX, seed + 1)
    assert sorted(reqs.document_tokens) == [12288] * 6 + [20480] * 6
    assert sorted(t for t, _ in reqs.sizes) == sorted(t for t, _ in other.sizes)  # the same sizes,
    assert sorted(a for _, a in reqs.sizes) == sorted(a for _, a in other.sizes)  # in another order
    assert reqs.sizes != other.sizes and reqs.documents[0] != other.documents[0]
    tails, answers = zip(*reqs.sizes)
    assert (min(tails), max(tails), min(answers), max(answers)) == (32, 256, 32, 128)
    assert 100 < sum(tails) / 96 < 125 and 65 < sum(answers) / 96 < 80
    seen = set()
    for i in range(2 * 96):
        r = reqs[i]
        doc = reqs.documents[r["document"]]
        n = reqs.document_tokens[r["document"]]
        # BOS and n - 1 bytes: the first n tokens of the prompt are the document's, whole
        assert r["document"] == i % 12 and len(doc) == n - 1 and r["prompt"].startswith(doc)
        tail = r["prompt"][len(doc):]
        assert (len(tail), r["max_tokens"]) == reqs.sizes[i % 96] and r["prompt_tokens"] == n + len(tail)
        assert tail not in seen  # no two requests share a tail
        seen.add(tail)
    assert reqs[5]["prompt"] == docs_shared.Requests(MIX, seed)[5]["prompt"]  # the seed is the draw
    assert len(set(reqs.documents)) == 12


def test_window_prefix_share_subtracts_two_readings():
    opened = {"prompt_tokens": 1000, "prompt_tokens_from_prefix": 100}
    closed = {"prompt_tokens": 1000 + 16500, "prompt_tokens_from_prefix": 100 + 16384}
    got = docs_shared.prefix_share(opened, closed)
    assert got == {"prompt_tokens": 16500, "from_prefix": 16384, "share": 16384 / 16500}
    assert docs_shared.prefix_share(opened, opened)["share"] == 0.0
    body = docs_shared.body_of("m", {"prompt": "p", "max_tokens": 7}, MIX)
    assert body["ignore_eos"] is True and body["max_tokens"] == 7 and body["temperature"] == 0.0


# ------------------------------------------------------- weights and counts


def test_parameter_shapes_count_the_cut_and_its_bytes():
    shapes = family.param_shapes(CONFIG)
    assert family.param_count(CONFIG) == 3_149_554_688  # the issue's 3,149.5 M: 6.30 GB in bfloat16
    assert family.attention_params(CONFIG) == 26_345_472  # Wq 12.58, Wkv_a 1.18, Wkv_b 4.19, Wo 8.39 M
    assert shapes["wq_latent"][0] == (5, 2048, 32, 192) and shapes["wkv_a_latent"][0] == (5, 2048, 576)
    assert shapes["wuk_latent"] == ((5, 32, 128, 512), 512) and shapes["wuv_latent"][0] == (5, 32, 512, 128)
    assert shapes["wo_latent"] == ((5, 32, 128, 2048), 4096) and shapes["w_gate"][0] == (1, 2048, 6144)
    assert shapes["moe_w_down"] == ((4, 128, 768, 2048), 768) and shapes["moe_router_bias"][0] == (4, 128)
    assert shapes["moe_shared_up"][0] == (4, 2048, 1536) and "wk" not in shapes
    assert 128 * family.expert_params(CONFIG) == 603_979_776  # an expert layer's routed bank
    assert family.moe_fixed_params(CONFIG) == 2048 * 128 + 128 + 9_437_184
    assert abs(family.param_count(CONFIG["published"]) / 30.67e9 - 1) < 1e-3


def test_needed_bytes_and_operations():
    c = CONFIG
    assert family.layer_rows(c) == {"all": 5, "dense": 1, "sparse": 4}
    assert family.latent_bytes_per_token_layer(c) == 1152
    every = family.moe_needed_bytes(c, 4, 4 * 128)
    assert abs(every / 4.91e9 - 1) < 5e-3  # four whole banks, routers and shared experts
    assert family.moe_needed_bytes(c, 4, 4 * 87) < every
    # all experts touched: every weight but the embedding table
    assert abs(family.decode_weight_bytes(c, 128) / (6.30e9 - 0.525e9) - 1) < 5e-3
    # one query over 16,384 cached positions: absorbed is cheaper; 256 queries: expanded
    one = family.attention_flops(c, 1, 16384, 16384)
    assert one == 2 * 32 * (128 * 512 + 16384 * (2 * 512 + 64))
    pairs = 256 * 16384 + 256 * 257 // 2
    chunk = family.attention_flops(c, 256, pairs, 16384 + 256)
    assert chunk == 2 * 32 * ((16384 + 256) * 512 * 256 + pairs * 320)
    assert chunk < 2 * 32 * (256 * 128 * 512 + pairs * (2 * 512 + 64))


def test_weights_from_a_seed_and_the_int8_control():
    import jax
    import jax.numpy as jnp

    tiny = common.load_json(os.path.join(common.BENCH_DIR, "configs", "rehearse-moe-latent-serve.json"))
    a = family.make_params(11, tiny, jnp.float32)
    b = family.make_params(11, tiny, jnp.float32)
    c = family.make_params(12, tiny, jnp.float32)
    assert {k: v.shape for k, v in a.items()} == {k: s for k, (s, _) in family.param_shapes(tiny).items()}
    assert all(np.array_equal(a[k], b[k]) for k in a) and not np.array_equal(a["wq_latent"], c["wq_latent"])
    assert abs(float(jnp.std(a["moe_w_down"])) / 32 ** -0.5 - 1) < 0.05
    assert abs(float(jnp.std(a["wuk_latent"])) / 32 ** -0.5 - 1) < 0.05
    assert abs(float(jnp.std(a["moe_router_bias"])) / family.BIAS_STD - 1) < 0.25  # 32 numbers
    assert np.array_equal(a["kv_norm_latent"], np.ones((3, 32), np.float32))
    bank = np.asarray(a["moe_w_gate"])
    cut = family.int8_roundtrip(jax.tree.map(jnp.copy, a))
    for left_alone in ("attn_norm", "kv_norm_latent", "moe_router_bias"):
        assert np.array_equal(cut[left_alone], a[left_alone])
    err = np.abs(np.asarray(cut["moe_w_gate"]) - bank)
    step = np.abs(bank).max(axis=2, keepdims=True) / 127.0
    assert (err <= 0.5 * step + 1e-7).all() and err.max() > 0
    assert not np.array_equal(cut["wuk_latent"], a["wuk_latent"])


# ---------------------------------------------------------- the reference


def test_reference_attention_router_and_rope_pieces():
    import jax.numpy as jnp

    from benchmark import reference_moe_latent as ref

    rng = np.random.default_rng(0)
    e, heads, rank, nope, rope, vd, t = 32, 4, 16, 8, 4, 8, 12
    w = {"attn_norm": np.ones(e, np.float32),
         "wq": 0.1 * rng.normal(size=(e, heads, nope + rope)).astype(np.float32),
         "wkv_a": 0.2 * rng.normal(size=(e, rank + rope)).astype(np.float32),
         "kv_norm": np.ones(rank, np.float32),
         "wuk": rng.normal(size=(heads, nope, rank)).astype(np.float32),
         "wuv": rng.normal(size=(heads, rank, vd)).astype(np.float32),
         "wo": rng.normal(size=(heads, vd, e)).astype(np.float32)}
    x = rng.normal(size=(1, t, e)).astype(np.float32)
    pos = np.arange(t, dtype=np.int32)[None]
    kw = dict(rank=rank, nope=nope, theta=1e4, eps=1e-6)
    base, k_pe, c = ref.attention_part(jnp.asarray(x), w, pos, **kw)
    assert k_pe.shape == (1, t, 1, rope) and c.shape == (1, t, 1, rank)
    assert np.allclose(np.mean(np.asarray(c) ** 2, -1), 1.0, atol=1e-4)  # the latent is normed
    moved = x.copy()
    moved[0, 7] += 1.0  # causal: positions before 7 do not see it, those from 7 do
    got, _, _ = ref.attention_part(jnp.asarray(moved), w, pos, **kw)
    assert np.allclose(got[0, :7], base[0, :7], atol=1e-5) and not np.allclose(got[0, 8], base[0, 8])
    # blocks of queries give what one block gives
    whole = ref.QUERY_BLOCK
    try:
        ref.QUERY_BLOCK = 5
        blocked, _, _ = ref.attention_part(jnp.asarray(x), w, pos, **kw)
    finally:
        ref.QUERY_BLOCK = whole
    assert np.allclose(blocked, base, atol=1e-5)
    # rotation of neighbours: position 0 stays, a pair keeps its length, the angle is t / theta^(2j/D)
    y = rng.normal(size=(1, 3, 1, 4)).astype(np.float32)
    r = np.asarray(ref.rope_pairs(jnp.asarray(y), np.asarray([[0, 1, 5]]), 1e4))
    assert np.allclose(r[0, 0], y[0, 0])
    assert np.allclose(np.hypot(r[..., 0::2], r[..., 1::2]), np.hypot(y[..., 0::2], y[..., 1::2]), atol=1e-5)
    ang = 5 / 1e4 ** (2 / 4)
    assert np.isclose(r[0, 2, 0, 2], y[0, 2, 0, 2] * np.cos(ang) - y[0, 2, 0, 3] * np.sin(ang), atol=1e-6)
    # routing: k nonzero weights a token that sum to one; the bias moves choices, never a weight
    router = {"mlp_norm": np.ones(e, np.float32),
              "moe_router": rng.normal(size=(e, 16)).astype(np.float32) / 4,
              "moe_router_bias": np.zeros(16, np.float32)}
    h, weights, idx = ref.route(jnp.asarray(x), router, top_k=3, eps=1e-6)
    assert np.allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    assert ((np.asarray(weights) > 0).sum(-1) == 3).all() and idx.shape == (1, t, 3)
    pushed = dict(router, moe_router_bias=np.where(np.arange(16) == 5, 10.0, 0.0).astype(np.float32))
    _, w5, idx5 = ref.route(jnp.asarray(x), pushed, top_k=3, eps=1e-6)
    assert (np.asarray(idx5) == 5).any(-1).all()  # every token now chooses expert 5 ...
    scores = 1 / (1 + np.exp(-(np.asarray(h) @ router["moe_router"])))
    chosen = np.take_along_axis(scores, np.asarray(idx5), -1)
    assert np.allclose(np.take_along_axis(np.asarray(w5), np.asarray(idx5), -1),
                       chosen / chosen.sum(-1, keepdims=True), atol=1e-6)  # ... at its own score


# ------------------------------------------------------------- the readers


def synthetic():
    """Two decode steps, one final chunk and one prefix seeding inside a 1 s
    window, milliseconds in round numbers; and the counters of an engine that
    ran 10 decode steps over 300,000 live tokens each and 4 final chunks of
    100 tokens behind 16,384 cached ones."""
    d, f = "jit(decode_fn)/", "jit(chunk_final)/"
    ops = []

    def add(start, ms, op_name):
        ops.append((start, start + ms * 1e-3, "fusion", op_name))
        return start + ms * 1e-3

    for step_start in (0.0, 0.1):
        t = step_start
        t = add(t, 4.0, d + "while/body/attn_core/latent/latent_decode_attention")
        t = add(t, 1.0, d + "attn_core/latent/bthn,hnr->bthr/dot_general")
        t = add(t, 5.0, d + "while/body/moe_ffn/experts/gmm")
        t = add(t, 0.5, d + "while/body/moe_ffn/router/dot_general")
        t = add(t, 0.5, d + "while/body/moe_ffn/shared_expert/dot_general")
        t = add(t, 1.0, d + "attn_qkv/dot_general")
    t = add(0.2, 4.0, f + "while/body/attn_core/latent/while/body/dot_general")
    t = add(t, 6.0, f + "while/body/moe_ffn/experts/gmm")
    add(0.3, 0.5, "jit(seed_prefix)/prefix_seed/dynamic_update_slice")
    parsed = {
        "window": (0.0, 1.0), "spans": [],
        "modules": [(0.0, 0.013, "jit_decode_fn"), (0.1, 0.113, "jit_decode_fn"),
                    (0.2, 0.212, "jit_chunk_final"), (0.3, 0.3005, "jit_seed_prefix")],
        "ops": sorted(ops),
    }
    n, seen = 100, 16384
    counters = {
        "decode_steps": 10, "decode_kv_tokens_latent": 10 * 300_000,
        "prompt_tokens": 4 * (seen + n) + 20480 + 50, "prompt_tokens_from_prefix": 4 * seen,
        "prefill_chunks": {"mid": 80, "final": 4},
        "prefill_query_tokens": {"chunk_mid": 20480, "chunk_final": 4 * n},
        "prefill_attended_positions": {"chunk_mid": 1, "chunk_final": 4 * (n * seen + n * (n + 1) // 2)},
        "moe_layer_steps": {"decode": 40, "chunk_mid": 320, "chunk_final": 16},
        "moe_assignments": {"decode": 40 * 144, "chunk_mid": 1, "chunk_final": 1},
        "moe_experts_touched": {"decode": 40 * 87, "chunk_mid": 1, "chunk_final": 1},
        "moe_max_expert_load_sum": {"decode": 40 * 5, "chunk_mid": 1, "chunk_final": 1},
    }
    ctx = {
        "cell": {"name": CELL}, "config": CONFIG, "device_kind": "TPU v5 lite",
        "trace": {"busy_s": 0.0385, "window_s": 1.0, "modules": {
            "jit_decode_fn": {"count": 2, "total_s": 0.026},
            "jit_chunk_final": {"count": 1, "total_s": 0.012},
            "jit_seed_prefix": {"count": 1, "total_s": 0.0005}}},
        "extra": {"stats_at_end": {"counters": counters, "max_num_seqs": 24}}, "samples": [],
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
    # 300,000 live tokens x 5 layers x 1,152 bytes in the 5 ms under attn_core/latent
    latent = 300_000 * 5 * 1152
    assert read["kernel.decode_latent_attention_hbm_share"] == pytest.approx(100 * latent / bw / 5e-3)
    # 4 expert layers of router, bias and shared experts, 87 touched experts each, in 6 ms
    need = 2 * (4 * family.moe_fixed_params(c) + 4 * 87 * family.expert_params(c))
    assert read["kernel.moe_decode_hbm_share.moe_latent"] == pytest.approx(100 * need / bw / 6e-3)
    whole = family.decode_weight_bytes(c, 87) + latent
    assert read["program.decode_hbm_share.moe_latent"] == pytest.approx(100 * whole / bw / 13e-3)
    # a final chunk of 100 tokens behind 16,384: operations bound, absorbed the cheaper form
    pairs, seen = 100 * 16384 + 100 * 101 // 2, pairs_seen(100, 16384)
    by_ops = 5 * family.attention_flops(c, 100, pairs, seen) / flops
    assert by_ops > 5 * seen * 1152 / bw
    assert family.attention_flops(c, 100, pairs, seen) == 2 * 32 * (100 * 128 * 512 + pairs * 1088)
    assert read["kernel.prefill_latent_attention_roofline_share"] == pytest.approx(100 * by_ops / 4e-3)
    assert read["program.prefill_final_chunk_ms"] == pytest.approx(12.0)
    assert read["kernel.prefix_seed_ms"] == pytest.approx(0.5)
    assert read["engine.prefix_hit_token_share"] == pytest.approx(
        100 * 4 * 16384 / (4 * 16484 + 20530))
    assert all(0 < read[n] <= 100 for n in NEW_READERS if not n.endswith("_ms"))


def pairs_seen(n, behind):
    """Cached positions the last of ``n`` queries behind ``behind`` sees, as
    the reader reckons it from the two counters."""
    return (n * behind + n * (n + 1) // 2) / n + (n - 1) / 2


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_scopes_and_counters(name, monkeypatch):
    """The parent commit on another model's trace: no ``latent`` scope, no
    latent, prefill-attention or routing counter, no ``jit_chunk_final`` or
    ``jit_seed_prefix`` in the window. The result line then leaves the metric
    out; nothing raises."""
    parsed, ctx = synthetic()
    ctx["extra"]["stats_at_end"]["counters"] = {"decode_steps": 10}
    flat = [(a, b, n, op.replace("/latent", "").replace("moe_ffn/experts", "ffn")
             .replace("moe_ffn/router", "ffn").replace("moe_ffn/shared_expert", "ffn"))
            for a, b, n, op in parsed["ops"]]
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: dict(parsed, ops=flat))
    ctx["trace"]["modules"] = {"jit_decode_fn": ctx["trace"]["modules"]["jit_decode_fn"]}
    assert common.load_reader(name)(ctx) is None
    # and with no trace and no stats at all
    monkeypatch.setattr(scopes, "trace_of", lambda _ctx: None)
    ctx["trace"]["modules"] = {}
    ctx["extra"] = {}
    assert common.load_reader(name)(ctx) is None
