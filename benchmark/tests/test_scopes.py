"""The readers of scopes, engine spans and engine counters: the attribution
rules on hand-made inputs, and every new reader on two small traces recorded
on a v5e with ``benchmark/tools/record_scoped_trace.py`` (the program's own
train step and engine at small widths) and the engine's recorded
``get_stats()``."""

import json
import os
import shutil

import pytest

from benchmark import common, scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "scoped")  # test_trace.py takes the first .xplane.pb of data/
MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
SERVE, TRAIN = "mistral7b-serve-saturated", "mistral7b-train-1chip"


def uses_scopes(metric: dict) -> bool:
    """The readers PR 26 added are those that share ``benchmark/scopes.py``."""
    with open(os.path.join(common.BENCH_DIR, "metrics", metric["name"] + ".py")) as f:
        return "scopes." in f.read()


NEW = [m for m in MANIFEST["per_layer"] if uses_scopes(m)]


@pytest.mark.parametrize("op_name, scope, direction", [
    ("jit(step_fn)/jvp()/while/body/closed_call/ffn/bte,ef->btf/dot_general:", "ffn", "forward"),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/attn_qkv/mul", "attn_qkv",
     "backward"),
    ("jit(step_fn)/jvp()/while/body/closed_call/checkpoint/attn_core/exp", "attn_core", "backward"),
    ("jit(step_fn)/transpose(jvp(loss))/while/body/add", "loss", "backward"),
    ("jit(step_fn)/jvp(embed)/gather", "embed", "forward"),
    ("jit(step_fn)/optimizer/mul", "optimizer", "other"),
    ("jit(step_fn)/jvp()/while/body/dynamic_update_slice", None, "forward"),
    ("jit(decode_fn)/jit(main)/while/body/attn_core/reduce_max", "attn_core", "other"),
    ("jit(decode_fn)/sampling/vmap(jit(_where))/select_n", "sampling", "other"),
    ("jit(loss)/add", None, "other"),  # a jitted function of that name is no scope
    ("jit(decode_fn)/norm_like/add", None, "other"),
    ("", None, "other"),
], ids=lambda v: str(v)[-40:])
def test_scope_and_direction_of_an_op_name(op_name, scope, direction):
    assert scopes.scope_of(op_name) == scope
    assert scopes.direction_of(op_name) == direction


def parsed():
    """Two executions of a step inside the window, one cut by its end."""
    f, b = "jit(step_fn)/jvp()/ffn/mul", "jit(step_fn)/transpose(jvp())/ffn/mul"
    return {
        "window": (0.0, 10.0),
        "modules": [(0.0, 4.0, "jit_step_fn"), (5.0, 9.0, "jit_step_fn"), (9.5, 12.0, "jit_step_fn"),
                    (4.2, 4.4, "jit_other")],
        "ops": sorted([
            (0.0, 1.0, "fusion.1", f), (1.0, 3.0, "fusion.2", b),
            (3.0, 3.5, "fusion.3", "jit(step_fn)/optimizer/add"), (3.5, 4.0, "copy.1", ""),
            (4.2, 4.4, "fusion.9", "jit(other)/norm/mul"),
            (5.0, 6.0, "fusion.1", f), (6.0, 8.0, "fusion.2", b),
            (8.0, 8.5, "fusion.3", "jit(step_fn)/optimizer/add"), (8.5, 9.0, "copy.1", ""),
            (9.5, 11.0, "fusion.1", f),
        ]),
        "spans": [(4.0, 4.25, "engine.drain"), (9.0, 9.2, "engine.fetch"),
                  (9.2, 9.6, "engine.idle_sleep")],
    }


def test_module_ops_counts_whole_executions_and_their_operations():
    n, ops = scopes.module_ops(parsed(), "jit_step_fn")
    assert n == 2 and len(ops) == 8
    assert scopes.scope_seconds(ops) == {"ffn": 6.0, "optimizer": 1.0, None: 1.0}
    assert scopes.scope_seconds(ops, ("backward",)) == {"ffn": 4.0}
    n, ops = scopes.module_ops(parsed(), "jit_other")
    assert n == 1 and [o[2] for o in ops] == ["fusion.9"]
    assert scopes.module_ops(parsed(), "jit_absent") == (0, [])


def test_uncovered_idle_gaps():
    p = parsed()
    # idle: [4.0, 4.2] under engine.drain, [4.4, 5.0] under nothing, [9.0, 9.5] under two
    # spans; the spans end at 9.6, so the window's last 0.4 s are not judged
    assert scopes.uncovered_idle(p, longer_than_s=0.1) == [[4.4, 5.0]]
    p["spans"].append((4.3, 5.1, "engine.pull_waiting"))
    assert scopes.uncovered_idle(p, longer_than_s=0.1) == []
    assert scopes.uncovered_idle(parsed(), longer_than_s=0.7) == []
    assert scopes.uncovered_idle(dict(parsed(), spans=[])) == []


def test_device_wait_share_is_fetches_and_launches_together(monkeypatch):
    p = parsed()
    p["spans"] += [(1.0, 3.0, "engine.prefill_chunk"), (2.5, 3.5, "engine.decode_launch"),
                   (9.9, 10.5, "engine.fetch"), (6.0, 6.1, "engine.prefix_seed")]
    monkeypatch.setattr(scopes, "trace_of", lambda ctx: p)
    # [1.0, 3.5], [6.0, 6.1], [9.0, 9.2] and [9.9, 10.0] of a window of 10 s
    assert scopes.span_share({}, scopes.DEVICE_CALL_SPANS) == pytest.approx(29.0)
    assert scopes.span_share({}, ("engine.fetch",)) == pytest.approx(3.0)
    assert scopes.span_share({}, ("engine.absent",)) is None
    assert common.load_reader("engine.device_wait_share")({}) == pytest.approx(29.0)


def stats_with(counts, sum_s=0.0):
    bounds = list(scopes_bounds())
    full = [0] * (len(bounds) + 1)
    for i, c in counts.items():
        full[i] = c
    return {"extra": {"stats_at_end": {"latency": {
        "boundaries": bounds, "queue_wait_s": {"counts": full, "sum": sum_s}}}}}


def scopes_bounds():
    return [1e-3 * 1.25**i for i in range(56)]


def test_latency_quantile_from_bucket_counts():
    bounds = scopes_bounds()
    # all in the bucket (bounds[9], bounds[10]]: the median is its geometric middle
    ctx = stats_with({10: 8}, sum_s=8 * 0.009)
    want = 1e3 * (bounds[9] * bounds[10]) ** 0.5
    assert scopes.latency_quantile_ms(ctx, "queue_wait_s", 0.5) == pytest.approx(want)
    # three of four below: the median falls in the lower bucket
    ctx = stats_with({5: 3, 30: 1})
    assert bounds[4] * 1e3 < scopes.latency_quantile_ms(ctx, "queue_wait_s", 0.5) <= bounds[5] * 1e3
    assert scopes.latency_quantile_ms(stats_with({}), "queue_wait_s", 0.5) is None
    assert scopes.latency_quantile_ms({"extra": {}}, "queue_wait_s", 0.5) is None
    assert scopes.latency_quantile_ms({"extra": {"stats_at_end": {}}}, "queue_wait_s", 0.5) is None


# ------------------------------------------------- the recorded v5e traces


def recorded_ctx(tmp_path, monkeypatch, cell):
    """A reader's context over the recorded trace of ``cell``'s kind, laid
    out as a traced run leaves it under ``.bench_out/<cell>/trace``."""
    from benchmark import trace

    kind = "serve" if cell == SERVE else "train"
    config = common.load_config(MANIFEST, common.find_cell(MANIFEST, cell)["config"])
    monkeypatch.setattr(common, "ROOT", str(tmp_path))
    trace_dir = tmp_path / ".bench_out" / cell / "trace"
    trace_dir.mkdir(parents=True)
    shutil.copy(os.path.join(SCOPED, f"v5e-{kind}.xplane.pb"), trace_dir / "t.xplane.pb")
    with open(os.path.join(SCOPED, "v5e-serve.stats.json")) as f:
        stats = json.load(f)
    scopes.read_xplane.cache_clear()
    return dict(
        cell={"name": cell}, config=config, device_kind="TPU v5 lite", trace=trace.reduce_dir(str(trace_dir)),
        samples=[{"active_slots": 3, "live_tokens": 300, "max_num_seqs": 4}],
        extra={"stats_at_end": stats} if kind == "serve" else {},
    )


@pytest.mark.parametrize("m", NEW, ids=lambda m: m["name"])
def test_reader_on_the_recorded_trace(m, tmp_path, monkeypatch):
    (cell,) = m["workloads"]
    value = common.load_reader(m["name"])(recorded_ctx(tmp_path, monkeypatch, cell))
    assert isinstance(value, float) and value > 0, m["name"]
    if m["unit"] == "%" and not m["name"].endswith("hbm_share"):
        assert value <= 100.0  # the hbm shares divide Mistral's bytes by a tiny model's time


@pytest.mark.parametrize("m", NEW, ids=lambda m: m["name"])
def test_reader_finds_nothing_in_a_program_without_scopes_spans_or_counters(
        m, tmp_path, monkeypatch):
    """The parent of the PR that brought the names: its trace has none, its
    stats have no counters. The reader returns None and does not raise."""
    from benchmark import trace

    (cell,) = m["workloads"]
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


def test_recorded_train_step_splits_into_forward_backward_and_optimizer(tmp_path, monkeypatch):
    ctx = recorded_ctx(tmp_path, monkeypatch, TRAIN)
    step_ms = 1e3 * ctx["trace"]["modules"]["jit_step_fn"]["total_s"] / ctx["trace"]["modules"][
        "jit_step_fn"]["count"]
    parts = [scopes.direction_ms(ctx, "jit_step_fn", d) for d in ("forward", "backward", "optimizer")]
    assert all(p > 0 for p in parts) and parts[1] > parts[0]
    assert sum(parts) <= step_ms
    # at these widths parameter copies outside any pass are an eighth of the
    # 0.4 ms step; at the cell's size the three add up to the step within 3%
    assert sum(parts) >= 0.75 * step_ms
    by_scope = scopes.scope_seconds(scopes.module_ops(scopes.trace_of(ctx), "jit_step_fn")[1])
    # no ``grad_norm``: the compiler computes the clip's global norm and the
    # reported one once, and the one operation keeps the clip's name
    assert {"embed", "norm", "attn_qkv", "attn_core", "attn_out", "ffn", "loss",
            "optimizer"} <= set(by_scope)


def test_recorded_decode_step_scopes_sum_to_no_more_than_the_step(tmp_path, monkeypatch):
    ctx = recorded_ctx(tmp_path, monkeypatch, SERVE)
    parsed_trace = scopes.trace_of(ctx)
    n, ops = scopes.module_ops(parsed_trace, "jit_decode_fn")
    by_scope = scopes.scope_seconds(ops)
    step_s = ctx["trace"]["modules"]["jit_decode_fn"]["total_s"] / n
    assert n == ctx["trace"]["modules"]["jit_decode_fn"]["count"]
    assert sum(by_scope.values()) / n <= step_s * 1.0001
    assert {"embed", "norm", "attn_qkv", "kv_write", "attn_core", "attn_out", "ffn", "lm_head",
            "sampling"} <= set(by_scope)
    names = {name for _, _, name in parsed_trace["spans"]}
    assert {"engine.pull_waiting", "engine.advance_admissions", "engine.prefill_chunk",
            "engine.launch_decodes", "engine.decode_launch", "engine.drain", "engine.fetch"} <= names
    # every idle gap of the device over 1 ms lies under a span of the engine's loop
    assert scopes.uncovered_idle(parsed_trace) == []
