"""``benchmark/window_counts.py`` and the window-own readers (PR 40), on a
small trace of this PR's own engine recorded on a v5e with
``benchmark/tools/record_scoped_trace.py`` (``data/counts``: three requests
before the window, six inside it), on the older recorded trace that holds no
``engine.counts`` event, and on hand-made events."""

import copy
import json
import os
import shutil

import pytest

from benchmark import common, scopes, trace, window_counts

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
COUNTS = os.path.join(DATA, "counts")  # test_trace.py takes the first .xplane.pb of data/
MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
SERVE = "mistral7b-serve-saturated"
TWINS = [m["name"] for m in MANIFEST["per_layer"]
         if m["name"].endswith(".window") and m["name"][:-len(".window")] in
         {e["name"] for e in MANIFEST["per_layer"]}]
OWN = [m for m in MANIFEST["per_layer"] if m["name"].endswith(".window")
       or m["name"] in ("engine.loop_longest_pass_ms", "entry.engine_warm_programs_s")]
# the six prompts of the tool's window, a BOS each, six tokens an answer
WINDOW = {"requests_submitted": 6, "prompt_tokens": 20 + 100 + 40 + 150 + 30 + 70 + 6,
          "tokens_generated": 36, "first_tokens": 6}


def recorded_ctx(tmp_path, monkeypatch, xplane=os.path.join(COUNTS, "v5e-serve.xplane.pb"),
                 stats=os.path.join(COUNTS, "v5e-serve.stats.json"), cell=SERVE):
    """A reader's context over a recorded trace, laid out as a traced run
    leaves it under ``.bench_out/<cell>/trace``."""
    config = common.load_config(MANIFEST, common.find_cell(MANIFEST, cell)["config"])
    monkeypatch.setattr(common, "ROOT", str(tmp_path))
    trace_dir = tmp_path / ".bench_out" / cell / "trace"
    trace_dir.mkdir(parents=True)
    shutil.copy(xplane, trace_dir / "t.xplane.pb")
    with open(stats) as f:
        stats = json.load(f)
    scopes.read_xplane.cache_clear()
    window_counts.read_events.cache_clear()
    records = (stats.get("loop") or {}).get("longest_pass_by_second") or [{"t": 0.0}]
    return dict(
        cell={"name": cell}, config=config, device_kind="TPU v5 lite",
        trace=trace.reduce_dir(str(trace_dir)),
        samples=[{"active_slots": 3, "live_tokens": 300, "max_num_seqs": 4}],
        extra={"stats_at_end": stats, "window": [records[0]["t"], records[-1]["t"]]},
    )


def test_the_windows_events_sum_to_what_the_window_ran(tmp_path, monkeypatch):
    ctx = recorded_ctx(tmp_path, monkeypatch)
    own = window_counts.window_counts(ctx)
    total = scopes.engine_stats(ctx)["counters"]
    for name, value in WINDOW.items():
        assert own[name] == value, name
    assert own["requests_finished"] == {"stop": 0, "length": 6}
    assert own["prefill_chunks"] == {"mid": 0 + 1 + 0 + 2 + 0 + 1, "final": 6}
    assert set(own) == set(total)  # shaped as the cumulative view, families and all
    # the three requests the tool sends before the window are in the cumulative ones alone
    assert total["requests_submitted"] == 9 and total["tokens_generated"] == 54
    assert 0 < own["decode_steps"] < total["decode_steps"]
    assert own["decode_slot_steps"] == WINDOW["tokens_generated"] - WINDOW["first_tokens"] + own[
        "tokens_discarded"]


def test_events_outside_the_window_are_not_summed():
    events = ((0.5, {"decode_steps": 1}), (1.0, {"decode_steps": 2, "moe_layer_steps:decode": 8}),
              (2.0, {"decode_steps": 4}), (2.5, {"decode_steps": 8}))
    assert window_counts.sums(events, 1.0, 2.0) == {"decode_steps": 6, "moe_layer_steps:decode": 8}
    assert window_counts.sums(events, 3.0, 4.0) is None
    assert window_counts.sums((), 0.0, 9.0) is None


def test_view_shapes_families_and_fills_what_no_event_named():
    like = {"decode_steps": 90, "tokens_discarded": 4,
            "moe_layer_steps": {"decode": 70, "chunk_mid": 3, "chunk_final": 9}}
    got = window_counts.view({"decode_steps": 6, "moe_layer_steps:decode": 8}, like)
    assert got == {"decode_steps": 6, "tokens_discarded": 0,
                   "moe_layer_steps": {"decode": 8, "chunk_mid": 0, "chunk_final": 0}}
    assert window_counts.view({"requests_failed:decode": 1}) == {"requests_failed": {"decode": 1}}


def test_windowed_leaves_the_callers_context_untouched(tmp_path, monkeypatch):
    ctx = recorded_ctx(tmp_path, monkeypatch)
    before = copy.deepcopy(ctx)
    own = window_counts.windowed(ctx)
    assert ctx == before
    stats, was = own["extra"]["stats_at_end"], ctx["extra"]["stats_at_end"]
    assert stats["counters"] == window_counts.window_counts(ctx) != was["counters"]
    for key in ("max_num_seqs", "pools", "latency", "loop", "init"):
        assert stats[key] == was[key]
    assert own["extra"]["window"] == ctx["extra"]["window"] and own["trace"] is ctx["trace"]


@pytest.mark.parametrize("m", OWN, ids=lambda m: m["name"])
def test_a_trace_without_the_events_reads_none_and_does_not_raise(m, tmp_path, monkeypatch):
    """The parent of the PR that brought the events, under this PR's files."""
    old = os.path.join(DATA, "scoped")
    ctx = recorded_ctx(tmp_path, monkeypatch, os.path.join(old, "v5e-serve.xplane.pb"),
                       os.path.join(old, "v5e-serve.stats.json"))
    assert window_counts.window_counts(ctx) is None and window_counts.windowed(ctx) is None
    assert common.load_reader(m["name"])(ctx) is None
    assert common.load_reader(m["name"])(dict(ctx, cell={"name": "no-trace-here"}, extra={})) is None


def test_count_events_are_spans_of_no_length_that_no_span_reader_counts(tmp_path, monkeypatch):
    ctx = recorded_ctx(tmp_path, monkeypatch)
    parsed = scopes.trace_of(ctx)
    counts = [(a, b) for a, b, name in parsed["spans"] if name == window_counts.COUNTS_EVENT]
    assert counts and max(b - a for a, b in counts) < 1e-3
    without = dict(parsed, spans=[s for s in parsed["spans"] if s[2] != window_counts.COUNTS_EVENT])
    share = scopes.span_share(ctx, scopes.DEVICE_CALL_SPANS)
    assert scopes.uncovered_idle(parsed) == scopes.uncovered_idle(without)
    monkeypatch.setattr(scopes, "trace_of", lambda ctx: without)
    assert scopes.span_share(ctx, scopes.DEVICE_CALL_SPANS) == share > 0


@pytest.mark.parametrize("name", TWINS)
def test_a_window_own_metric_is_its_twins_formula_on_the_windowed_context(
        name, tmp_path, monkeypatch):
    ctx = recorded_ctx(tmp_path, monkeypatch)
    read = common.load_reader(name)
    asked = []

    def load_reader(metric):
        asked.append(metric)
        return lambda c: c["extra"]["stats_at_end"]["counters"]["decode_steps"]

    monkeypatch.setattr(common, "load_reader", load_reader)
    assert read(ctx) == window_counts.window_counts(ctx)["decode_steps"]
    assert asked == [name[:-len(".window")]]


@pytest.mark.parametrize("name, low, high", [
    ("engine.decode_live_rows.window", 1.0, 4.0),
    ("engine.useful_token_share.window", 20.0, 100.0),
    ("engine.prefill_rows_per_program.window", 1.0, 4.0),
    ("kernel.decode_read_efficiency.window", 1.0, 100.0),
    ("engine.loop_longest_pass_ms", 0.1, 60e3),
    ("entry.engine_warm_programs_s", 0.1, 600.0),
])
def test_reader_on_the_recorded_trace(name, low, high, tmp_path, monkeypatch):
    ctx = recorded_ctx(tmp_path, monkeypatch)
    value = common.load_reader(name)(ctx)
    assert isinstance(value, float) and low <= value <= high, (name, value)
    if name == "entry.engine_warm_programs_s":
        assert value <= common.load_reader("entry.engine_init_s")(ctx)
    if name == "engine.decode_live_rows.window":
        own = window_counts.window_counts(ctx)
        assert value == own["decode_slot_steps"] / own["decode_steps"]
    if name == "engine.useful_token_share.window":
        # the twin on the cumulative counters holds the three requests sent one at a time
        assert value > common.load_reader("engine.useful_token_share")(ctx)


def test_every_new_entry_has_its_reader_file_and_lists_serving_cells():
    serving = {c["name"] for c in MANIFEST["workloads"] if c["name"] != "mistral7b-train-1chip"}
    assert len(OWN) >= 10
    for m in OWN:
        assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", m["name"] + ".py"))
        assert m["source"] == "program_span" and set(m["workloads"]) <= serving
    for name in TWINS:
        by = {m["name"]: m for m in MANIFEST["per_layer"]}
        twin, own = by[name[:-len(".window")]], by[name]
        assert (own["layer"], own["better"], own["moves"], own["unit"], own["workloads"]) == (
            twin["layer"], twin["better"], twin["moves"], twin["unit"], twin["workloads"])
