"""``engine.decode_in_chunk_share.window`` (PR 41): read from a small trace of
an engine whose chunk launches carry the pool's decode step, recorded on a v5e
with ``benchmark/tools/record_scoped_trace.py`` (``data/carried``: six prompts
at once on four slots, so chunks run beside rows that decode), None from the
trace PR 40 recorded (``data/counts``: it has the events and no such counter)
and from the one before it (``data/scoped``: no events at all)."""

import json
import os
import shutil

import pytest

from benchmark import common, scopes, trace, window_counts

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
METRIC = "engine.decode_in_chunk_share.window"
SERVING = [c["name"] for c in MANIFEST["workloads"] if c["name"] != "mistral7b-train-1chip"]


def recorded_ctx(tmp_path, monkeypatch, recording, cell):
    """A reader's context over ``data/<recording>``, laid out as a traced run
    of ``cell`` leaves it under ``.bench_out/<cell>/trace``."""
    config = common.load_config(MANIFEST, common.find_cell(MANIFEST, cell)["config"])
    monkeypatch.setattr(common, "ROOT", str(tmp_path))
    trace_dir = tmp_path / ".bench_out" / cell / "trace"
    trace_dir.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, recording, "v5e-serve.xplane.pb"), trace_dir / "t.xplane.pb")
    with open(os.path.join(DATA, recording, "v5e-serve.stats.json")) as f:
        stats = json.load(f)
    scopes.read_xplane.cache_clear()
    window_counts.read_events.cache_clear()
    return dict(
        cell={"name": cell}, config=config, device_kind="TPU v5 lite",
        trace=trace.reduce_dir(str(trace_dir)), samples=[], extra={"stats_at_end": stats},
    )


@pytest.mark.parametrize("cell", SERVING)
def test_reader_on_a_trace_of_an_engine_whose_chunk_launches_carry(cell, tmp_path, monkeypatch):
    ctx = recorded_ctx(tmp_path, monkeypatch, "carried", cell)
    own = window_counts.window_counts(ctx)
    value = common.load_reader(METRIC)(ctx)
    assert 0 < own["decode_steps_in_chunk"] <= own["decode_steps"]
    assert value == 100.0 * own["decode_steps_in_chunk"] / own["decode_steps"]
    assert 0.0 < value <= 100.0
    # a carried step is a decode step in every count a per-step ratio divides by
    total = scopes.engine_stats(ctx)["counters"]
    assert own["decode_steps_in_chunk"] <= total["decode_steps_in_chunk"]
    assert own["decode_slot_steps"] == (
        own["tokens_generated"] - own["first_tokens"] + own["tokens_discarded"])
    live = common.load_reader("engine.decode_live_rows.window")(ctx)
    assert live == own["decode_slot_steps"] / own["decode_steps"]


@pytest.mark.parametrize("recording", ["counts", "scoped"])
def test_a_trace_of_an_engine_without_the_counter_reads_none(recording, tmp_path, monkeypatch):
    """The parent of the PR that brought the counter, under this PR's files."""
    ctx = recorded_ctx(tmp_path, monkeypatch, recording, SERVING[0])
    assert "decode_steps_in_chunk" not in scopes.engine_stats(ctx).get("counters", {})
    assert common.load_reader(METRIC)(ctx) is None
    assert common.load_reader(METRIC)(dict(ctx, cell={"name": "no-trace-here"}, extra={})) is None


def test_a_window_that_carried_nothing_reads_zero(tmp_path, monkeypatch):
    """A pool that never carries (the latent one): the counter is there, no
    event names it, and the window's steps are all the decode program's."""
    ctx = recorded_ctx(tmp_path, monkeypatch, "carried", "kanana2-serve-docs-shared")
    events = tuple((t, {k: v for k, v in deltas.items() if k != "decode_steps_in_chunk"})
                   for t, deltas in window_counts.read_events(
                       trace.find_xplane(os.path.join(str(tmp_path), ".bench_out",
                                                      "kanana2-serve-docs-shared", "trace"))))
    monkeypatch.setattr(window_counts, "read_events", lambda path: events)
    assert common.load_reader(METRIC)(ctx) == 0.0


def test_the_entry_names_the_scheduler_and_the_serving_cells():
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "%", "better": "higher", "source": "program_span",
                     "layer": "engine scheduler", "moves": "serve_tok_s", "workloads": SERVING}
    assert MANIFEST["per_layer"][-1] is entry  # appended: nothing before it moved
    assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", METRIC + ".py"))
