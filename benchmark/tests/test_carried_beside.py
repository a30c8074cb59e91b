"""The five readers of what a chunk launch carried (PR 53;
``benchmark/carried.py``): read from a small trace of this PR's engine on a
v5e, recorded with ``benchmark/tools/record_scoped_trace.py`` as
``data/carried`` was (``data/beside``: six prompts at once on four slots, so
chunks run beside rows that decode, and several final chunks fall due in one
pass), and None from the trace of the engine before it (``data/carried``: its
chunk programs carry the rows under the chunk's own names, and it counts no
dead launch)."""

import os

import pytest

from benchmark import carried, common, scopes, window_counts
from benchmark.tests.test_decode_in_chunk import MANIFEST, recorded_ctx

CELLS = ["mistral7b-serve-saturated", "laguna-xs2-serve-mixed", "nemotron3-super-serve-chat",
         "solar-open2-serve-long-chat", "granite4-h-micro-serve-sessions",
         "zaya1-8b-serve-long-chat"]
KERNELS = {"kernel.carried_attn_core_ms": "attn_core", "kernel.carried_kv_write_ms": "kv_write",
           "kernel.carried_sampling_ms": "sampling"}
OWN, DEAD = "program.carried_step_own_ms", "engine.carried_step_dead_share.window"
ENTRIES = {
    OWN: ("ms", "device_trace", "programs"),
    **{name: ("ms", "device_trace", "kernels") for name in KERNELS},
    DEAD: ("%", "program_span", "engine scheduler"),
}


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    carried._rows_time.cache_clear()
    return recorded_ctx(tmp_path, monkeypatch, "beside", CELLS[0])


def test_the_rows_own_time_is_above_zero_holds_its_kernels_and_lies_inside_the_modules(ctx):
    own = common.load_reader(OWN)(ctx)
    kernels = {name: common.load_reader(name)(ctx) for name in KERNELS}
    assert own > 0 and all(v > 0 for v in kernels.values())
    # what is left is the rows' alone under attn_qkv, ffn, lm_head, norm (a
    # middle chunk's head and norm here)
    assert own >= sum(kernels.values())
    n, seconds = carried.rows_time(ctx)
    assert own == 1e3 * sum(seconds.values()) / n
    assert {KERNELS[name]: v for name, v in kernels.items()} == {
        scope: 1e3 * seconds[scope] / n for scope in KERNELS.values()}
    # every launch of a carrying form runs the rows, live or dead: the
    # executions that hold such an operation are the launches the engine
    # counted as carrying or dead (a middle chunk of several rows takes none)
    parsed = scopes.trace_of(ctx)
    runs = {m: scopes.module_ops(parsed, m) for m in carried.MODULES}
    counts = window_counts.window_counts(ctx)
    assert n == counts["decode_steps_in_chunk"] + sum(
        counts["decode_steps_dead_in_chunk"].values()) <= sum(c for c, _ in runs.values())
    # and their time lies inside the modules' own
    lo, hi = parsed["window"]
    module_s = sum(b - a for a, b, name in parsed["modules"]
                   if name in carried.MODULES and a >= lo and b <= hi)
    assert 0 < sum(seconds.values()) < module_s
    # the old readers book a rows' operation to the name behind the part
    beside = [op for _, ops in runs.values() for op in ops if carried.is_beside(op[3])]
    assert beside and {scopes.scope_of(op[3]) for op in beside} >= set(KERNELS.values())
    assert not [op for op in scopes.module_ops(parsed, "jit_decode_fn")[1]
                if carried.is_beside(op[3])]


def test_the_dead_share_is_its_counts_ratio(ctx):
    own = window_counts.window_counts(ctx)
    dead = own["decode_steps_dead_in_chunk"]
    assert set(dead) == {"step_carried", "runahead_full", "no_slot"}
    assert sum(dead.values()) > 0 and own["decode_steps_in_chunk"] > 0
    value = common.load_reader(DEAD)(ctx)
    assert value == 100.0 * sum(dead.values()) / (
        sum(dead.values()) + own["decode_steps_in_chunk"])
    assert 0.0 < value < 100.0
    # the launches of a form that takes the rows: every final chunk and the
    # middle chunks of one row (this engine's middle chunks pair up to four)
    launches = own["prefill_programs"]
    assert own["prefill_programs"]["final"] <= sum(dead.values()) + own[
        "decode_steps_in_chunk"] <= launches["final"] + launches["mid"]
    # the cumulative counters hold the window's
    total = scopes.engine_stats(ctx)["counters"]
    assert all(dead[cause] <= total["decode_steps_dead_in_chunk"][cause] for cause in dead)


@pytest.mark.parametrize("metric", list(ENTRIES))
def test_a_trace_of_the_engine_before_reads_none(metric, tmp_path, monkeypatch):
    """The parent of the PR that brought the part and the counter, under this
    PR's files: its chunk launches carry (``data/carried``), nothing says so."""
    carried._rows_time.cache_clear()
    before = recorded_ctx(tmp_path, monkeypatch, "carried", CELLS[0])
    assert window_counts.window_counts(before)["decode_steps_in_chunk"] > 0
    assert common.load_reader(metric)(before) is None
    assert common.load_reader(metric)(dict(before, cell={"name": "no-trace-here"}, extra={})) is None


@pytest.mark.parametrize("metric", list(ENTRIES))
def test_the_entry_names_its_layer_and_the_cells_whose_pools_carry(metric):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    unit, source, layer = ENTRIES[metric]
    assert entry == {"name": metric, "unit": unit, "better": "lower", "source": source,
                     "layer": layer, "moves": "serve_tok_s", "workloads": CELLS}
    assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", metric + ".py"))
