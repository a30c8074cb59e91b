"""``entry.programs_restored_share``: the reader on a stats line with and
without ``init.programs``, and its entry in the manifest."""

import os

import pytest

from benchmark import common

NAME = "entry.programs_restored_share"
SERVING_CELLS = [
    "mistral7b-serve-saturated", "laguna-xs2-serve-mixed", "kanana2-serve-docs-shared",
    "nemotron3-super-serve-chat", "solar-open2-serve-long-chat", "granite4-h-micro-serve-sessions",
]


def _ctx(init):
    return {"extra": {"stats_at_end": {"init": init}}}


@pytest.mark.parametrize("programs,want", [
    ({"restored": 12, "compiled": 0, "fallback": 0}, 100.0),  # a warm start
    ({"restored": 0, "compiled": 12, "fallback": 0}, 0.0),  # a checkout's first
    ({"restored": 9, "compiled": 0, "fallback": 3}, 75.0),  # three kept files did not load
    ({"restored": 6, "compiled": 6, "fallback": 0}, 50.0),  # half the forms are new
], ids=["warm", "first", "fallback", "half-new"])
def test_the_share_of_forms_restored(programs, want):
    read = common.load_reader(NAME)
    assert read(_ctx({"warm_programs_s": 3.0, "programs": programs})) == pytest.approx(want)


@pytest.mark.parametrize("init", [
    {"warm_programs_s": 40.7, "warm_programs_by_program_s": {"decode": 1.0}},  # the parent's engine
    {"programs": {"restored": 0, "compiled": 0, "fallback": 0}},  # no compile cache, or a mesh
    {"programs": None},
    None,
], ids=["parent", "restores-nothing", "null", "no-init"])
def test_an_engine_without_the_counter_reads_nothing(init):
    read = common.load_reader(NAME)
    assert read(_ctx(init)) is None
    assert read({"extra": {}}) is None


def test_the_manifest_lists_it_for_the_serving_cells_on_the_entry_layer():
    manifest = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    warm = next(m for m in manifest["per_layer"] if m["name"] == "entry.engine_warm_programs_s")
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": warm["layer"], "moves": "setup_s", "workloads": SERVING_CELLS,
    }
    assert warm["workloads"] == SERVING_CELLS
    for cell in SERVING_CELLS:
        assert NAME in [m["name"] for m in common.metrics_for(manifest, "per_layer", cell)]
    assert NAME not in [m["name"] for m in common.metrics_for(
        manifest, "per_layer", "mistral7b-train-1chip")]
