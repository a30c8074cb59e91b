"""``program.moe_single_pass_share``: the reader on a stats line with and
without ``moe_passes``, and its entry in the manifest."""

import os

import pytest

from benchmark import common

NAME = "program.moe_single_pass_share"
HELD_CELLS = ["nemotron3-super-serve-chat", "solar-open2-serve-long-chat"]
RUNS = {"decode": 800, "chunk_mid": 120, "chunk_final": 80}


def _ctx(**counters):
    return {"extra": {"stats_at_end": {"counters": counters}}}


@pytest.mark.parametrize("passes,want", [
    (RUNS, 100.0),  # a block a layer run: none overflowed
    ({"decode": 800, "chunk_mid": 125, "chunk_final": 85}, 99.0),  # ten second blocks in 1,000 runs
    ({"decode": 1600, "chunk_mid": 240, "chunk_final": 160}, 0.0),  # every run took two
    ({"decode": 3200, "chunk_mid": 480, "chunk_final": 320}, 0.0),  # four a run: floored
], ids=["none-overflowed", "one-in-a-hundred", "every-run", "floored"])
def test_the_share_of_layer_runs_one_block_served(passes, want):
    read = common.load_reader(NAME)
    assert read(_ctx(moe_layer_steps=RUNS, moe_passes=passes)) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {"moe_layer_steps": RUNS},  # the parent's engine: no such counter
    {"moe_layer_steps": RUNS, "moe_passes": dict.fromkeys(RUNS, 0)},  # a model that holds all its experts
    {"moe_passes": RUNS},
    {"moe_layer_steps": dict.fromkeys(RUNS, 0), "moe_passes": dict.fromkeys(RUNS, 0)},
    {},
], ids=["parent", "nothing-held", "no-runs-counted", "no-run-yet", "no-counters"])
def test_an_engine_that_counts_no_passes_reads_nothing(counters):
    read = common.load_reader(NAME)
    assert read(_ctx(**counters)) is None
    assert read({"extra": {}}) is None


def test_the_manifest_lists_it_last_for_the_two_cells_that_hold_a_share():
    manifest = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
    entry = manifest["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "programs", "moves": "serve_tok_s", "workloads": HELD_CELLS,
    }
    held_share = next(m for m in manifest["per_layer"]
                      if m["name"] == "program.moe_held_assignment_share")
    assert held_share["workloads"] == HELD_CELLS and held_share["layer"] == entry["layer"]
    for cell in HELD_CELLS:
        assert NAME in [m["name"] for m in common.metrics_for(manifest, "per_layer", cell)]
