"""BENCHMARK.json against the contract's shape rules, and the data files it
names."""

import os
import re

import pytest

from benchmark import common

MANIFEST = common.load_manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = {c["name"]: c for c in MANIFEST["workloads"]}


def one_line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys():
    assert set(MANIFEST) - {"_path"} == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["paths"] == ["benchmark"]
    assert os.path.getsize(MANIFEST["_path"]) <= 64 * 1024


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for cell in m.get("workloads", []):
        assert cell in CELLS


def test_names_are_unique():
    for section in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[section]]
        assert len(set(names)) == len(names)
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("m", MANIFEST["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_entry(m):
    assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert 0.01 <= m["bound"] <= 0.1
    assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("m", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_entry(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert one_line(m["layer"])
    moved = [e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"]]
    assert len(moved) == 1
    # every cell that reads the metric reports the end-to-end metric it moves
    for cell in m.get("workloads", CELLS):
        assert "workloads" not in moved[0] or cell in moved[0]["workloads"]
    assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", m["name"] + ".py"))
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda c: c["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and NAME.match(cell["config"])
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    common.load_config(MANIFEST, cell["config"])
    traffic = common.load_traffic(cell["traffic"])
    assert os.path.exists(os.path.join(common.BENCH_DIR, "kinds", traffic["kind"] + ".py"))
    e2e = common.metrics_for(MANIFEST, "end_to_end", cell["name"])
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert common.metrics_for(MANIFEST, "per_layer", cell["name"])


def test_four_chip_cells_are_at_most_a_quarter():
    four = [c for c in MANIFEST["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)


WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|head).*(size|dim)|_dim$|_rank$"
                   r"|head_dim|expansion|experts_per_tok")


def check_config_file(config: dict, reduced: list) -> None:
    """The file's own ``published`` block (the source's values) against the
    values it runs: they differ only in the keys ``reduced`` names, and those
    are no widths."""
    assert config["reduced"] == reduced
    published = config["published"]
    assert published, "a configuration states what its source publishes"
    for key, value in published.items():
        if key in reduced:
            assert config[key] != value, f"{key} is listed as reduced and is not"
        else:
            assert config[key] == value, key
    for key in reduced:
        assert key in published and not WIDTH.search(key), key
    assert os.path.exists(
        os.path.join(common.BENCH_DIR, "families", config["run"]["family"] + ".py"))


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith("benchmark/") and one_line(entry["source"])
    assert any(c["config"] == entry["name"] for c in MANIFEST["workloads"])
    config = common.load_json(os.path.join(common.ROOT, entry["file"]))
    check_config_file(config, entry["reduced"])
    limits = config["run"]["limits"]
    assert limits and all(isinstance(v, float) and v >= 0 for v in limits.values())


@pytest.mark.parametrize("reduced, changed, ok", [
    (["num_hidden_layers"], {"num_hidden_layers": 4}, True),
    ([], {"num_hidden_layers": 4}, False),            # a change that is not listed
    (["num_hidden_layers"], {}, False),               # listed, and not changed
    (["hidden_size"], {"hidden_size": 64}, False),    # a width
    (["head_dim"], {"head_dim": 64}, False),
    (["num_experts_per_tok"], {"num_experts_per_tok": 1}, False),
    (["kv_lora_rank"], {"kv_lora_rank": 8}, False),
], ids=["depth", "unlisted", "unchanged", "width", "head", "experts", "rank"])
def test_config_file_check_on_made_up_sources(reduced, changed, ok):
    """The check takes the published values from the file, so a configuration
    of another source and family passes or fails on its own numbers."""
    published = {"hidden_size": 2048, "head_dim": 128, "num_hidden_layers": 16,
                 "num_experts_per_tok": 8, "kv_lora_rank": 512}
    config = dict(published, **changed, published=published, reduced=reduced,
                  run={"family": "dense_gqa"})
    if ok:
        check_config_file(config, reduced)
    else:
        with pytest.raises(AssertionError):
            check_config_file(config, reduced)


def test_files_under_paths_are_named_from_name_characters():
    for base, _, files in os.walk(common.BENCH_DIR):
        if "__pycache__" in base:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(base, f)


def test_rehearsal_manifest_cannot_pass_for_the_benchmark():
    rehearsal = common.load_manifest(os.path.join(common.BENCH_DIR, "rehearsal.json"))
    assert rehearsal["rehearsal"] is True
    assert not set(c["name"] for c in rehearsal["workloads"]) & set(CELLS)
    for entry in rehearsal["configs"]:
        check_config_file(common.load_json(os.path.join(common.ROOT, entry["file"])), [])
