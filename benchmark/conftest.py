"""``benchmark/tests/test_scopes.py`` (PR 26) unpacks ``(cell,) = m["workloads"]``
in its two parametrised reader tests: it assumes every scope-reading metric
lists one cell. PR 28 appended ``laguna-xs2-serve-mixed`` to nine of those
lists, as its issue names them, and may edit no file the benchmark had. So
those cases are marked as expected failures here, by name, and
``benchmark/tests/test_moe_window.py`` runs the same two checks, assertion for
assertion and on the same two recorded v5e traces, once for every cell a
metric lists (and checks that the cases marked here are the ones it runs).
The next ``benchmark`` issue makes the old tests loop over ``m["workloads"]``
and deletes this file and those copies."""

import json
import os

import pytest

_TESTS = ("test_reader_on_the_recorded_trace", "test_reader_finds_nothing_in_a_program_without")


def pytest_collection_modifyitems(items):
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "BENCHMARK.json")) as f:
        shared = {m["name"] for m in json.load(f)["per_layer"] if len(m.get("workloads", ())) > 1}
    for item in items:
        if "test_scopes.py" in item.nodeid and item.name.startswith(_TESTS):
            metric = item.name.split("[", 1)[-1].rstrip("]")
            if metric in shared:
                item.add_marker(pytest.mark.xfail(
                    reason="unpacks one cell a metric; benchmark/tests/test_moe_window.py covers each cell",
                    raises=ValueError, strict=True))
