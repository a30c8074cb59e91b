"""What every part of the harness shares: where things are, the manifest, the
metric readers, and the one result line."""

from __future__ import annotations

import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# seeds come as large as a little over 2**31; the program's model seed goes
# through int32 arithmetic in places (engine: ``seed ^ 0x5EED``)
MODEL_SEED_MOD = 2**31 - 1


def log(**line) -> None:
    """An earlier line of the output: one JSON object, never the last line."""
    print(json.dumps(line, default=float), flush=True)


class BenchFailure(Exception):
    """The run cannot give a result: exit nonzero, print no result line."""


def require(cond: bool, why: str) -> None:
    if not cond:
        raise BenchFailure(why)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str) -> dict:
    manifest = load_json(path)
    manifest["_path"] = path
    return manifest


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchFailure(
        f"no workload {name!r} in {manifest['_path']} "
        f"(known: {[c['name'] for c in manifest['workloads']]})"
    )


def load_config(manifest: dict, name: str) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == name:
            return load_json(os.path.join(ROOT, entry["file"]))
    raise BenchFailure(f"no configuration {name!r} in {manifest['_path']}")


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def metrics_for(manifest: dict, section: str, cell: str) -> list[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports: those
    that list it under ``workloads``, and those that list nothing."""
    return [
        m for m in manifest[section]
        if "workloads" not in m or cell in m["workloads"]
    ]


def load_reader(metric: str):
    """The ``read`` function of ``benchmark/metrics/<metric>.py``."""
    import importlib.util

    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def decide(errors: dict, limits: dict) -> dict:
    """name -> {value, limit, ok} for every number a limit is set on."""
    return {
        k: {"value": errors[k], "limit": lim, "ok": bool(errors[k] <= lim)}
        for k, lim in limits.items()
    }


def device_entry(report: dict, memory_peak_bytes: int) -> dict:
    """``device`` of the result line, from what the chip's process saw."""
    return {
        "platform": report["platform"],
        "kind": report["device_kind"],
        "count": report["device_count"],
        "memory_peak_bytes": int(memory_peak_bytes),
    }


def peak_bytes(report: dict, program_bytes: int = 0) -> int:
    """The fullest chip's peak. ``peak_bytes_in_use`` leaves out what a
    compiled program allocates for itself (PERF.md, PR 21), so a caller that
    knows its program's argument and temporary bytes passes them."""
    peaks = [b or 0 for b in report.get("peak_bytes_in_use") or [0]]
    return max(max(peaks), int(program_bytes))


def emit_result(correct, attempted, failed, metrics, device, breakdown=None,
                rehearsal=False) -> None:
    line = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": metrics, "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    if rehearsal:
        # never to be read as a chip number: no ``metrics`` key at all
        line["rehearsal_metrics"] = line.pop("metrics")
        line["rehearsal"] = True
    sys.stdout.flush()
    print(json.dumps(line, default=float), flush=True)
