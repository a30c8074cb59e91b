"""Every device operation of one compiled program in a traced run, in the
order it runs, for the question a per-scope sum cannot answer: what runs
between two kernels, and under which name.

    python3 benchmark/tools/module_ops.py <cell name or path to an .xplane.pb> <module>

For each operation of the module's whole executions inside the window: its
name, the last parts of its ``op_name`` path, the scope it is booked to, its
mean start (milliseconds after its execution's start) and mean duration, and
how many times an execution runs it. Then the module's time, the sum of its
operations, and the union of their intervals (a sum above the union means
operations overlap). One JSON object on the last line."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import common, scopes, trace  # noqa: E402


def report(path: str, module: str) -> dict:
    parsed = scopes.read_xplane(path)
    lo, hi = parsed["window"]
    runs = sorted((a, b) for a, b, m in parsed["modules"] if m == module and a >= lo and b <= hi)
    n, ops = scopes.module_ops(parsed, module)
    if not n:
        return {"module": module, "executions": 0}
    rows, at = {}, 0
    for start, end, name, op_name in sorted(ops):
        while at + 1 < len(runs) and runs[at + 1][0] <= start:
            at += 1
        row = rows.setdefault((name, op_name), [0, 0.0, 0.0])
        row[0] += 1
        row[1] += start - runs[at][0]
        row[2] += end - start
    table = sorted(
        ({"name": name, "path": "/".join(op_name.split("/")[-4:]), "scope": scopes.scope_of(op_name),
          "start_ms": 1e3 * s / c, "ms": 1e3 * d / n, "per_execution": c / n}
         for (name, op_name), (c, s, d) in rows.items()),
        key=lambda r: r["start_ms"])
    busy = trace.union([[a, b] for a, b, _, _ in ops])
    return {
        "module": module, "executions": n,
        "module_ms": 1e3 * sum(b - a for a, b in runs) / n,
        "ops_sum_ms": 1e3 * sum(b - a for a, b, _, _ in ops) / n,
        "ops_union_ms": 1e3 * trace.total(busy) / n,
        "ops": table,
    }


def main(what: str, module: str) -> None:
    path = what if what.endswith(".pb") else trace.find_xplane(
        os.path.join(common.ROOT, ".bench_out", what, "trace"))
    print(json.dumps(report(path, module), default=float))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
