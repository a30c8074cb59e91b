"""The per-scope split of a traced run, for PERF.md section 5, from the
``.xplane.pb`` the run left under ``.bench_out/<cell>/trace``:

    python3 benchmark/tools/scope_report.py <cell name or path to an .xplane.pb>

Per compiled program: executions inside the window, device milliseconds an
execution by scope and direction, the operations no scope covers; then the
``engine.*`` host spans, and the device's idle gaps over 1 ms that none of
them covers. One JSON object on the last line."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import common, scopes, trace  # noqa: E402


def report(path: str) -> dict:
    parsed = scopes.read_xplane(path)
    lo, hi = parsed["window"]
    out = {"window_s": hi - lo, "modules": {}}
    for module in sorted({name for _, _, name in parsed["modules"]}):
        n, ops = scopes.module_ops(parsed, module)
        if not n:
            continue
        split, unscoped = {}, {}
        for start, end, name, op_name in ops:
            scope = scopes.scope_of(op_name)
            kind = "optimizer" if scope in scopes.OPTIMIZER else scopes.direction_of(op_name)
            key = f"{scope or 'none'}.{kind}"
            split[key] = split.get(key, 0.0) + 1e3 * (end - start) / n
            if scope is None:
                unscoped[name] = unscoped.get(name, 0.0) + 1e3 * (end - start) / n
        runs = [(a, b) for a, b, m in parsed["modules"] if m == module and a >= lo and b <= hi]
        out["modules"][module] = {
            "executions": n,
            "module_ms": 1e3 * sum(b - a for a, b in runs) / n,
            "ops_ms": sum(split.values()),
            "by_scope_ms": dict(sorted(split.items(), key=lambda kv: -kv[1])),
            "unscoped_top": sorted(unscoped.items(), key=lambda kv: -kv[1])[:8],
        }
    spans = {}
    for a, b, name in parsed["spans"]:
        if b > lo and a < hi:
            entry = spans.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += min(b, hi) - max(a, lo)
    out["engine_spans"] = {k: {"count": c, "total_s": s} for k, (c, s) in sorted(spans.items())}
    busy = trace.clip(
        trace.union([[a, b] for a, b, _, _ in parsed["ops"] if b > lo and a < hi]), lo, hi)
    idle = trace.subtract([[lo, hi]], busy)
    long_gaps = [g for g in idle if g[1] - g[0] > 1e-3]
    uncovered = scopes.uncovered_idle(parsed)
    out["idle"] = {
        "busy_s": trace.total(busy), "idle_s": trace.total(idle),
        "gaps_over_1ms": len(long_gaps), "gaps_over_1ms_s": trace.total(long_gaps),
        "not_under_an_engine_span": len(uncovered),
        "not_under_an_engine_span_s": trace.total(uncovered),
    }
    return out


def main(what: str) -> None:
    path = what if what.endswith(".pb") else trace.find_xplane(
        os.path.join(common.ROOT, ".bench_out", what, "trace"))
    print(json.dumps(report(path), default=float))


if __name__ == "__main__":
    main(sys.argv[1])
