"""Traffic kind ``open_loop``: requests arrive on a schedule drawn from the
seed at a rate fixed in the traffic file, whatever the server does
(interactive users). Each request is timed from when it was due. The kind
reports the median gap between tokens; time to first token, the tails, the
mean and quartiles of the gaps and the generator's lateness are on an
earlier line of every run. No cell of ``BENCHMARK.json`` uses the kind yet
(PERF.md section 7 says what a streamed cell needs); the rehearsal manifest
and the tests run it."""

from __future__ import annotations

import threading
import time

from benchmark import common, serving, stats, traffic as gen
from benchmark.common import log


def run(ctx: dict) -> dict:
    from ray_tpu import serve

    args, traffic = ctx["args"], ctx["traffic"]
    try:
        served = serving.Served(ctx)
        checks = served.prepare()
        due, n_ramp = gen.arrivals(traffic, args.seed, args.seconds)
        # the window's requests are one whole set of sizes; the ramp has its own
        in_window = gen.Requests(traffic, args.seed, len(due) - n_ramp)
        in_ramp = gen.Requests(traffic, args.seed + 1, max(1, n_ramp))
        results = [None] * len(due)
        late = [0.0] * len(due)

        def one(i: int, t_due: float):
            req = in_ramp[i] if i < n_ramp else in_window[i - n_ramp]
            body = serving.completion_body(served.model, req, traffic, traffic["stream"])
            late[i] = time.perf_counter() - t_due
            r = serving.http_stream(served.url, body, traffic["request_timeout_s"])
            r["t_due"] = t_due
            results[i] = r

        threads = []
        ramp = -due[0] if n_ramp else 0.0
        t_open = time.perf_counter() + ramp + 0.05  # perf_counter time of the window's start
        opened = False
        t0_wall = None
        for i, rel in enumerate(due):
            if i == n_ramp and not opened:
                # the ramp's requests are out: open the window on the replica
                wait = t_open - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                served.window_open()
                t0_wall, opened = time.time(), True
                # opening took a moment; the schedule moves with it
                t_open = time.perf_counter()
            t_due = t_open + rel
            wait = t_due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            t = threading.Thread(target=one, args=(i, t_due), daemon=True)
            t.start()
            threads.append(t)
        wait = t_open + args.seconds - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        closed = served.window_close()
        for t in threads:  # requests of the window that are still streaming
            t.join(traffic["request_timeout_s"])

        window = [r or {"ok": False, "error": "never returned", "chunk_t": []}
                  for r in results[n_ramp:]]
        ttft = [
            1e3 * (r["chunk_t"][0] - r["t_due"]) if r["ok"] and r["chunk_t"] else None
            for r in window
        ]
        gaps = [
            1e3 * (b - a) for r in window if r["ok"]
            for a, b in zip(r["chunk_t"], r["chunk_t"][1:])
        ]
        summary = serving.summarize_requests(window)
        # for the sweep: requests (the ramp's too) that finished inside the
        # window, over those due inside it; under the knee the two are equal
        t_close = t_open + args.seconds
        finished_inside = sum(
            1 for r in results if r and r["ok"] and t_open <= r["t_end"] < t_close
        )
        lateness = [1e3 * x for x in late[n_ramp:]]
        end = closed["stats"]
        log(requests=summary, ttft_samples=len(ttft), itl_samples=len(gaps),
            ttft_p50_ms=stats.percentile(ttft, 50), itl_p50_ms=stats.percentile(gaps, 50),
            ttft_p95_ms=stats.percentile(ttft, 95), itl_p95_ms=stats.percentile(gaps, 95),
            ttft_p99_ms=stats.percentile(ttft, 99), itl_p99_ms=stats.percentile(gaps, 99),
            itl_mean_ms=sum(gaps) / len(gaps) if gaps else None,
            itl_quartiles_ms=[stats.percentile(gaps, q) for q in (10, 25, 75, 90)],
            late_p95_ms=stats.percentile(lateness, 95), late_max_ms=max(lateness),
            completed_share=(summary["attempted"] - summary["failed"]) / summary["attempted"],
            finished_inside_over_offered=finished_inside / len(window),
            completion_tokens=sum(r.get("completion_tokens", 0) for r in window if r["ok"]),
            rate_per_s=traffic["rate_per_s"], compiles_in_window=closed["compiles_in_window"], compiled_in_window=closed["compiled_in_window"], memory=closed["memory"],
            waiting_at_end=end["waiting"] + end["admitting"], active_at_end=end["active_slots"])
        return dict(
            correct=checks["correct"] and closed["compiles_in_window"] == 0,
            attempted=summary["attempted"], failed=summary["failed"],
            e2e={
                "itl_p50_ms": stats.percentile(gaps, 50),
                "setup_s": t0_wall - ctx["t_start_wall"],
            },
            device=common.device_entry(served.device_report, common.peak_bytes(served.device_report)),
            spans=served.spans,
            samples=[x for x in closed["samples"] if t0_wall <= x["t"] <= t0_wall + args.seconds],
            trace=closed.get("trace"),
            extra={"stats_at_end": end},
        )
    finally:
        serve.shutdown()
