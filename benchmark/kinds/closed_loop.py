"""Traffic kind ``closed_loop``: ``clients`` callers, each sending its next
request when the last returns (batch inference, evaluation jobs). The metric
is completion tokens per second over the window."""

from __future__ import annotations

import threading
import time

from benchmark import common, serving, traffic as gen
from benchmark.common import log


def run(ctx: dict) -> dict:
    from ray_tpu import serve

    args, traffic = ctx["args"], ctx["traffic"]
    try:
        served = serving.Served(ctx)
        checks = served.prepare()
        requests = gen.Requests(traffic, args.seed, traffic["pool"])
        results, lock = [], threading.Lock()
        stop = threading.Event()
        cursor = iter(range(10**9))

        def client():
            while not stop.is_set():
                req = requests[next(cursor)]
                body = serving.completion_body(served.model, req, traffic, traffic["stream"])
                r = serving.http_completion(served.url, body, traffic["request_timeout_s"])
                with lock:
                    results.append(r)

        threads = [
            threading.Thread(target=client, daemon=True, name=f"client-{i}")
            for i in range(traffic["clients"])
        ]
        for t in threads:
            t.start()
        time.sleep(traffic["ramp_seconds"])
        served.window_open()
        t0, t0_wall = time.perf_counter(), time.time()
        time.sleep(args.seconds)
        t1 = time.perf_counter()
        stop.set()
        closed = served.window_close()
        for t in threads:  # each finishes the request it has in flight
            t.join(traffic["request_timeout_s"])
        with lock:
            inside = [r for r in results if t0 <= r["t_end"] < t1]
        tokens = sum(r["completion_tokens"] for r in inside if r["ok"])
        summary = serving.summarize_requests(inside)
        log(requests=summary, completion_tokens=tokens, window_s=t1 - t0,
            compiles_in_window=closed["compiles_in_window"], compiled_in_window=closed["compiled_in_window"], memory=closed["memory"], stats_at_end=closed["stats"])
        return dict(
            correct=checks["correct"] and closed["compiles_in_window"] == 0,
            attempted=summary["attempted"], failed=summary["failed"],
            e2e={"serve_tok_s": tokens / (t1 - t0), "setup_s": t0_wall - ctx["t_start_wall"]},
            device=common.device_entry(served.device_report, common.peak_bytes(served.device_report)),
            spans=served.spans, trace=closed.get("trace"),
            samples=[x for x in closed["samples"] if t0_wall <= x["t"] <= t0_wall + (t1 - t0)],
            extra={"stats_at_end": closed["stats"], "window": [t0_wall, t0_wall + (t1 - t0)]},
        )
    finally:
        serve.shutdown()
