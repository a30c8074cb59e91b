"""Traffic kind ``docs_shared``: ``clients`` callers in a closed loop, each
asking a short question about one of a few long documents that the replica's
prefix cache holds (an assistant over a code base, a manual or a contract; an
agent loop with a long shared preamble). The metric is completion tokens per
second over the window.

The generator in ``benchmark/traffic.py`` makes prompts that share nothing, so
this kind brings its own under the same rule: the sizes (tails and answer
budgets) are the quantile midpoints of the file's distributions and the same
set for every seed; the seed permutes them, orders the documents and draws
every byte. A request is a document followed by a tail of its own, so its
first ``tokens`` tokens (the byte tokenizer's BOS and the document's bytes)
are the same for every request about that document: with the document's
length among the engine's ``prefill_buckets`` that is a prefix-cache key.

The long caches are built before the window opens, not inside it: after
``Served.prepare()`` every document is sent once (its own miss: the prefix
store takes it), then once more with the same question (a hit), then one
request for every final-chunk width behind a seeded prefix of each document
length. All of that is ``setup_s``.

``correct`` also needs: no compile in the window; over the window alone
(the replica's cumulative counters read at its open and its close and
subtracted) at least ``min_window_prefix_share`` of the prompt tokens served
from the prefix cache, which with tails under 1% of a prompt says every
document stayed resident; and each document's question answered the same,
greedy, when the document was a miss and when it was a hit
(``benchmark/compare.py engine_probe`` refuses prefix hits, so this is the
check the seeded path gets)."""

from __future__ import annotations

import random
import threading
import time

from benchmark import common, serving, traffic as gen
from benchmark.common import log, require


def tail_text(rng: random.Random, tokens: int) -> str:
    """``tokens`` printable bytes: behind a document each is one token."""
    return "".join(rng.choices(gen.PRINTABLE, k=tokens))


class Requests:
    """An endless sequence of requests over a fixed set of documents and a
    fixed set of ``pool`` (tail, answer) sizes. Request ``i`` asks about
    document ``i % len(documents)`` (the seed's order) with the sizes of entry
    ``i % pool`` and tail bytes of its own."""

    def __init__(self, traffic: dict, seed: int):
        rng = random.Random(seed)
        lengths = [d["tokens"] for d in traffic["documents"] for _ in range(d["count"])]
        rng.shuffle(lengths)
        # BOS and ``tokens - 1`` bytes: ``tokens`` tokens, a whole bucket
        self.documents = [
            gen.prompt_text(random.Random(seed * 1_000_003 - 1 - j), n)
            for j, n in enumerate(lengths)
        ]
        self.document_tokens = lengths
        tails = gen.stratified(traffic["tail_tokens"], traffic["pool"])
        answers = gen.stratified(traffic["max_tokens"], traffic["pool"])
        rng.shuffle(tails)
        rng.shuffle(answers)
        self.sizes = list(zip(tails, answers))
        self.seed = seed

    def about(self, doc: int, tail_tokens: int, max_tokens: int, draw: int) -> dict:
        rng = random.Random(self.seed * 1_000_003 + draw)
        return {"prompt": self.documents[doc] + tail_text(rng, tail_tokens),
                "prompt_tokens": self.document_tokens[doc] + tail_tokens,
                "document": doc, "max_tokens": max_tokens}

    def __getitem__(self, i: int) -> dict:
        tail, answer = self.sizes[i % len(self.sizes)]
        return self.about(i % len(self.documents), tail, answer, i)


def body_of(model: str, req: dict, traffic: dict) -> dict:
    body = serving.completion_body(model, req, traffic, traffic["stream"])
    body["ignore_eos"] = traffic["ignore_eos"]
    return body


SETUP_TIMEOUT_S = 900.0  # a set-up request may wait for a program to compile


def send_all(served, traffic: dict, reqs: list) -> list:
    """The requests at once, one thread each; every one must succeed."""
    out = [None] * len(reqs)

    def one(i):
        out[i] = serving.http_completion(
            served.url, body_of(served.model, reqs[i], traffic), SETUP_TIMEOUT_S)

    threads = [threading.Thread(target=one, args=(i,), daemon=True) for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in out:
        require(r["ok"], f"set-up request failed: {r['error']}")
    return out


def build_caches(served, traffic: dict, requests: Requests) -> dict:
    """Every document's own miss, the same question again as a hit, and the
    final-chunk widths behind a seeded prefix. Returns what was seen."""
    n = len(requests.documents)
    t = time.perf_counter()
    # draws below zero: no request of the window asks the same question
    questions = [
        requests.about(d, traffic["warmup_tail_tokens"][-1], traffic["hit_check_max_tokens"], -1 - d)
        for d in range(n)
    ]
    before = served.call("stats")["counters"]
    missed = send_all(served, traffic, questions)
    between = served.call("stats")["counters"]
    hit = send_all(served, traffic, questions)
    after = served.call("stats")["counters"]
    miss_s = time.perf_counter() - t

    def from_prefix(a, b):
        return b["prompt_tokens_from_prefix"] - a["prompt_tokens_from_prefix"]

    same = [m["text"] == h["text"] and m["completion_tokens"] == h["completion_tokens"]
            for m, h in zip(missed, hit)]
    widths = []
    seen = set()
    for d, tokens in enumerate(requests.document_tokens):
        if tokens in seen:
            continue
        seen.add(tokens)
        widths += [requests.about(d, tail, traffic["warmup_max_tokens"], -1000 - 10 * d - j)
                   for j, tail in enumerate(traffic["warmup_tail_tokens"])]
    for req in widths:  # one at a time: each compiles at most one program
        send_all(served, traffic, [req])
    return {
        "documents": n, "document_tokens": sum(requests.document_tokens),
        "miss_pass_tokens_from_prefix": from_prefix(before, between),
        "hit_pass_tokens_from_prefix": from_prefix(between, after),
        "same_on_hit": same, "miss_and_hit_s": miss_s,
        "widths_s": time.perf_counter() - t - miss_s,
    }


def prefix_share(opened: dict, closed: dict) -> dict:
    """Of the prompt tokens admitted between two readings of the replica's
    cumulative counters, the share served from the prefix cache."""
    prompt = closed["prompt_tokens"] - opened["prompt_tokens"]
    prefix = closed["prompt_tokens_from_prefix"] - opened["prompt_tokens_from_prefix"]
    return {"prompt_tokens": prompt, "from_prefix": prefix,
            "share": prefix / prompt if prompt else 0.0}


def run(ctx: dict) -> dict:
    from ray_tpu import serve

    args, traffic = ctx["args"], ctx["traffic"]
    try:
        served = serving.Served(ctx)
        checks = served.prepare()
        requests = Requests(traffic, args.seed)
        built = build_caches(served, traffic, requests)
        # the documents' own misses bring none from the cache, their second
        # sends each its whole document
        built_ok = (built["miss_pass_tokens_from_prefix"] == 0
                    and built["hit_pass_tokens_from_prefix"] == built["document_tokens"]
                    and all(built["same_on_hit"]))
        log(built=built, built_ok=built_ok)
        results, lock = [], threading.Lock()
        stop = threading.Event()
        cursor = iter(range(10**9))

        def client():
            while not stop.is_set():
                body = body_of(served.model, requests[next(cursor)], traffic)
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
        opened = served.call("stats")["counters"]
        t0, t0_wall = time.perf_counter(), time.time()
        time.sleep(args.seconds)
        t1 = time.perf_counter()
        at_close = served.call("stats")["counters"]
        stop.set()
        closed = served.window_close()
        for t in threads:  # each finishes the request it has in flight
            t.join(traffic["request_timeout_s"])
        with lock:
            inside = [r for r in results if t0 <= r["t_end"] < t1]
        tokens = sum(r["completion_tokens"] for r in inside if r["ok"])
        summary = serving.summarize_requests(inside)
        in_window = prefix_share(opened, at_close)
        stats = closed["stats"]
        log(requests=summary, completion_tokens=tokens, window_s=t1 - t0,
            compiles_in_window=closed["compiles_in_window"],
            compiled_in_window=closed["compiled_in_window"], memory=closed["memory"],
            prefix_in_window=in_window, stats_at_end=stats)
        return dict(
            correct=(checks["correct"] and built_ok and closed["compiles_in_window"] == 0
                     and in_window["share"] >= traffic["min_window_prefix_share"]),
            attempted=summary["attempted"], failed=summary["failed"],
            e2e={"serve_tok_s": tokens / (t1 - t0), "setup_s": t0_wall - ctx["t_start_wall"]},
            device=common.device_entry(served.device_report, common.peak_bytes(served.device_report)),
            spans=served.spans, trace=closed.get("trace"),
            samples=[x for x in closed["samples"] if t0_wall <= x["t"] <= t0_wall + (t1 - t0)],
            extra={"stats_at_end": stats, "window": [t0_wall, t0_wall + (t1 - t0)],
                   "prefix_in_window": in_window, "built": built},
        )
    finally:
        serve.shutdown()
