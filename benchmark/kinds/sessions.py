"""Traffic kind ``sessions``: ``clients`` callers in a closed loop, each playing
scripted multi-turn conversations one after another (a chat assistant's
conversations, an agent's tool loop). All sessions open with the same system
prompt; turn k's prompt is the system prompt, then for every earlier turn its
user message and a scripted assistant reply, then user message k. So all of a
turn's prompt but its last user message was the previous turn's prompt and
reply budget, and a replica that stores what a finished prompt left (the
prefix cache: keys and values, and for a model that keeps a state a slot a
snapshot of the state) serves turn k + 1 from turn k. The metric is completion
tokens per second over the window.

The reply that enters the history is scripted (seeded bytes, as many as the
turn's answer budget) and not the model's own: on seeded weights the model
answers ids that the byte tokenizer does not map back to themselves, so a
client could not send the same tokens again.

The generator follows ``benchmark/traffic.py``'s rule: the scripts (a number of
turns, each turn's user-message length and answer budget) are a fixed set, the
lengths at the quantile midpoints of the file's distributions dealt out by a
constant shuffle, the same for every seed; the seed orders the scripts and
draws every byte. A session instance draws bytes of its own, so two sessions
share the system prompt and nothing else.

Set-up, after ``Served.prepare()`` (all of it ``setup_s``): the check sessions,
one request at a time: turn 2 cold (nothing it starts with is stored yet: a
miss), then turn 1, then turn 2 again, which must be seeded from turn 1's
prompt at its exact length (the replica's ``prompt_tokens_from_prefix`` read
before and after). What the two turns 2 *leave* is compared, not what they
answer: the replica takes the snapshot the miss stored out of its prefix cache
and keeps it (``SessionsServer.bench_take_snapshot``), so the hit stores its
own under the same prompt, and ``bench_snapshot_distance`` reads how far the
two lie apart (``snapshot_distance``: the state leaves, and the keys and
values of the positions the seeded turn computed itself, behind turn 1's
length). Then the system prompt alone, so that what it leaves is resident when
the clients start. A session lasts longer than any ramp, so the clients start
staggered: client i opens its first session at turn ``1 + i % stagger_turns``
with that turn's prompt sent cold, and the window opens on a steady mix of
turns (logged for the window's first and last seconds).

``correct`` also needs: no compile in the window; over the window alone at
least ``min_window_prefix_share`` of the prompt tokens served from the prefix
cache; every check session's turn 2 seeded from exactly turn 1's length when
sent again; and in every check session what the seeded turn left within
``hit_check_max_state_rel_rms`` and ``hit_check_max_kv_rel_rms`` of what the
miss left. A hit is not computed as its miss was (the scan's chunks fall
elsewhere), so the two differ by the served type's rounding; seeded from
another session's snapshot or with the convolution tails zeroed they differ
by several times more (``benchmark/tools/snapshot_control.py`` reads the sound
seed and both controls on the chip through this module's own
``snapshot_distance`` and ``within``; the traffic file gives the readings the
two limits lie between). The answers' tokens decide nothing: on seeded
weights they are ids the byte tokenizer has no text for, and at unit entries
of the tied table they were the last prompt token repeated whatever the seed
(PERF.md section 2, PR 44; the tool, which holds the ids, prints how many
lead alike). What no run on the chip separates is a state that went through
bfloat16 (it lies inside the hit's own rounding): the CPU tests hold that
(``tests/test_snapshot_prefix.py``)."""

from __future__ import annotations

import random
import threading
import time
from unittest import mock

import numpy as np

from benchmark import common, serving, traffic as gen
from benchmark.common import log, require
from benchmark.kinds.docs_shared import SETUP_TIMEOUT_S, body_of, prefix_share, tail_text

SCRIPT_SHUFFLE = 0x5E5510  # the constant that deals the sizes out to the scripts


def scripts(traffic: dict) -> list[list[tuple[int, int]]]:
    """The fixed set of session scripts: ``traffic["scripts"]`` lists of
    (user tokens, answer budget) a turn, the same for every seed. Turn counts
    go round ``turns.min`` .. ``turns.max``; a script ends early where its
    next prompt and answer would pass ``max_session_tokens``."""
    n, lo, hi = traffic["scripts"], traffic["turns"]["min"], traffic["turns"]["max"]
    counts = [lo + j % (hi - lo + 1) for j in range(n)]
    users = gen.stratified(traffic["user_tokens"], sum(counts))
    answers = gen.stratified(traffic["max_tokens"], sum(counts))
    rng = random.Random(SCRIPT_SHUFFLE)
    rng.shuffle(users)
    rng.shuffle(answers)
    out, at = [], 0
    for count in counts:
        script, prompt = [], traffic["system_tokens"]
        for user, answer in zip(users[at:at + count], answers[at:at + count]):
            if prompt + user + answer > traffic["max_session_tokens"]:
                break
            script.append((user, answer))
            prompt += user + answer
        out.append(script)
        at += count
    return out


def script_tokens(traffic: dict, script: list) -> dict:
    """Prompt tokens a whole session sends, and of them those that were an
    earlier prompt of the session or the system prompt (what a prefix cache
    that keeps every turn can serve)."""
    prompt, sent, shared = traffic["system_tokens"], 0, 0
    for user, answer in script:
        sent += prompt + user
        shared += prompt
        prompt += user + answer
    return {"prompt_tokens": sent, "shared_tokens": shared}


class Sessions:
    """Session instance ``n`` plays script ``order[n % len(order)]`` (the
    seed's order) with bytes of its own; ``turn(n, k)`` is the request of its
    turn ``k`` (0-based), or None where the script has no such turn."""

    def __init__(self, traffic: dict, seed: int):
        self.traffic, self.seed = traffic, seed
        self.scripts = scripts(traffic)
        self.order = list(range(len(self.scripts)))
        random.Random(seed).shuffle(self.order)
        # BOS and ``system_tokens - 1`` bytes
        self.system = gen.prompt_text(random.Random(seed * 1_000_003 - 1), traffic["system_tokens"])
        self._texts: dict = {}

    def script(self, n: int) -> list:
        return self.scripts[self.order[n % len(self.order)]]

    def _pieces(self, n: int) -> list:
        """(user text, reply text) of every turn of instance ``n``."""
        if n not in self._texts:
            rng = random.Random(self.seed * 1_000_003 + 7919 * n + 1)
            self._texts[n] = [(tail_text(rng, user), tail_text(rng, answer))
                              for user, answer in self.script(n)]
        return self._texts[n]

    def drop(self, n: int) -> None:
        self._texts.pop(n, None)

    def turn(self, n: int, k: int) -> "dict | None":
        script = self.script(n)
        if k >= len(script):
            return None
        pieces = self._pieces(n)
        history = "".join(user + reply for user, reply in pieces[:k])
        prompt = self.system + history + pieces[k][0]
        return {"prompt": prompt, "prompt_tokens": 1 + len(prompt), "max_tokens": script[k][1],
                "session": n, "turn": k}

    def system_request(self) -> dict:
        return {"prompt": self.system, "prompt_tokens": self.traffic["system_tokens"],
                "max_tokens": self.traffic["warmup_max_tokens"]}


def _rel_rms(got, want) -> float:
    got, want = (np.asarray(x.astype("float32"), np.float64) for x in (got, want))
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


def snapshot_distance(left: dict, want: dict, start: int) -> dict:
    """How far two snapshots of one prompt (entries of the engine's prefix
    cache: ``llm/engine.py _snapshot_store``) lie apart, as relative root mean
    squares against ``want``: ``state`` over each state leaf (the largest),
    ``kv`` over the keys and over the values of positions ``start`` up to the
    prompt's end (the larger): with ``start`` the length a prompt was seeded
    at, the positions it computed itself."""
    end = want["length"]
    return {
        "state": max(_rel_rms(left["state"][n], want["state"][n]) for n in want["state"]),
        "kv": max(_rel_rms(left[n][:, :, start:end], want[n][:, :, start:end]) for n in ("k", "v")),
    }


def within(distance: dict, traffic: dict) -> bool:
    """The rule of the check sessions on one session's ``snapshot_distance``."""
    return (distance["state"] <= traffic["hit_check_max_state_rel_rms"]
            and distance["kv"] <= traffic["hit_check_max_kv_rel_rms"])


class SessionsServer(serving.BenchLLMServer):
    """The replica with what the check sessions read of its prefix cache. The
    engine's programs hand out tokens only, so what a seeded prompt left is
    read where it lies, as ``bench_check_reference`` reads the probe's keys
    and values."""

    def _snapshot_key(self, prompt: str) -> bytes:
        ids = self.engine.tokenizer.encode(prompt)
        return self.engine._prefix_key(ids, len(ids))

    def bench_take_snapshot(self, prompt: str) -> int:
        """Take the snapshot ``prompt`` left out of the prefix cache and keep
        it: the same prompt sent again then stores what *it* leaves (a prompt
        that is stored already stores nothing). Returns its length."""
        engine = self.engine
        self._bench_taken = engine._prefix_cache.pop(self._snapshot_key(prompt))
        engine._prefix_bytes -= self._bench_taken["nbytes"]
        return self._bench_taken["length"]

    def bench_snapshot_distance(self, prompt: str, start: int) -> dict:
        """What ``prompt`` has left now against what was taken."""
        taken, self._bench_taken = self._bench_taken, None
        return snapshot_distance(self.engine._prefix_cache[self._snapshot_key(prompt)], taken, start)


def send_one(served, traffic: dict, req: dict) -> tuple[dict, int]:
    """One set-up request alone, and the prompt tokens the replica served it
    from its prefix cache (its cumulative counter before and after)."""
    before = served.call("stats")["counters"]["prompt_tokens_from_prefix"]
    r = serving.http_completion(served.url, body_of(served.model, req, traffic), SETUP_TIMEOUT_S)
    require(r["ok"], f"set-up request failed: {r['error']}")
    return r, served.call("stats")["counters"]["prompt_tokens_from_prefix"] - before


def check_sessions(served, traffic: dict, sessions: Sessions) -> dict:
    """Turn 2 cold, turn 1, turn 2 again, for each check session (instances
    below zero: no session of the window has their bytes)."""
    t = time.perf_counter()
    budget = traffic["hit_check_max_tokens"]
    seen = []
    for j in range(traffic["hit_check_sessions"]):
        n = -1 - j
        one, two = sessions.turn(n, 0), sessions.turn(n, 1)
        one, two = dict(one, max_tokens=budget), dict(two, max_tokens=budget)
        cold, cold_from = send_one(served, traffic, two)
        require(served.call("bench_take_snapshot", two["prompt"]) == two["prompt_tokens"],
                "the snapshot a check session's turn 2 left is not as long as its prompt")
        _, one_from = send_one(served, traffic, one)
        hit, hit_from = send_one(served, traffic, two)
        distance = served.call("bench_snapshot_distance", two["prompt"], one["prompt_tokens"])
        seen.append({
            "turn1_tokens": one["prompt_tokens"], "turn2_tokens": two["prompt_tokens"],
            "from_prefix": [cold_from, one_from, hit_from],
            "left": distance, "within": within(distance, traffic),
            "completion_tokens": [cold["completion_tokens"], hit["completion_tokens"]],
        })
        sessions.drop(n)
    ok = all(
        s["within"] and s["from_prefix"] == [0, 0, s["turn1_tokens"]]
        and s["completion_tokens"] == [budget, budget]
        for s in seen
    )
    return {"sessions": seen, "ok": ok, "seconds": time.perf_counter() - t,
            "left_max": {k: max(s["left"][k] for s in seen) for k in ("state", "kv")}}


def turn_mix(results: list, lo: float, hi: float) -> dict:
    mix: dict = {}
    for r in results:
        if lo <= r["t_end"] < hi:
            mix[r["turn"] + 1] = mix.get(r["turn"] + 1, 0) + 1
    return dict(sorted(mix.items()))


def run(ctx: dict) -> dict:
    from ray_tpu import serve

    args, traffic = ctx["args"], ctx["traffic"]
    try:
        # ``serving.build_app`` deploys the class this name holds when it is
        # called: the kind's replica is the harness's with two methods more
        with mock.patch.object(serving, "BenchLLMServer", SessionsServer):
            served = serving.Served(ctx)
        checks = served.prepare()
        sessions = Sessions(traffic, args.seed)
        checked = check_sessions(served, traffic, sessions)
        _, system_from = send_one(served, traffic, sessions.system_request())
        predicted = [script_tokens(traffic, s) for s in sessions.scripts]
        log(checked=checked, system_prompt_from_prefix=system_from,
            scripts={"n": len(sessions.scripts),
                     "turns": sum(len(s) for s in sessions.scripts),
                     "predicted_shared_share": sum(p["shared_tokens"] for p in predicted)
                     / sum(p["prompt_tokens"] for p in predicted)})
        results, lock = [], threading.Lock()
        stop = threading.Event()
        cursor = iter(range(10**9))

        def client(i: int):
            first = True
            while not stop.is_set():
                with lock:
                    n = next(cursor)
                # a client's first session opens in its middle, that turn cold
                k = i % traffic["stagger_turns"] if first else 0
                first = False
                while not stop.is_set():
                    req = sessions.turn(n, k)
                    if req is None:
                        break
                    r = serving.http_completion(
                        served.url, body_of(served.model, req, traffic), traffic["request_timeout_s"])
                    r["turn"] = k
                    with lock:
                        results.append(r)
                    k += 1
                sessions.drop(n)

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True, name=f"client-{i}")
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
        edge = min(traffic["turn_mix_seconds"], (t1 - t0) / 2)
        log(requests=summary, completion_tokens=tokens, window_s=t1 - t0,
            compiles_in_window=closed["compiles_in_window"],
            compiled_in_window=closed["compiled_in_window"], memory=closed["memory"],
            prefix_in_window=in_window,
            turn_mix={"first": turn_mix(inside, t0, t0 + edge), "last": turn_mix(inside, t1 - edge, t1)},
            stats_at_end=stats)
        return dict(
            correct=(checks["correct"] and checked["ok"] and closed["compiles_in_window"] == 0
                     and in_window["share"] >= traffic["min_window_prefix_share"]),
            attempted=summary["attempted"], failed=summary["failed"],
            e2e={"serve_tok_s": tokens / (t1 - t0), "setup_s": t0_wall - ctx["t_start_wall"]},
            device=common.device_entry(served.device_report, common.peak_bytes(served.device_report)),
            spans=served.spans, trace=closed.get("trace"),
            samples=[x for x in closed["samples"] if t0_wall <= x["t"] <= t0_wall + (t1 - t0)],
            extra={"stats_at_end": stats, "window": [t0_wall, t0_wall + (t1 - t0)],
                   "prefix_in_window": in_window, "checked": checked},
        )
    finally:
        serve.shutdown()
