"""Traffic kind ``block_closed_loop``: the closed loop of
``benchmark/kinds/closed_loop.py`` for a model that generates by diffusion
over blocks (JetLM SDAR; ``models/llama.py block_step``): ``clients`` callers,
each sending its next request when the last returns, every request with
``ignore_eos`` (its length is its ``max_tokens``) and the denoising steps the
traffic file deals out by the request's number (``denoise_steps``: request
``i`` asks the ``i % len``-th). The metric is completion tokens per second
over the window.

The replica is the harness's with another ``bench_check_reference``
(``BlockServer``, swapped in as ``benchmark/kinds/sessions.py`` swaps its
own): ``benchmark/compare.py`` holds ``prefill`` and ``decode_step`` to a
causal reference and reads one token a step out of the engine, and this model
has neither. What decides ``correct`` here comes, all of it, from what the
timed programs produce at the published widths: ``probe.requests`` requests
(as many as the engine has slots) through the engine's own loop at once, each
at its own denoising steps, so that the window's ``jit_block_step`` runs with
every slot bound and requests of different steps in one launch; prompts of one,
two and three chunks. Every fetched hand-out of that program (a slot's block
after the forward, whether it committed, how many positions it unmasked) is
kept (``HandOuts``), and each request's denoise forwards are told again from
them (``forwards_of``): the block as it was forwarded, the positions the
forward unmasked and the tokens it wrote there.

- ``kv_prefill_rel_rms``, ``kv_commit_rel_rms``: the keys and values the
  engine's chunk programs (the prompt's whole blocks) and its committing block
  steps (every generated block) left in the engine's own cache, every layer,
  every request, against the reference's forward pass under the block mask
  over the prompt and the engine's own tokens. A commit that was skipped, or
  whose keys were taken from a denoise forward, fails here: the positions
  unmasked last would hold the mask token's keys.
- ``x0_logit_gap``: the reference judges the tokens the engine wrote. For
  ``probe.judged_forwards`` denoise forwards of each request the reference's
  ``denoise_rows`` gives the logits of the block as the engine forwarded it;
  at every position the forward unmasked, the reference's largest logit less
  its logit of the engine's token, over the row's standard deviation; the
  mean. Logits and not equal tokens: on seeded weights the largest of 151,936
  logits changes on rounding, so a sound token stands a rounding error under
  the reference's best, and a token from a wrong head, norm, mask column or
  last layer stands a row's spread under it (about 4).
- ``confidence_order_err``: the reference judges which positions the engine
  unmasked. In the same forwards, where the engine left a position masked:
  the reference's log-confidence (its largest logit less the row's
  log-sum-exp) of the best position left, less that of the worst position
  taken, where that is over 0; summed, over the sum of the masked positions'
  range of it. 0: the engine took what the reference would; 1: it took the
  least confident; a choice by chance reads about a half.
- ``unmasked_per_forward_err``: from the engine's counters over the probe,
  the positions unmasked over the denoise forwards against what the schedule
  gives for the same requests (``block_length / denoise_steps`` a forward of
  a whole block; the first block of a prompt with a tail has fewer masked),
  and the forwards and commits themselves: all exact, so the limit is 0. It
  reads 1 as well where a probe answer is not its ``max_tokens`` long or holds
  a mask token, or where the hand-outs do not tell one story (a committed
  block that is not the block the forwards left, a count of unmasked positions
  that is not the positions that changed).
- the same greedy request twice gives the same answer
  (``serving.Served.prepare``); nothing compiles in the window.

``--control int8`` runs the program's side on weights cut to int8 and has to
come out as not correct by at least one limit (``run.limits``; PERF.md gives
the readings each lies between)."""

from __future__ import annotations

import threading
import time
from unittest import mock

import numpy as np

from benchmark import common, families, serving, traffic as gen
from benchmark.common import log, require


def schedule(step: int, steps: int, block: int) -> int:
    """Positions denoising step ``step`` of ``steps`` unmasks in a block."""
    return block // steps + (step < block % steps)


def expected_forwards(prompt: int, answer: int, steps: int, block: int) -> dict:
    """What one request costs by the schedule alone: denoise forwards,
    commits and positions unmasked (a first block holds ``prompt % block``
    clean tokens; a request ends on the commit that reaches its answer)."""
    tail, made, out = prompt % block, 0, dict(denoise=0, commit=0, unmasked=0)
    while made < answer:
        left = block - tail
        for j in range(steps):
            if left <= 0:
                break
            took = min(left, schedule(j, steps, block))
            out["denoise"] += 1
            out["unmasked"] += took
            left -= took
        out["commit"] += 1
        made, tail = made + block - tail, 0
    return out


def probe_requests(seed: int, probe: dict, block: int) -> list:
    """The probe's requests: seeded byte tokens, a prompt length, an answer
    budget and denoising steps each (dealt out by the request's number).
    ``long_prompts`` come first, each with the shortest answer allowed; the
    others draw an answer from ``answers`` (smallest, largest) and take the
    prompt that fills one of ``totals`` with it (few lengths in all: the
    reference compiles a program a length), so prompts of every tail occur.
    Prompt and answer end on a block's end together, so that every committed
    block's tokens reach the answer."""
    rng = np.random.default_rng([seed, 0xB10C])
    low, high = probe["answers"]
    out = []
    for i in range(probe["requests"]):
        if i < len(probe["long_prompts"]):
            n = probe["long_prompts"][i]
            answer = low + -(n + low) % block
        else:
            total = probe["totals"][i % len(probe["totals"])]
            answer = int(rng.integers(low, high + 1))
            n = total - answer
        require(n > 0 and (n + answer) % block == 0, "a probe request ends on a block's end")
        out.append({"ids": [int(t) for t in rng.integers(0, 256, n)], "max_tokens": answer,
                    "denoise_steps": probe["denoise_steps"][i % len(probe["denoise_steps"])]})
    return out


class HandOuts:
    """Stands in ``JaxEngine._take_blocks``' place while the probe runs: keeps,
    a request, every fetched hand-out of the engine's block step that the
    engine then took for it ([B + 2] int32) and how many slots that launch
    ran for a request, then lets the engine take them."""

    def __init__(self, engine):
        self.take = engine._take_blocks
        self.rows = {}  # id(request) -> [(hand-out, live slots)]

    def __call__(self, pool, arr, binding, *rest):
        bound = [slot for slot, req in binding.items() if pool.slots[slot] is req]
        for slot in bound:
            self.rows.setdefault(id(binding[slot]), []).append((np.array(arr[slot]), len(bound)))
        return self.take(pool, arr, binding, *rest)


def forwards_of(ids: list, rows: list, block: int, mask_id: int):
    """A request's denoise forwards, told again from its hand-outs alone: each
    the cached positions it stood behind (``length``), the block as it was
    forwarded (``block``), which positions it unmasked (``took``), the block
    after it (``after``) and the launch's live slots. None where the hand-outs
    do not tell one story."""
    tail = len(ids) % block
    length = len(ids) - tail
    cur = np.full((block,), mask_id, np.int64)
    cur[:tail] = ids[length:]
    out = []
    for row, live in rows:
        after, committed, unmasked = row[:block], row[block], row[block + 1]
        if committed:  # the clean block the forwards left, kept
            if mask_id in cur or not np.array_equal(after, cur):
                return None
            length, cur = length + block, np.full((block,), mask_id, np.int64)
            continue
        clean = cur != mask_id
        took = ~clean & (after != mask_id)
        if took.sum() != unmasked or not np.array_equal(after[clean], cur[clean]):
            return None
        out.append({"length": length, "block": cur, "took": took, "after": after, "live": live})
        cur = after.astype(np.int64)
    return out


def through_engine(engine, requests: list) -> dict:
    """The probe requests through the engine's own loop, all at once; what
    its programs left in its cache for each, the hand-outs of its block
    steps, and its counters' growth."""
    import jax

    from ray_tpu.llm import SamplingParams

    before = engine.get_stats()["counters"]
    handed = HandOuts(engine)
    with mock.patch.object(engine, "_take_blocks", handed):
        reqs = [engine.submit(prompt_token_ids=r["ids"], sampling_params=SamplingParams(
            max_tokens=r["max_tokens"], temperature=0.0, ignore_eos=True,
            denoise_steps=r["denoise_steps"])) for r in requests]
        for req in reqs:
            engine._await_done(req)
            if req.error is not None:
                raise req.error
    after = engine.get_stats()["counters"]
    rows = []
    # one program for every slot: [L, slots, K, T, D] -> the slot's [L, K, T, D]
    stripe = jax.jit(lambda leaf, slot: jax.lax.dynamic_index_in_dim(leaf, slot, 1, keepdims=False))
    for r, req in zip(requests, reqs):
        pool = next(p for p in engine._pools if p.stripe_len == req.pool_stripe)
        total = len(r["ids"]) + len(req.out_tokens)
        # what the request's sequence holds of it, as [L, T, K, D]
        k, v = (np.asarray(stripe(pool.cache[name], req.slot))[:, :, :total].astype(np.float32)
                .transpose(0, 2, 1, 3) for name in ("k", "v"))
        rows.append({"tokens": r["ids"] + [int(t) for t in req.out_tokens], "k": k, "v": v,
                     "answer": len(req.out_tokens), "prompt": len(r["ids"]),
                     "handed": handed.rows.get(id(req), [])})

    def grown(name, label=None):
        a, b = (c[name] if label is None else c[name][label] for c in (after, before))
        return a - b

    return {"rows": rows, "counters": {
        "denoise": grown("block_forwards", "denoise"), "commit": grown("block_forwards", "commit"),
        "unmasked": grown("block_tokens_unmasked"), "emitted": grown("block_tokens_emitted"),
    }}


def judged(ref, params, kv, forwards: list, picks: list, mask_id: int) -> dict:
    """The reference on the engine's choices in the forwards ``picks`` of one
    request (``kv``: the reference's own keys and values of the request's
    sequence): the sums ``x0_logit_gap`` and ``confidence_order_err`` are
    made of."""
    logits = ref.denoise_rows(params, kv, [forwards[i]["length"] for i in picks],
                              [forwards[i]["block"] for i in picks]).astype(np.float64)
    logits[..., mask_id] = -np.inf  # the program sets the mask token's own logit so
    keep = np.ones(logits.shape[-1], bool)
    keep[mask_id] = False
    out = dict(gap=0.0, positions=0, over=0.0, range=0.0, live=0)
    for f, rows in zip((forwards[i] for i in dict.fromkeys(picks)), logits):
        top = rows.max(axis=-1)
        for at in np.flatnonzero(f["took"]):
            out["gap"] += (top[at] - rows[at, f["after"][at]]) / rows[at, keep].std()
            out["positions"] += 1
        sure = -np.log(np.exp(rows - top[:, None]).sum(axis=-1))  # log of the largest probability
        masked = f["block"] == mask_id
        left = masked & ~f["took"]
        if left.any() and f["took"].any():
            out["over"] += max(0.0, sure[left].max() - sure[f["took"]].min())
            out["range"] += sure[masked].max() - sure[masked].min()
        out["live"] += f["live"]
    return out


def block_errors(got: dict, ref, params, requests: list, cfg, seed: int, n_judged: int) -> dict:
    """The program's side against the reference's."""
    B, mask_id = cfg.block_length, cfg.mask_token_id
    rng = np.random.default_rng([seed, 0x5EED])
    sums = {"kv_prefill": np.zeros(2), "kv_commit": np.zeros(2)}
    judge = dict(gap=0.0, positions=0, over=0.0, range=0.0, live=0)
    whole_story, n_forwards = True, 0
    for r, row in zip(requests, got["rows"]):
        keys, values = kv = ref.forward(params, row["tokens"])["kv"]
        whole = row["prompt"] - row["prompt"] % B  # what the chunk programs wrote
        for want, have in ((keys, row["k"]), (values, row["v"])):
            for name, part in (("kv_prefill", slice(0, whole)), ("kv_commit", slice(whole, None))):
                w, h = want[:, part].astype(np.float64), have[:, part].astype(np.float64)
                sums[name] += [((h - w) ** 2).sum(), (w ** 2).sum()]
        forwards = forwards_of(r["ids"], row["handed"], B, mask_id)
        whole_story &= (bool(forwards) and row["answer"] == r["max_tokens"]
                        and mask_id not in row["tokens"])
        if not forwards:
            continue
        # the first (the prompt's tail stands in it) and others by the seed;
        # padded with the last so that every request asks one shape
        others = rng.permutation(np.arange(1, len(forwards)))[:n_judged - 1]
        some = [0] + sorted(int(i) for i in others)
        n_forwards += len(some)
        for k, v in judged(ref, params, kv, forwards, some + some[-1:] * (n_judged - len(some)),
                           mask_id).items():
            judge[k] += v
    want = {k: sum(expected_forwards(len(r["ids"]), r["max_tokens"], r["denoise_steps"], B)[k]
                   for r in requests) for k in ("denoise", "commit", "unmasked")}
    have = got["counters"]
    exact = whole_story and all(have[k] == want[k] for k in want) and have["emitted"] == sum(
        r["max_tokens"] for r in requests)
    return {
        "kv_prefill_rel_rms": float(np.sqrt(sums["kv_prefill"][0] / sums["kv_prefill"][1])),
        "kv_commit_rel_rms": float(np.sqrt(sums["kv_commit"][0] / sums["kv_commit"][1])),
        "x0_logit_gap": float(judge["gap"] / max(1, judge["positions"])),
        "confidence_order_err": float(judge["over"] / judge["range"]) if judge["range"] else 0.0,
        # positions unmasked a denoise forward, against the schedule's; 1 where
        # a count of forwards, commits or tokens itself is off, an answer is
        # not whole or the hand-outs do not tell one story
        "unmasked_per_forward_err": abs(
            have["unmasked"] / max(1, have["denoise"]) - want["unmasked"] / want["denoise"]
        ) if exact else 1.0,
        "block_forwards": {"counted": have, "by_schedule": want},
        "judged": {"forwards": n_forwards, "positions": judge["positions"],
                   "live_slots_mean": judge["live"] / max(1, n_forwards)},
    }


class BlockServer(serving.BenchLLMServer):
    """The replica with the check of a model that generates by blocks."""

    def bench_check_reference(self, seed: int, config: dict, control=None) -> dict:
        import jax

        t = time.perf_counter()
        family = families.load(config)
        probe = config["run"]["probe"]
        cfg = self.engine.model_cfg
        requests = probe_requests(seed, probe, cfg.block_length)
        if control == "int8":
            self.engine.params = family.int8_roundtrip(self.engine.params)
        got = through_engine(self.engine, requests)
        if control == "int8":
            self.bench_load_weights(seed, config)
        ref = family.Reference(config, jax.local_devices()[:1])
        errors = block_errors(got, ref, self.engine.params, requests, cfg, seed,
                              probe["judged_forwards"])
        return dict(errors, seconds=time.perf_counter() - t, memory=self.bench_memory())


def body_of(model: str, req: dict, traffic: dict, i: int) -> dict:
    """Request ``i``'s body: the harness's, its length fixed (``ignore_eos``)
    and its denoising steps dealt out by its number."""
    steps = traffic["denoise_steps"]
    return dict(serving.completion_body(model, req, traffic, traffic["stream"]),
                ignore_eos=True, denoise_steps=steps[i % len(steps)])


def run(ctx: dict) -> dict:
    from ray_tpu import serve

    args, traffic = ctx["args"], ctx["traffic"]
    try:
        # ``serving.build_app`` deploys the class this name holds when it is called
        with mock.patch.object(serving, "BenchLLMServer", BlockServer):
            served = serving.Served(ctx)
        checks = served.prepare()
        requests = gen.Requests(traffic, args.seed, traffic["pool"])
        results, lock = [], threading.Lock()
        stop = threading.Event()
        cursor = iter(range(10**9))

        def client():
            while not stop.is_set():
                with lock:
                    i = next(cursor)
                body = body_of(served.model, requests[i], traffic, i)
                r = serving.http_completion(served.url, body, traffic["request_timeout_s"])
                r["whole"] = r.get("completion_tokens") == body["max_tokens"]
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
        # under ``ignore_eos`` an answer is its ``max_tokens`` long, whatever
        # block it ends in
        whole = all(r["whole"] for r in inside if r["ok"])

        def grown(name, label=None):
            a, b = (c[name] if label is None else c[name][label] for c in (at_close, opened))
            return a - b

        forwards = {kind: grown("block_forwards", kind) for kind in ("denoise", "commit")}
        in_window = {
            "block_forwards": forwards, "tokens_emitted": grown("block_tokens_emitted"),
            "tokens_per_forward": grown("block_tokens_emitted") / max(1, sum(forwards.values())),
            "unmasked_per_denoise_forward": grown("block_tokens_unmasked") / max(1, forwards["denoise"]),
        }
        log(requests=summary, completion_tokens=tokens, window_s=t1 - t0, answers_whole=whole,
            compiles_in_window=closed["compiles_in_window"],
            compiled_in_window=closed["compiled_in_window"], memory=closed["memory"],
            blocks_in_window=in_window, stats_at_end=closed["stats"])
        return dict(
            correct=checks["correct"] and closed["compiles_in_window"] == 0 and whole,
            attempted=summary["attempted"], failed=summary["failed"],
            e2e={"serve_tok_s": tokens / (t1 - t0), "setup_s": t0_wall - ctx["t_start_wall"]},
            device=common.device_entry(served.device_report, common.peak_bytes(served.device_report)),
            spans=served.spans, trace=closed.get("trace"),
            samples=[x for x in closed["samples"] if t0_wall <= x["t"] <= t0_wall + (t1 - t0)],
            extra={"stats_at_end": closed["stats"], "window": [t0_wall, t0_wall + (t1 - t0)],
                   "blocks_in_window": in_window},
        )
    finally:
        serve.shutdown()
