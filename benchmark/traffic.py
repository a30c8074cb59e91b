"""The one traffic generator. A mix is a data file of parameters
(``benchmark/traffic/<name>.json``); this module turns it and ``--seed`` into
requests and arrival times.

Every seed gets the same set of sizes and the same set of gaps between
arrivals, in another order: lengths are the quantile midpoints of the file's
distribution (not a random sample of it), and the seed permutes them and
draws the prompt bytes. So two seeds differ in order and content, never in
the amount of work."""

from __future__ import annotations

import math
import random
import statistics

PRINTABLE = bytes(range(0x20, 0x7F)).decode()


def stratified(dist: dict, n: int) -> list[int]:
    """``n`` whole numbers at the quantile midpoints of the distribution,
    clipped to its ``min`` and ``max``."""
    if dist["dist"] == "constant":
        return [int(dist["value"])] * n
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    normal = statistics.NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        v = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(min(max(round(v), dist["min"]), dist["max"])))
    return out


def exponential_gaps(rate_per_s: float, n: int) -> list[float]:
    """``n`` gaps at the quantile midpoints of the exponential distribution:
    the gaps of a Poisson process of that rate, as a fixed set."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate_per_s for i in range(n)]


def prompt_text(rng: random.Random, tokens: int) -> str:
    """Random printable bytes; the byte tokenizer adds BOS, so ``tokens`` - 1
    bytes make ``tokens`` tokens. No two prompts share a prefix of any
    length worth caching."""
    return "".join(rng.choices(PRINTABLE, k=max(1, tokens - 1)))


class Requests:
    """An endless sequence of requests over a fixed set of ``n`` sizes.

    The ``n`` prompt lengths and the ``n`` answer budgets are the quantile
    midpoints of the file's distributions, paired and ordered by the seed.
    Request ``i`` has the sizes of entry ``i % n`` and prompt bytes of its
    own, so that a second pass over the set shares no prefix with the first.
    A closed loop takes ``n`` from the file's ``pool`` (small, so that a
    window holds many whole passes, each of the same work); an open loop takes
    the number of requests its window holds."""

    def __init__(self, traffic: dict, seed: int, n: int):
        rng = random.Random(seed)
        prompts = stratified(traffic["prompt_tokens"], n)
        answers = stratified(traffic["max_tokens"], n)
        rng.shuffle(prompts)
        rng.shuffle(answers)
        self.sizes = list(zip(prompts, answers))
        self.seed = seed

    def __getitem__(self, i: int) -> dict:
        prompt_tokens, max_tokens = self.sizes[i % len(self.sizes)]
        rng = random.Random(self.seed * 1_000_003 + i)
        return {"prompt": prompt_text(rng, prompt_tokens), "prompt_tokens": prompt_tokens,
                "max_tokens": max_tokens}


def warmup_requests(traffic: dict, seed: int) -> list[dict]:
    """One request for every prefill program the length range touches (the
    file lists the prompt lengths), before the window."""
    rng = random.Random(seed ^ 0x5A5A5A5A)
    return [
        {"prompt": prompt_text(rng, p), "prompt_tokens": p,
         "max_tokens": traffic["warmup_max_tokens"]}
        for p in traffic["warmup_prompt_tokens"]
    ]


def window_counts(traffic: dict, seconds: float) -> tuple[int, int]:
    """Requests due inside an open-loop window of ``seconds``, and in the ramp
    before it: the same for every seed."""
    rate = traffic["rate_per_s"]
    return max(1, round(rate * seconds)), round(rate * traffic.get("ramp_seconds", 0))


def arrivals(traffic: dict, seed: int, seconds: float) -> tuple[list[float], int]:
    """Open loop: due times relative to the start of the window, the ramp's
    negative. ``rate_per_s * seconds`` requests are due inside the window,
    whatever the seed. Returns (due times, number that belong to the ramp)."""
    rate = traffic["rate_per_s"]
    n_window, n_ramp = window_counts(traffic, seconds)
    rng = random.Random(seed ^ 0x0A771CA1)
    gaps = exponential_gaps(rate, n_window)
    rng.shuffle(gaps)
    # the set of gaps sums to a little under n / rate; stretch it to the window
    scale = seconds / sum(gaps)
    due, t = [], 0.0
    for g in gaps:
        due.append(t)
        t += g * scale
    ramp_gaps = exponential_gaps(rate, n_ramp) if n_ramp else []
    rng.shuffle(ramp_gaps)
    ramp, t = [], 0.0
    for g in ramp_gaps:
        t -= g
        ramp.append(t)
    return sorted(ramp) + due, n_ramp
