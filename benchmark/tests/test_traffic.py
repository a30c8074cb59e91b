import os

import pytest

from benchmark import common, traffic as gen

TRAFFIC_DIR = os.path.join(common.BENCH_DIR, "traffic")
SERVING = [
    f[:-5] for f in sorted(os.listdir(TRAFFIC_DIR))
    if f.endswith(".json") and not f.endswith(".sweep.json")
    and common.load_json(os.path.join(TRAFFIC_DIR, f))["kind"] in ("closed_loop", "open_loop")
]


def pool(t, seed, n=64, count=None):
    requests = gen.Requests(t, seed, n)
    return [requests[i] for i in range(count or n)]


@pytest.mark.parametrize("name", SERVING)
def test_same_seed_same_requests(name):
    t = common.load_traffic(name)
    assert pool(t, 2**31 + 5) == pool(t, 2**31 + 5)
    assert gen.warmup_requests(t, 7) == gen.warmup_requests(t, 7)
    assert pool(t, 1) != pool(t, 2)


@pytest.mark.parametrize("name", SERVING)
def test_every_seed_gets_the_same_sizes_in_another_order(name):
    t = common.load_traffic(name)
    a, b = pool(t, 1), pool(t, 2)
    for key in ("prompt_tokens", "max_tokens"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
        assert [r[key] for r in a] != [r[key] for r in b]
        lo, hi = t[key]["min"], t[key]["max"]
        assert all(lo <= r[key] <= hi for r in a)
    # the byte tokenizer adds BOS: bytes + 1 tokens
    assert all(len(r["prompt"].encode()) + 1 == max(2, r["prompt_tokens"]) for r in a)


@pytest.mark.parametrize("name", SERVING)
def test_prompts_share_no_cacheable_prefix(name):
    # three passes over the set of sizes: the same sizes again, other bytes
    requests = pool(common.load_traffic(name), 3, n=64, count=192)
    heads = [r["prompt"][:31] for r in requests]
    assert len(set(heads)) == len(heads)
    assert [r["max_tokens"] for r in requests[:64]] == [r["max_tokens"] for r in requests[64:128]]


def test_lengths_fit_the_stripe():
    config = common.load_json(
        os.path.join(common.BENCH_DIR, "configs", "mistral-7b-v0.3-serve-l16.json")
    )
    stripe = config["run"]["engine"]["max_seq_len"]
    for name in ("chat-closed-64", "chat-open-steady"):
        t = common.load_traffic(name)
        assert t["prompt_tokens"]["max"] + t["max_tokens"]["max"] + 1 <= stripe
        assert max(t["warmup_prompt_tokens"]) + t["warmup_max_tokens"] + 1 <= stripe


def test_stratified_median_and_clip():
    d = {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32, "max": 768}
    xs = gen.stratified(d, 64)
    assert xs == sorted(xs) and xs[0] == 32 and xs[-1] == 768
    assert abs(xs[32] - 192) <= 4


def test_arrivals_fixed_count_and_order_only():
    t = common.load_traffic("chat-open-steady")
    due_a, ramp_a = gen.arrivals(t, 1, 30.0)
    due_b, ramp_b = gen.arrivals(t, 2, 30.0)
    assert gen.arrivals(t, 1, 30.0) == (due_a, ramp_a)
    assert ramp_a == ramp_b == round(t["rate_per_s"] * t["ramp_seconds"])
    assert len(due_a) - ramp_a == round(t["rate_per_s"] * 30.0) == len(due_b) - ramp_b
    assert due_a == sorted(due_a) and due_a[ramp_a] == 0.0 and due_a[-1] < 30.0
    assert all(x < 0 for x in due_a[:ramp_a])
    gaps = lambda due, n: sorted(round(b - a, 9) for a, b in zip(due[n:], due[n + 1:]))  # noqa: E731
    # the same set of gaps (all but the one the window's end cuts), reordered
    assert len(set(gaps(due_a, ramp_a)) ^ set(gaps(due_b, ramp_b))) <= 2
    assert due_a != due_b


def test_train_batch_from_seed():
    from benchmark.kinds import train_steps

    t = common.load_traffic("pretrain-4x2048")
    a = train_steps.make_batch(2**31 + 9, t, 32768)
    assert a.shape == (4, 2049) and a.min() >= 0 and a.max() < 32768
    assert (a == train_steps.make_batch(2**31 + 9, t, 32768)).all()
    assert (a != train_steps.make_batch(5, t, 32768)).any()
