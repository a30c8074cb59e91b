"""Generation by diffusion over blocks (``LlamaConfig.block_length``; JetLM
SDAR's): the program against the plain reference on seeded weights, the
unmasking schedule, the engine's loop over blocks, and the paths that refuse
the model by name."""

import contextlib
import copy
import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import block_moe as family
from benchmark.kinds import block_closed_loop as kind
from benchmark.kinds.block_closed_loop import expected_forwards
from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig, block_schedule
from tests.engine_helpers import decoding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal configuration's model, its seeded weights and reference."""
    with open(os.path.join(ROOT, "benchmark/configs/rehearse-block-moe-serve.json")) as f:
        config = json.load(f)
    cfg = LlamaConfig.sdar_tiny(**family.model_kwargs(config), max_seq_len=128)
    params = family.make_params(3, config, jnp.float32)
    return config, cfg, params, family.Reference(config, jax.local_devices()[:1])


@functools.lru_cache(maxsize=None)
def _jitted(cfg, with_logits):
    return jax.jit(lambda p, c, t, m, n, co: llama.block_step(
        p, c, t, m, n, co, cfg, with_logits=with_logits))


def _step(cfg, params, cache, block, masked, n, commit, with_logits=False):
    rows = block.shape[0]
    return _jitted(cfg, with_logits)(
        params, cache, jnp.asarray(block), jnp.asarray(masked), jnp.full((rows,), n),
        jnp.asarray(commit))


def test_forward_under_the_block_mask_matches_the_reference(tiny):
    _, cfg, params, ref = tiny
    tokens = np.random.default_rng(0).integers(0, 256, 22)  # a cut last block too
    want = ref.forward(params, tokens, logits_at=np.arange(22))["logits"]
    assert _rel(llama.forward(params, jnp.asarray(tokens)[None], cfg)[0], want) < 1e-5


def test_the_reference_reads_several_blocks_of_a_sequence_as_it_reads_one(tiny):
    """``denoise_rows`` (each block over its prefix out of the sequence's own
    keys and values) against ``denoise_logits`` (the whole forward again)."""
    _, cfg, params, ref = tiny
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 256, 24)
    blocks = np.where(rng.random((2, 4)) < 0.5, cfg.mask_token_id, rng.integers(0, 256, (2, 4)))
    got = ref.denoise_rows(params, ref.forward(params, tokens)["kv"], [8, 20], blocks)
    for have, length, block in zip(got, (8, 20), blocks):
        assert _rel(have, ref.denoise_logits(params, tokens[:length], block)) < 1e-5


@pytest.mark.parametrize("stripe", [pytest.param(128, id="kernel"), pytest.param(64, id="einsum")])
def test_prefill_then_block_steps_match_the_reference(tiny, stripe):
    """Two rows through ``prefill`` (4 blocks) and then two blocks each of
    ``block_step``, two denoise forwards a block: the first row's logits
    against the reference's for the same prefix and block, the tokens it
    unmasks (float32: the same), and what the commits left in the cache
    against the reference's keys and values, both rows'; a row that does not
    commit keeps its length."""
    _, cfg, params, ref = tiny
    rng = np.random.default_rng(stripe)
    seqs = rng.integers(0, 256, (2, 16))
    cache = llama.init_kv_cache(cfg, 2, stripe)
    _, cache = llama.prefill(params, cache, jnp.asarray(seqs), cfg, with_logits=False)
    seqs = [list(s) for s in seqs]
    for _ in range(2):
        block = np.full((2, 4), cfg.mask_token_id)
        block[0, 0] = 7  # a clean token in front, as a prompt's tail is
        masked = block == cfg.mask_token_id
        for step in range(2):
            want = ref.denoise_logits(params, seqs[0], block[0])
            want[:, cfg.mask_token_id] = -np.inf
            new, still, logits, cache = _step(
                cfg, params, cache, block, masked, 2, [False, False], with_logits=True)
            finite = np.isfinite(want)
            assert _rel(np.asarray(logits)[0][finite], want[finite]) < 1e-5
            assert (np.asarray(logits)[0][~finite] == -np.inf).all()
            # the masked positions of largest confidence take their greedy tokens
            conf = np.where(masked[0], jax.nn.softmax(want, axis=-1).max(-1), -1.0)
            taken = np.argsort(-conf, kind="stable")[:min(2, int(masked[0].sum()))]
            assert sorted(np.flatnonzero(masked[0] & ~np.asarray(still)[0])) == sorted(taken)
            assert [int(new[0, at]) for at in taken] == [int(want[at].argmax()) for at in taken]
            block, masked = np.asarray(new), np.asarray(still)
        assert not masked.any() and list(np.asarray(cache["length"])) == [len(seqs[0])] * 2
        _, _, _, cache = _step(cfg, params, cache, block, masked, 0, [True, False])
        assert list(np.asarray(cache["length"])) == [len(seqs[0]) + 4, len(seqs[1])]
        _, _, _, cache = _step(cfg, params, cache, block, masked, 0, [False, True])
        for b in range(2):
            seqs[b] += [int(t) for t in block[b]]
    for b in range(2):
        keys, values = ref.forward(params, seqs[b])["kv"]
        assert _rel(np.asarray(cache["k"])[:, b, :, :24].transpose(0, 2, 1, 3), keys) < 1e-5
        assert _rel(np.asarray(cache["v"])[:, b, :, :24].transpose(0, 2, 1, 3), values) < 1e-5


@pytest.mark.parametrize("steps,want", [(1, [4]), (2, [2, 2]), (3, [2, 1, 1]), (4, [1, 1, 1, 1])])
def test_the_schedule_unmasks_the_block_over_its_steps(steps, want):
    assert [int(block_schedule(j, steps, 4)) for j in range(steps)] == want
    assert list(np.asarray(block_schedule(jnp.arange(steps), jnp.full((steps,), steps), 4))) == want


def test_ties_go_to_the_lower_position_and_the_threshold_unmasks_beyond_the_schedule(tiny):
    """Zeroed weights make every position's confidence the same: the schedule
    takes the lowest masked positions. On a model whose head is scaled until
    its largest probability is near one, every masked position passes the
    model's threshold of 0.9 in one forward; under a model whose threshold is
    1 only the schedule's."""
    _, cfg, params, _ = tiny
    cache = llama.init_kv_cache(cfg, 1, 64)
    block = np.array([[7] + [cfg.mask_token_id] * 3])
    masked = block == cfg.mask_token_id
    flat = dict(params, unembed=jnp.zeros_like(params["unembed"]))
    _, still, _, _ = _step(cfg, flat, cache, block, masked, 2, [False])
    assert list(np.asarray(still)[0]) == [False, False, False, True]
    sharp = dict(params, unembed=params["unembed"] * 200.0)
    assert cfg.confidence_threshold == 0.9
    _, still, _, _ = _step(cfg, sharp, cache, block, masked, 1, [False])
    assert not np.asarray(still).any()
    never = dataclasses.replace(cfg, confidence_threshold=1.0)
    new, still, _, _ = _step(never, sharp, cache, block, masked, 1, [False])
    assert int(np.asarray(still).sum()) == 2 and cfg.mask_token_id not in list(
        np.asarray(new)[0][~np.asarray(still)[0]])


# ------------------------------------- the block step beside a prompt's chunk


@pytest.fixture(scope="module")
def pool(tiny):
    """(the engine's program bodies, a pool's cache of four slots behind
    prompts of 8, 16, 20 and 12 tokens, its block state: a block half
    unmasked at step 1 of 2, a clean one that commits, one of masks at step 0
    of 4, a tenant's leftovers; the sampler's arrays, a prompt of 24)."""
    from ray_tpu.llm.engine import programs

    _, cfg, params, _ = tiny
    fns = programs(cfg)
    rng = np.random.default_rng(17)
    lens = np.asarray([8, 16, 20, 12], np.int32)
    _, cache = llama.prefill(params, llama.init_kv_cache(cfg, 4, 64),
                             jnp.asarray(rng.integers(0, 256, (4, 20))), cfg,
                             lengths=jnp.asarray(lens), with_logits=False)
    mask = cfg.mask_token_id
    block = dict(
        tokens=jnp.asarray([[5, mask, 9, mask], [1, 2, 3, 4], [mask] * 4, [7, mask, mask, mask]],
                           jnp.int32),
        masked=jnp.asarray([[False, True, False, True], [False] * 4, [True] * 4,
                            [False, True, True, True]]),
        step=jnp.asarray([1, 2, 0, 1], jnp.int32), steps=jnp.asarray([2, 2, 4, 3], jnp.int32))
    rows = dict(block=block, temps=jnp.asarray([0.0, 0.9, 0.7, 0.0], jnp.float32),
                top_ks=jnp.asarray([1, 8, 4, 1], jnp.int32),
                keys=jax.random.split(jax.random.PRNGKey(9), 4))
    return fns, cache, rows, rng.integers(0, 256, 24).astype(np.int32)


def _chunk(prompt, at, width=8):
    piece = prompt[at:at + width]
    return (jnp.asarray(piece[None]), jnp.asarray([len(piece)], jnp.int32),
            jnp.asarray([at], jnp.int32))


def _assert_the_live_rows_stepped_and_the_others_stayed(got, want, was, live, slots=range(4)):
    """``got``, ``want``, ``was``: (hand-outs or None, cache, block state,
    keys) of the carrying launch, of ``block_step`` alone, and as they went
    in. A live row's are the step's; a row that is not live keeps its block
    state, its key and its length bit for bit, and its block's keys and values
    lie behind a length that did not move."""
    for slot in slots:
        side = want if live[slot] else was
        if live[slot]:
            np.testing.assert_array_equal(got[0][slot], want[0][slot])
        for name in was[2]:
            np.testing.assert_array_equal(got[2][name][slot], side[2][name][slot])
        np.testing.assert_array_equal(got[3][slot], side[3][slot])
        assert int(got[1]["length"][slot]) == int(side[1]["length"][slot])
        for name in ("k", "v"):  # every forward writes its block: the step's bytes either way
            np.testing.assert_allclose(got[1][name][:, slot], want[1][name][:, slot],
                                       atol=2e-5, rtol=2e-5)
            held = int(was[1]["length"][slot])
            np.testing.assert_array_equal(np.asarray(got[1][name])[:, slot, :, :held],
                                          np.asarray(was[1][name])[:, slot, :, :held])


@pytest.mark.parametrize("live", [(True, True, False, True), (False,) * 4, (True,) * 4],
                         ids=["one-row-dead", "none-live", "all-live"])
def test_a_carrying_middle_chunk_is_the_chunk_and_the_block_step_of_the_live_rows(
        tiny, pool, live):
    """``chunk_mid`` with the pool's cache, block state and sampler arrays
    against ``chunk_mid`` and ``block_step`` apart: the stripe is the chunk's,
    each live row's hand-out, next block state, key, length and block of keys
    and values are the step's (a commit, a denoise forward under a schedule of
    2 and one of 3, greedy and drawn), and a row that is not live leaves its
    state, key and ``cache["length"]`` as they were."""
    _, cfg, params, _ = tiny
    fns, cache, rows, prompt = pool
    one = fns["new_stripe"](64)
    want_one, = fns["chunk_mid"](params, (dict(one),), *_chunk(prompt, 0))
    out, want_cache, want_block, want_keys, _ = fns["block_step"](
        params, dict(cache), rows["block"], rows["temps"], rows["top_ks"], rows["keys"])
    (got_one,), handed, got_cache, got_keys, got_block = fns["chunk_mid"](
        params, (dict(one),), *_chunk(prompt, 0), dict(cache),
        dict(rows, live=jnp.asarray(live)))
    assert handed.shape == out.shape == (4, cfg.block_length + 2)
    assert list(np.asarray(out)[:, cfg.block_length]) == [0, 1, 0, 0]  # the clean block commits
    for name in ("k", "v", "length"):
        np.testing.assert_allclose(got_one[name], want_one[name], atol=2e-5, rtol=2e-5)
    _assert_the_live_rows_stepped_and_the_others_stayed(
        (handed, got_cache, got_block, got_keys), (out, want_cache, want_block, want_keys),
        (None, cache, rows["block"], rows["keys"]), live)
    np.testing.assert_array_equal(  # the routing counts are of all the launch's rows
        got_one["moe_stats"][1], want_one["moe_stats"][1] + 4 * cfg.block_length * cfg.moe_top_k * (
            int(want_one["moe_stats"][0])))


def test_a_carrying_final_chunk_activates_a_slot_that_is_no_live_row_of_its_launch(tiny, pool):
    """``chunk_final`` with the pool's rows into slot 2: the slot holds the
    prompt's stripe and length and the request's key, its block state waits
    for ``seed_block`` as it was, and the other rows' forward is the step's."""
    _, cfg, params, _ = tiny
    fns, cache, rows, prompt = pool
    one, = fns["chunk_mid"](params, (fns["new_stripe"](64),), *_chunk(prompt, 0, 16))
    final = (*_chunk(prompt, 16), jnp.int32(2), jnp.float32(0.8), jnp.int32(5),
             jax.random.PRNGKey(77))
    live = (True, True, False, True)
    out, step_cache, want_block, want_keys, _ = fns["block_step"](
        params, dict(cache), rows["block"], rows["temps"], rows["top_ks"], rows["keys"])
    _, _, want_cache, _, _ = fns["chunk_final"](params, dict(step_cache), dict(one), *final)
    tok, key, got_cache, _, stats, handed, got_keys, got_block = fns["chunk_final"](
        params, dict(cache), dict(one), *final, dict(rows, live=jnp.asarray(live)))
    assert int(tok) == 0 and stats.shape[0] == 2  # the middle chunks' counts, the launch's
    np.testing.assert_array_equal(key, jax.random.PRNGKey(77))
    np.testing.assert_array_equal(got_keys[2], jax.random.PRNGKey(77))
    assert list(np.asarray(got_cache["length"])) == [8, 20, 24, 12]
    for name in ("k", "v"):
        np.testing.assert_allclose(got_cache[name][:, 2], want_cache[name][:, 2],
                                   atol=2e-5, rtol=2e-5)
    for name in rows["block"]:
        np.testing.assert_array_equal(got_block[name][2], rows["block"][name][2])
    _assert_the_live_rows_stepped_and_the_others_stayed(
        (handed, got_cache, got_block, got_keys), (out, step_cache, want_block, want_keys),
        (None, cache, rows["block"], rows["keys"]), live, slots=(0, 1, 3))


# ------------------------------------------------------------------ the engine


@pytest.fixture(scope="module")
def engine():
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="sdar-tiny", seed=1),
        engine=EngineConfig(max_num_seqs=4, max_seq_len=64, prefill_chunk=16,
                            prefill_buckets=(8, 16), max_concurrent_admissions=2,
                            dtype="float32")))
    yield eng
    eng.shutdown()


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(1000 * seed + n).integers(0, 256, n)]


def _counters(eng):
    c = eng.get_stats()["counters"]
    return {**{k: v for k, v in c.items() if not isinstance(v, dict)},
            **{f"{k}:{label}": v for k, d in c.items() if isinstance(d, dict)
               for label, v in d.items()}}


def _grown(eng, before):
    return {k: v - before[k] for k, v in _counters(eng).items() if v != before[k]}


def _forwards(prompt: int, answer: int, steps: int) -> tuple:
    """(denoise forwards, commits, positions unmasked) of one request, from
    the schedule alone (the benchmark's own count of them)."""
    want = expected_forwards(prompt, answer, steps, 4)
    return want["denoise"], want["commit"], want["unmasked"]


@pytest.mark.parametrize("prompt,answer,steps", [
    (13, 1, 4), (13, 4, 4), (16, 5, 2), (21, 7, 3), (3, 5, 2), (40, 4, 1),
], ids=["one-token", "four", "five", "seven", "shorter-than-a-block", "multiple-of-4-one-step"])
def test_an_answer_is_exactly_max_tokens_and_costs_steps_plus_one_forwards_a_block(
        engine, prompt, answer, steps):
    before = _counters(engine)
    out = engine.generate(prompt_token_ids=_prompt(prompt), sampling_params=SamplingParams(
        max_tokens=answer, ignore_eos=True, denoise_steps=steps))
    assert len(out.token_ids) == answer and out.finish_reason == "length"
    assert engine.model_cfg.mask_token_id not in out.token_ids
    grown = _grown(engine, before)
    denoise, commits, unmasked = _forwards(prompt, answer, steps)
    assert grown["block_forwards:denoise"] == denoise
    assert grown["block_forwards:commit"] == grown["blocks_committed"] == commits
    assert grown["block_tokens_unmasked"] == unmasked
    assert grown["block_tokens_emitted"] == grown["tokens_generated"] == answer
    assert grown.get("block_prompt_tail_tokens", 0) == prompt % 4
    assert grown.get("tokens_discarded", 0) >= 4 * commits - prompt % 4 - answer
    assert grown["prompt_tokens"] == prompt
    # a prompt's whole blocks are prefilled, and nothing is sampled from them
    assert grown.get("prefill_query_tokens:chunk_final", 0) + grown.get(
        "prefill_query_tokens:chunk_mid", 0) == prompt - prompt % 4 - grown.get(
            "prompt_tokens_from_prefix", 0)
    assert "first_tokens" not in grown


def test_a_stop_token_inside_a_block_ends_the_request_there(engine):
    sp = dict(max_tokens=8, ignore_eos=True, denoise_steps=2)
    whole = engine.generate(prompt_token_ids=_prompt(12), sampling_params=SamplingParams(**sp))
    stop = whole.token_ids[5]  # the second block's second token
    first = whole.token_ids.index(stop)
    cut = engine.generate(prompt_token_ids=_prompt(12), sampling_params=SamplingParams(
        **sp, stop_token_ids=[stop]))
    assert cut.token_ids == whole.token_ids[:first] and cut.finish_reason == "stop"


def test_requests_of_different_steps_share_a_launch_and_a_seed_repeats(engine):
    """Four requests at once, two at 4 steps and two at 2, drawn at a
    temperature under seeds: each gets what it gets alone (a slot's key and
    schedule are its own), in fewer launches than one after another."""
    cases = [(14, 9, 4, 11), (9, 6, 2, 12), (20, 8, 4, 13), (5, 7, 2, 14)]

    def sp(answer, steps, seed):
        return SamplingParams(max_tokens=answer, ignore_eos=True, denoise_steps=steps,
                              temperature=0.8, seed=seed)

    alone = []
    before = _counters(engine)
    for prompt, answer, steps, seed in cases:
        alone.append(engine.generate(
            prompt_token_ids=_prompt(prompt), sampling_params=sp(answer, steps, seed)).token_ids)
    steps_alone = _grown(engine, before)["decode_steps"]
    before = _counters(engine)
    reqs = [engine.submit(prompt_token_ids=_prompt(prompt), sampling_params=sp(answer, steps, seed))
            for prompt, answer, steps, seed in cases]
    for req in reqs:
        engine._await_done(req)
    grown = _grown(engine, before)
    assert [list(r.out_tokens) for r in reqs] == alone
    assert grown["decode_steps"] < steps_alone
    assert grown["decode_slot_steps"] > grown["decode_steps"]
    want = [_forwards(prompt, answer, steps) for prompt, answer, steps, _ in cases]
    assert grown["block_forwards:denoise"] == sum(w[0] for w in want)
    assert grown["block_tokens_unmasked"] == sum(w[2] for w in want)
    # run-ahead: forwards launched for requests that had ended count nowhere,
    # and a block they committed is discarded whole
    assert grown["decode_slot_steps"] >= sum(w[0] + w[1] for w in want)


@contextlib.contextmanager
def _steps_launched_alone(eng):
    """While open, ``eng``'s pools do not carry: every chunk launch is the
    chunk's alone and every block step a ``jit_block_step`` (the forms that
    take no rows are compiled at their first launch; the carrying ones come
    back at the end). The loop holds no request on the way in or out."""
    deadline = time.monotonic() + 30  # (a step launched ahead of the last request's end
    while any(p.inflight or p.first_pending for p in eng._pools) and time.monotonic() < deadline:
        time.sleep(0.001)  # is fetched after the request returned: one run in seven met it here)
    assert not any(p.admitting or p.inflight or any(p.slots) for p in eng._pools)
    chunks = {form: program for form, program in eng._programs.items()
              if form[0].startswith("chunk_")}
    for form in chunks:
        del eng._programs[form]
    for p in eng._pools:
        p.carries = False
    try:
        yield
    finally:
        while any(p.admitting or p.inflight or p.first_pending for p in eng._pools):
            time.sleep(0.001)  # the loop thread drains what ran ahead
        for p in eng._pools:
            p.carries = True
        eng._programs.update(chunks)


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "seeded"])
def test_chunk_launches_that_carry_give_the_tokens_of_steps_launched_alone(engine, temperature):
    """A request generates a long answer while three more are admitted, their
    prompts of one, two and three chunks and of every tail, at 4 and at 2
    denoising steps in one launch: the chunk launches carry the pool's block
    step, and each request gets, token for token, what it gets from the same
    engine with every step launched alone; the forwards, the commits and the
    positions unmasked are the schedule's either way."""
    cases = [(9, 24, 4, 31), (34, 12, 2, 32), (47, 9, 4, 33), (16, 10, 2, 34)]

    def serve():
        before = _counters(engine)
        sps = [SamplingParams(max_tokens=answer, ignore_eos=True, denoise_steps=steps,
                              temperature=temperature, seed=seed)
               for _, answer, steps, seed in cases]
        first = decoding(engine, _prompt(cases[0][0], 7), sps[0])
        rest = [engine.submit(prompt_token_ids=_prompt(n, 7), sampling_params=sp)
                for (n, *_), sp in zip(cases[1:], sps[1:])]
        for req in (first, *rest):
            engine._await_done(req)
            assert req.error is None
        return [list(r.out_tokens) for r in (first, *rest)], _grown(engine, before)

    carried, grown = serve()
    assert 0 < grown["decode_steps_in_chunk"] < grown["decode_steps"]
    want = [_forwards(prompt, answer, steps) for prompt, answer, steps, _ in cases]
    assert grown["block_forwards:denoise"] == sum(w[0] for w in want)
    assert grown["block_forwards:commit"] == sum(w[1] for w in want)
    assert grown["block_tokens_unmasked"] == sum(w[2] for w in want)
    assert grown["block_tokens_emitted"] == sum(answer for _, answer, _, _ in cases)
    with _steps_launched_alone(engine):
        alone, grown_alone = serve()
    assert "decode_steps_in_chunk" not in grown_alone
    assert not any(k.startswith("decode_steps_dead_in_chunk") for k in grown_alone)
    assert carried == alone and [len(t) for t in alone] == [answer for _, answer, _, _ in cases]
    for name in ("block_forwards:denoise", "block_forwards:commit", "block_tokens_unmasked",
                 "block_tokens_emitted"):
        assert grown[name] == grown_alone[name]


def test_streamed_and_unary_agree_and_a_fetch_brings_several_tokens(engine):
    sp = SamplingParams(max_tokens=10, ignore_eos=True, denoise_steps=2)
    unary = engine.generate(prompt_token_ids=_prompt(18), sampling_params=sp)
    streamed = [inc["token_id"] for inc in engine.generate_stream(
        prompt_token_ids=_prompt(18), sampling_params=sp)]
    assert streamed == unary.token_ids and len(streamed) == 10


def test_the_stripes_end_ends_a_request_on_a_whole_block(engine):
    """A stripe of 64 holds sixteen blocks: a prompt of 50 has room for 14
    tokens (its tail of 2 rides in the first block), whatever it asks for."""
    out = engine.generate(prompt_token_ids=_prompt(50), sampling_params=SamplingParams(
        max_tokens=40, ignore_eos=True))
    assert len(out.token_ids) == 14 and out.finish_reason == "length"


def test_the_openai_body_carries_the_denoising_steps():
    from ray_tpu.llm.server import sampling_from_body

    assert sampling_from_body({"max_tokens": 5, "denoise_steps": 2}).denoise_steps == 2
    assert sampling_from_body({}).denoise_steps is None


@pytest.mark.parametrize("module", ["llm/spmd.py", "llm/gang.py", "llm/disagg.py",
                                    "tensor_parallel_degree"])
def test_the_paths_without_a_block_step_refuse_the_model_by_name(module):
    cfg = LLMConfig(model=ModelConfig(model_id="sdar-tiny"),
                    engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32"))
    match = module.replace(".", r"\.") + ".*generates by blocks"
    if module == "llm/spmd.py":
        from ray_tpu.llm.spmd import SPMDGenerator

        build = lambda: SPMDGenerator(cfg)  # noqa: E731
    elif module == "llm/gang.py":
        from ray_tpu.llm.gang import GangLLMServer

        build = lambda: GangLLMServer(cfg, num_workers=2)  # noqa: E731
    elif module == "llm/disagg.py":
        from ray_tpu.llm.disagg import DecodeWorker, PrefillWorker

        with pytest.raises(NotImplementedError, match=match):
            DecodeWorker(cfg)
        build = lambda: PrefillWorker(cfg)  # noqa: E731
    else:
        cfg.engine.tensor_parallel_degree = 2
        build = lambda: JaxEngine(cfg)  # noqa: E731
        match = r"llm/engine\.py over a mesh.*generates by blocks"
    with pytest.raises(NotImplementedError, match=match):
        build()


@pytest.fixture(scope="module")
def probed(engine, tiny):
    """The benchmark's probe through the engine's own loop: a prompt of three
    chunks with a tail, one of whole blocks, all four slots bound."""
    probe = {"requests": 4, "long_prompts": [37], "totals": [24, 32], "answers": [8, 12],
             "denoise_steps": [4, 2], "judged_forwards": 4}
    requests = kind.probe_requests(5, probe, 4)
    assert [len(r["ids"]) % 4 for r in requests] == [1, 0, 0, 3]
    before = _counters(engine)
    got = kind.through_engine(engine, requests)
    # the hand-outs the check judges came out of chunk launches too: the pool
    # carries, and the long prompt's chunks ran beside the others' blocks
    assert engine._pools[0].carries and _grown(engine, before)["decode_steps_in_chunk"] > 0
    # (its rows are a block wide: their write stays the scatter)
    assert engine.get_stats()["pools"][0]["decode_write"] == "scatter"
    return requests, got


def _first_taken(handed, mask_id):
    """The position a request's first hand-out unmasked where the prompt
    left no tail, and the position its second did."""
    first, second = handed[0][0][:4], handed[1][0][:4]
    a = int(np.flatnonzero(first != mask_id)[0])
    return a, int(np.flatnonzero((second != mask_id) & (first == mask_id))[0])


@pytest.mark.parametrize("how,fails", [
    ("sound", ()), ("another-token", ("x0_logit_gap",)),
    ("another-position", ("confidence_order_err",)), ("no-commit", ("unmasked_per_forward_err",)),
])
def test_the_benchmark_check_judges_what_the_engines_block_step_handed_out(
        engine, tiny, probed, how, fails):
    """The hand-outs of the engine's own ``jit_block_step`` pass the
    reference's judgement; a token the reference would not have written, a
    position unmasked out of its turn and a commit that never came each fail
    the number that is there for it."""
    requests, got = probed
    got, mask_id = copy.deepcopy(got), engine.model_cfg.mask_token_id
    handed = got["rows"][2]["handed"]  # a prompt of 12, 4 steps: a position a forward
    a, b = _first_taken(handed, mask_id)
    if how == "another-token":  # where the first forward wrote, in every hand-out of the block
        for row, _ in handed[:5]:
            row[a] = (row[a] + 1) % 256
    elif how == "another-position":  # the first forward takes what the second took
        first, second = handed[0][0], handed[1][0]
        first[a], first[b] = mask_id, second[b]
    elif how == "no-commit":
        del handed[4]
    errors = kind.block_errors(got, tiny[3], engine.params, requests, engine.model_cfg, 5, 4)
    # (a request whose hand-outs tell no story is not judged)
    assert errors["judged"]["forwards"] == (12 if how == "no-commit" else 16)
    assert errors["judged"]["live_slots_mean"] > 2
    # (a token or a position that is not the engine's own changes what the
    # forwards behind it are judged on, so the other number may move too)
    sound = ["kv_prefill_rel_rms", "kv_commit_rel_rms"] + ([] if fails else [
        "x0_logit_gap", "confidence_order_err", "unmasked_per_forward_err"])
    assert all(errors[name] > 1e-3 for name in fails), errors
    assert all(errors[name] < 1e-3 for name in sound), errors


@pytest.mark.parametrize("sizes", [
    pytest.param(dict(prefill_chunk=30), id="chunk"),
    pytest.param(dict(prefill_buckets=(16, 34)), id="bucket"),
    pytest.param(dict(decode_steps=2), id="decode_steps"),
])
def test_sizes_that_would_cut_a_block_are_refused_at_construction(sizes):
    """A chunk or a stored prefix that ended inside a block would read keys
    nobody wrote; more than a step a launch is not a forward of a block."""
    with pytest.raises(ValueError, match="blocks"):
        JaxEngine(LLMConfig(
            model=ModelConfig(model_id="sdar-tiny"),
            engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32", **sizes)))


def test_a_model_that_generates_a_token_a_step_is_untouched():
    """``block_length`` 0 is every other model: no block state, the decode
    program and a final chunk that samples."""
    from ray_tpu.llm.engine import programs

    fns = programs(LlamaConfig.laguna_tiny())
    assert "block_step" not in fns and "seed_block" not in fns
    with pytest.raises(ValueError, match="block_length"):
        LlamaConfig.tiny(block_length=4)
    with pytest.raises(ValueError, match="full attention layers alone"):
        llama._param_shapes(LlamaConfig.laguna_tiny(qk_norm=True))
