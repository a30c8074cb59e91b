"""The loop that launches carrying chunks (``llm/engine.py
_advance_admissions``): when a first token is taken, the counters of carrying
launches and of those that took the rows and carried none (a pool that
generates by blocks counts its block steps the same way, and hands
``_take_blocks`` what its chunk launches handed out), and the pool that
never carries (a latent pool launches the chunk
alone). Requests admitted beside decoding rows, family by family:
``tests/test_carried_decode_beside.py``. The subject:
``tests/test_carried_decode.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import JaxEngine, SamplingParams
from ray_tpu.llm.engine import programs
from ray_tpu.models.llama import init_kv_cache, prefill
from ray_tpu.models.patterned import moe_stats_names
from tests.engine_helpers import decoding, programs_replaced, tiny_engine as _engine

pytestmark = pytest.mark.timeout(900) if hasattr(pytest.mark, "timeout") else []


def _flat(eng):
    return dict(eng._n)


def _grew(eng, before):
    return {k: v - before[k] for k, v in eng._n.items() if v != before[k]}


class _Token:
    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready


def test_a_first_token_is_taken_when_it_has_arrived_or_before_its_slots_next_step_is_fetched():
    """``_arrived``: in a pool that carries, a first token that has not come
    yet is left for a later pass (nothing is queued behind the launch that
    carried the step), unless a step that decoded its slot is fetched in this
    pass: its tokens follow the first one."""
    class Pool:
        inflight = []

    a, b, c = object(), object(), object()
    arrived, late, decoded = (0, a, _Token(True), None), (1, b, _Token(False), None), (
        2, c, _Token(False), None)
    Pool.inflight = [("older", {0: a}, None), ("step", {0: a, 2: c}, None), ("newest", {1: b}, None)]
    now, later = JaxEngine._arrived(Pool, [arrived, late, decoded], 1)
    assert now == [arrived, decoded] and later == [late]
    now, later = JaxEngine._arrived(Pool, [late, decoded], 3)  # nothing is fetched this pass
    assert now == [] and later == [late, decoded]
    now, later = JaxEngine._arrived(Pool, [late], 0)  # every step is: none may overtake its first token
    assert now == [late] and later == []
    Pool.inflight = [("step", {1: object()}, None)]  # the slot's earlier tenant, not this request
    assert JaxEngine._arrived(Pool, [late], 0) == ([], [late])


def _pass(eng):
    eng._pull_waiting()
    eng._advance_admissions()
    eng._launch_decodes()
    eng._drain()


def test_the_counters_of_carrying_launches():
    """The loop's stages by hand, a pass at a time. One request decodes; a
    second of three chunks is admitted: each of its chunk launches carries
    the pool's decode step, and ``_launch_decodes`` launches none in those
    passes. ``decode_steps_in_chunk`` counts the carrying launches,
    ``decode_steps`` and ``decode_slot_steps`` grow as a decode launch grows
    them, no ``moe_*:decode`` count grows (the rows' routing is among the
    chunk program's), and a pass with no chunk runs ``decode_fn`` again."""
    eng = _engine("routed")
    eng.shutdown()  # the loop thread is gone: the stages are the test's
    pool = eng._pools[0]
    expert_layers, k = eng.model_cfg.n_layers, eng.model_cfg.moe_top_k
    rng = np.random.default_rng(2)
    sp = SamplingParams(max_tokens=30, temperature=0.0, ignore_eos=True)
    decodes = []
    inner = eng._decode
    eng._decode = lambda *a, **kw: decodes.append(1) or inner(*a, **kw)
    a = eng.submit(prompt_token_ids=[int(t) for t in rng.integers(1, 250, 5)], sampling_params=sp)
    _pass(eng)  # its final chunk: nothing decodes yet, nothing to carry
    assert pool.slots[0] is a and eng._n["decode_steps_in_chunk"] == 0
    assert eng._n["decode_steps"] == len(decodes) == 1  # bound by then: its first step
    b = eng.submit(prompt_token_ids=[int(t) for t in rng.integers(1, 250, 40)], sampling_params=sp)
    for n, (kind, live) in enumerate([("chunk_mid", 1), ("chunk_mid", 1), ("chunk_final", 1)]):
        before, a_length = _flat(eng), 5 + len(a.out_tokens)
        _pass(eng)
        grew = _grew(eng, before)
        assert grew["decode_steps_in_chunk"] == grew["decode_steps"] == 1
        assert grew["decode_slot_steps"] == live
        assert grew["decode_kv_tokens_global"] == a_length  # the length the loop holds at the launch
        assert grew["prefill_programs:" + kind[6:]] == 1
        assert len(decodes) == 1, "a decode launch beside a carrying chunk launch"
    assert eng._n["decode_steps_in_chunk"] == 3
    before = _flat(eng)
    _pass(eng)  # no chunk is due: the decode program, both requests' rows
    grew = _grew(eng, before)
    assert len(decodes) == 2 and "decode_steps_in_chunk" not in grew
    assert grew["decode_steps"] == 1 and grew["decode_slot_steps"] == 2
    while not (a.done.is_set() and b.done.is_set()):
        _pass(eng)
    while pool.inflight:
        eng._drain()
    n = eng._n
    # the routing counts a fetch brought, by the program that handed them out:
    # a carried step's rows are rows of its chunk program
    assert n["moe_layer_steps:decode"] == expert_layers * (
        n["decode_steps"] - n["decode_steps_in_chunk"]) == expert_layers * len(decodes)
    assert n["moe_layer_steps:chunk_mid"] == expert_layers * n["prefill_programs:mid"]
    assert n["moe_layer_steps:chunk_final"] == expert_layers * n["prefill_programs:final"]
    # every row a program routes counts, the pool's slots too, live or not: a
    # final chunk's 8 tokens and 4 slots, a middle chunk's 16 and 4, in each
    # expert layer
    assert n["moe_assignments:chunk_final"] == k * expert_layers * (8 + 4) * n["prefill_programs:final"]
    assert n["moe_assignments:chunk_mid"] == k * expert_layers * (16 + 4) * n["prefill_programs:mid"]
    assert n["decode_slot_steps"] == (
        n["tokens_generated"] - n["first_tokens"] + n["tokens_discarded"])
    assert a.error is None and b.error is None and len(a.out_tokens) == len(b.out_tokens) == 30


@pytest.mark.parametrize("family", ["dense", "blocks"])
def test_a_launch_that_takes_rows_and_carries_none_is_counted_by_its_cause(family):
    """Every launch of a chunk program that takes the pool's rows runs them,
    and one that carries no step counts why (``decode_steps_dead_in_chunk``):
    no slot held a request, an earlier launch of the pass had carried the step
    (two final chunks due in one pass: one carries, one runs dead), or the
    run-ahead was full (``_drain`` leaves no more than ``decode_runahead`` steps
    in flight, so the loop's own passes never find it so: here two launches go
    undrained). ``decode_steps_in_chunk`` and the three causes are the
    launches of the carrying forms, one ``engine.counts`` key each. A pool
    that generates by blocks counts its block steps the same way."""
    eng = _engine(family)
    eng.shutdown()  # the loop thread is gone: the stages are the test's
    pool = eng._pools[0]
    took = []  # launches that were handed the pool's rows, by program

    def recording(name, with_rows):
        def make(inner):
            def program(*args, **kw):
                took.extend([name] * (len(args) == with_rows))
                return inner(*args, **kw)
            return program
        return make

    def dead(grew):
        return {k.partition(":")[2]: v for k, v in grew.items()
                if k.startswith("decode_steps_dead_in_chunk")}

    rng = np.random.default_rng(6)
    ids = lambda n: [int(t) for t in rng.integers(1, 250, n)]  # noqa: E731
    sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    with programs_replaced(eng, "chunk_mid", recording("chunk_mid", 7)), \
            programs_replaced(eng, "chunk_final", recording("chunk_final", 11)):
        before = _flat(eng)
        a = eng.submit(prompt_token_ids=ids(5), sampling_params=sp)
        _pass(eng)  # its final chunk: no slot holds a request yet
        grew = _grew(eng, before)
        assert dead(grew) == {"no_slot": 1} and "decode_steps_in_chunk" not in grew
        before = _flat(eng)
        b, c = (eng.submit(prompt_token_ids=ids(n), sampling_params=sp) for n in (6, 7))
        _pass(eng)  # two final chunks due in one pass
        grew = _grew(eng, before)
        assert grew["prefill_programs:final"] == 2 and grew["decode_steps"] == 1
        assert grew["decode_steps_in_chunk"] == 1 and dead(grew) == {"step_carried": 1}
        for _ in range(2):  # launches nobody drains: one step more in flight than the run-ahead
            eng._advance_admissions()
            eng._launch_decodes()
        assert len(pool.inflight) == eng.config.engine.decode_runahead + 1
        before = _flat(eng)
        d = eng.submit(prompt_token_ids=ids(40), sampling_params=sp)
        eng._pull_waiting()
        eng._advance_admissions()  # its first middle chunk, one row
        grew = _grew(eng, before)
        assert grew["prefill_programs:mid"] == 1 and "decode_steps" not in grew
        assert dead(grew) == {"runahead_full": 1}
        while not all(r.done.is_set() for r in (a, b, c, d)):
            _pass(eng)
    n = eng._n
    assert took.count("chunk_final") == n["prefill_programs:final"] == 4
    assert took.count("chunk_mid") == n["prefill_programs:mid"] == 2
    assert n["decode_steps_in_chunk"] + sum(
        v for k, v in n.items() if k.startswith("decode_steps_dead_in_chunk")) == len(took)
    assert all(r.error is None and len(r.out_tokens) == 40 for r in (a, b, c, d))


def test_a_block_pools_chunk_launches_carry_its_block_step():
    """The loop's stages by hand in a pool that generates by blocks. One
    request generates; a second of three chunks is admitted: each of its chunk
    launches carries one forward of every slot's block, ``_launch_decodes``
    launches no ``block_step`` in those passes, and what the launch handed
    out reaches ``_take_blocks`` as a step's own does ([slots, B + 2], the
    binding the launch's). The pool's block state is the chunk program's; a
    pass with no chunk runs ``block_step`` again."""
    eng = _engine("blocks")
    eng.shutdown()  # the loop thread is gone: the stages are the test's
    pool = eng._pools[0]
    B = pool.block_length
    assert pool.carries and eng.get_stats()["pools"][0]["carries"] is True
    rng = np.random.default_rng(4)
    sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True, denoise_steps=2)
    steps, taken = [], []
    inner, take = eng._block_step, eng._take_blocks
    eng._block_step = lambda *a, **kw: steps.append(1) or inner(*a, **kw)

    def taking(pool, arr, binding, *rest):
        taken.append((arr.shape, sorted(binding)))
        return take(pool, arr, binding, *rest)

    eng._take_blocks = taking
    a = eng.submit(prompt_token_ids=[int(t) for t in rng.integers(1, 250, 6)], sampling_params=sp)
    _pass(eng)  # its final chunk: no slot holds a request yet
    assert pool.slots[0] is a and eng._n["decode_steps_in_chunk"] == 0
    assert eng._n["decode_steps"] == len(steps) == 1 and eng._n["decode_steps_dead_in_chunk:no_slot"] == 1
    b = eng.submit(prompt_token_ids=[int(t) for t in rng.integers(1, 250, 43)], sampling_params=sp)
    for kind in ("mid", "mid", "final"):
        before, state = _flat(eng), pool.block
        a_blocks = (6 + len(a.out_tokens)) // B * B + B  # the length the loop holds at the launch
        _pass(eng)
        grew = _grew(eng, before)
        assert grew["decode_steps_in_chunk"] == grew["decode_steps"] == grew["decode_slot_steps"] == 1
        assert grew["decode_kv_tokens_global"] == a_blocks
        assert grew["prefill_programs:" + kind] == 1
        assert len(steps) == 1, "a block step beside a carrying chunk launch"
        assert pool.block is not state and set(pool.block) == set(state)
    assert eng._n["decode_steps_in_chunk"] == 3
    before = _flat(eng)
    _pass(eng)  # no chunk is due: the block step, both requests' rows
    grew = _grew(eng, before)
    assert len(steps) == 2 and "decode_steps_in_chunk" not in grew
    assert grew["decode_steps"] == 1 and grew["decode_slot_steps"] == 2
    while not (a.done.is_set() and b.done.is_set()):
        _pass(eng)
    while pool.inflight:
        eng._drain()
    n = eng._n
    assert all(shape == (pool.n_slots, B + 2) for shape, _ in taken)
    assert len(taken) == n["decode_steps"] and [0] in [slots for _, slots in taken]
    # every forward a request was bound for is a denoise or a commit, whoever launched it
    assert n["block_forwards:denoise"] + n["block_forwards:commit"] <= n["decode_slot_steps"]
    assert n["block_tokens_emitted"] == n["tokens_generated"] == 48
    assert a.error is None and b.error is None and len(a.out_tokens) == len(b.out_tokens) == 24


def _plain_chunk_final(cfg):
    """``chunk_final`` as it was before a chunk's launch could carry a decode
    step: the body a pool that does not carry must still run."""
    from ray_tpu.llm.engine import top_k_static

    K = top_k_static(cfg)

    def chunk_final(params, cache, one, tokens, length, start, slot, temp, top_k, key):
        mid_stats = one.get("moe_stats")
        last_logits, one = prefill(params, one, tokens, cfg, lengths=length, start_pos=start)
        stats = one.pop("moe_stats", None)
        if stats is not None:
            stats = jnp.stack([mid_stats, stats - mid_stats])
        total = start[0] + length[0]
        with jax.named_scope("kv_write"):
            cache = {
                **{k: cache[k].at[:, slot].set(one[k][:, 0]) for k in ("k", "v")},
                "length": cache["length"].at[slot].set(total),
            }
        with jax.named_scope("sampling"):
            logits_row = last_logits[0]
            greedy = jnp.argmax(logits_row, -1)
            vals, idxs = jax.lax.top_k(logits_row, K)
            rank_ok = jnp.arange(K) < top_k
            scaled = jnp.where(rank_ok, vals / jnp.maximum(temp, 1e-6), -jnp.inf)
            key, sub = jax.random.split(key)
            sampled = idxs[jax.random.categorical(sub, scaled)]
            tok = jnp.where(temp <= 0.0, greedy, sampled).astype(jnp.int32)
        return tok, key, cache, one, stats

    return chunk_final


def test_a_latent_pool_launches_the_chunk_alone():
    """The latent pool's launches are what they were: its programs are
    handed the chunk's own arguments and nothing of the decode step (a
    request beside decoding rows, so a carrying pool would carry), its
    compiled ``chunk_final`` is to the letter the plain body's text, and
    ``decode_steps_in_chunk`` stays 0."""
    eng = _engine("latent")
    try:
        pool = eng._pools[0]
        assert not pool.carries and pool.chunk_rows == 1
        seen = []

        def recording(name):
            def make(inner):
                def program(*args, **kw):
                    seen.append((name, len(args), sorted(kw)))
                    return inner(*args, **kw)
                return program
            return make

        rng = np.random.default_rng(5)
        sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
        with programs_replaced(eng, "chunk_mid", recording("chunk_mid")), \
                programs_replaced(eng, "chunk_final", recording("chunk_final")):
            first = decoding(eng, [int(t) for t in rng.integers(1, 250, 6)], sp)
            second = eng.submit(prompt_token_ids=[int(t) for t in rng.integers(1, 250, 40)],
                                sampling_params=sp)
            for req in (first, second):
                eng._await_done(req)
                assert req.error is None
        assert {("chunk_mid", 5, ()), ("chunk_final", 10, ())} == {
            (name, n, tuple(kw)) for name, n, kw in seen}
        assert eng._n["decode_steps_in_chunk"] == 0 and eng._n["decode_steps"] > 0

        cfg = eng.model_cfg
        shapes = lambda tree: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        one = jax.eval_shape(lambda: init_kv_cache(cfg, 1, pool.stripe_len))
        one["moe_stats"] = jax.ShapeDtypeStruct((len(moe_stats_names(cfg)),), jnp.int32)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        args = (shapes(eng.params), shapes(pool.cache), one, i32(1, 16), i32(1), i32(1), i32(),
                jax.ShapeDtypeStruct((), jnp.float32), i32(),
                jax.ShapeDtypeStruct(pool.keys.shape[1:], pool.keys.dtype))
        text = lambda fn: jax.jit(fn, donate_argnums=(1, 2)).lower(*args).as_text()  # noqa: E731
        assert text(programs(cfg)["chunk_final"]) == text(_plain_chunk_final(cfg))
    finally:
        eng.shutdown()
