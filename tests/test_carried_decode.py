"""A prompt chunk's launch carries the pool's decode step (``llm/engine.py
programs``: ``chunk_mid`` and ``chunk_final`` with the pool's ``rows``;
``models/patterned.py decode_forward`` with ``beside``): the rows that decode
ride through the same read of the weights as the chunk's tokens. The carrying
programs against the two programs they replace, the loop that launches them
against the loop that does not, the counters, the pool that never carries and
what an engine compiles before it takes requests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from ray_tpu.llm.engine import programs
from ray_tpu.models.llama import LlamaConfig, init_kv_cache, init_params, prefill
from ray_tpu.models.patterned import STATE_LEAVES, moe_stats_names, plan
from tests.test_chunk_rows import _Compiles

pytestmark = pytest.mark.timeout(900) if hasattr(pytest.mark, "timeout") else []

CONFIGS = {
    "dense": LlamaConfig.tiny,
    "routed-window": LlamaConfig.laguna_tiny,
    "state-space": LlamaConfig.nemotron_tiny,
}
SLOTS, STRIPE, CHUNK = 4, 64, 8
TOL = dict(atol=2e-5, rtol=2e-5)


@pytest.fixture(scope="module", params=list(CONFIGS))
def pool(request):
    """(cfg, the programs' bodies, params, a pool's cache of four slots of
    which three hold prompts of 5, 17 and 23 tokens and the fourth a
    tenant's leftovers, the pool's decode inputs, a prompt of 21 tokens)."""
    cfg = CONFIGS[request.param]()
    fns = programs(cfg)
    params = init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(41)
    lens = np.asarray([5, 17, 23, 11], np.int32)
    tokens = rng.integers(1, 250, (SLOTS, 24)).astype(np.int32)
    _, cache = prefill(params, init_kv_cache(cfg, SLOTS, STRIPE), jnp.asarray(tokens), cfg,
                       lengths=jnp.asarray(lens))
    rows = dict(
        tokens=jnp.asarray(rng.integers(1, 250, SLOTS).astype(np.int32)),
        temps=jnp.asarray([0.0, 0.9, 0.0, 0.7], jnp.float32),
        top_ks=jnp.asarray([1, 8, 1, 4], jnp.int32),
        keys=jax.random.split(jax.random.PRNGKey(9), SLOTS),
    )
    prompt = rng.integers(1, 250, 21).astype(np.int32)
    return cfg, fns, params, cache, rows, prompt


def _chunk(prompt, at, width=CHUNK):
    piece = prompt[at:at + width]
    toks = np.zeros((1, width), np.int32)
    toks[0, :len(piece)] = piece
    return (jnp.asarray(toks), jnp.asarray([len(piece)], jnp.int32), jnp.asarray([at], jnp.int32))


def _decode(fns, params, cache, rows):
    return fns["decode_fn"](params, dict(cache), rows["tokens"], rows["temps"], rows["top_ks"],
                            rows["keys"])


def _assert_slots_equal(got, want, before, rows_live, written_at, slots=range(SLOTS)):
    """A pool's cache after a carried step against the decode program's:
    equal within float32 rounding where a live row wrote (its position
    ``written_at[b]``), to the bit everywhere else, and a row that is not
    live as it was ``before``."""
    for name in ("k", "v"):
        g, w, b = (np.asarray(c[name]) for c in (got, want, before))
        for slot in slots:
            if not rows_live[slot]:
                np.testing.assert_array_equal(g[:, slot], b[:, slot])
                continue
            at = written_at[slot]
            np.testing.assert_allclose(g[:, slot, :, at], w[:, slot, :, at], **TOL)
            rest = np.arange(STRIPE) != at
            np.testing.assert_array_equal(g[:, slot][:, :, rest], w[:, slot][:, :, rest])
            np.testing.assert_array_equal(g[:, slot][:, :, rest], b[:, slot][:, :, rest])
    for name in STATE_LEAVES:
        if name in want:
            g, w, b = (np.asarray(c[name]) for c in (got, want, before))
            for slot in slots:
                if rows_live[slot]:
                    np.testing.assert_allclose(g[:, slot], w[:, slot], **TOL)
                else:
                    np.testing.assert_array_equal(g[:, slot], b[:, slot])


@pytest.mark.parametrize("n_stripes", [1, 2])
def test_a_carrying_middle_chunk_is_the_chunk_then_the_decode_step(pool, n_stripes):
    """``chunk_mid`` with the pool's rows against ``chunk_mid`` and then
    ``decode_fn``: the stripes, the next tokens (greedy and sampled rows),
    the keys, the pool's cache and state, the routing counts of all the
    launch's rows under the chunk's stripe. One row is not live: it comes
    out as it went in."""
    cfg, fns, params, cache, rows, prompt = pool
    ones = tuple(fns["new_stripe"](STRIPE) for _ in range(n_stripes))
    ones = fns["chunk_mid"](params, ones, *(jnp.concatenate([a] * n_stripes) for a in _chunk(prompt, 0)))
    args = tuple(jnp.concatenate([a] * n_stripes) for a in _chunk(prompt, CHUNK))
    live = np.asarray([True, True, False, True])
    want_ones = fns["chunk_mid"](params, ones, *args)
    want_tokens, want_cache, want_keys, want_stats = _decode(fns, params, cache, rows)
    got_ones, got_tokens, got_cache, got_keys = fns["chunk_mid"](
        params, ones, *args, dict(cache), dict(rows, live=jnp.asarray(live)))
    for got, want in zip(got_ones, want_ones):
        assert set(got) == set(want)
        for name in want:
            if name != "moe_stats":
                np.testing.assert_allclose(got[name], want[name], **TOL)
    length = np.asarray(cache["length"])
    np.testing.assert_array_equal(got_cache["length"], length + live)
    _assert_slots_equal(got_cache, want_cache, cache, live, length)
    np.testing.assert_array_equal(
        got_tokens, np.where(live, np.asarray(want_tokens), np.asarray(rows["tokens"])))
    np.testing.assert_array_equal(
        got_keys, np.where(live[:, None], np.asarray(want_keys), np.asarray(rows["keys"])))
    if cfg.moe_experts:
        names = moe_stats_names(cfg)
        grew = dict(zip(names, np.asarray(got_ones[0]["moe_stats"] - ones[0]["moe_stats"])))
        alone = dict(zip(names, np.asarray(want_ones[0]["moe_stats"] - ones[0]["moe_stats"])))
        step = dict(zip(names, np.asarray(want_stats)))
        assert grew["layer_steps"] == alone["layer_steps"] == step["layer_steps"]
        if cfg.moe_experts_held:  # a block of sorted rows a layer run, carried rows or not
            assert grew["passes"] == grew["layer_steps"] and alone["passes"] == alone["layer_steps"]
        # every routed row counts, a row that is not live and a padded token
        # too; the last layer's feed-forward, where it is traced on its own,
        # is the decode rows' alone (nothing reads the chunk's rows behind it)
        pl = plan(cfg)
        own = pl.kinds[-1][2] == "sparse" and (pl.reps == 0 or pl.tail_from < cfg.n_layers)
        assert own  # both rehearsal patterns end in an expert layer of its own
        assert grew["assignments"] == alone["assignments"] + step["assignments"] - (
            n_stripes * CHUNK * cfg.moe_top_k)
        assert grew["experts_touched"] <= alone["experts_touched"] + step["experts_touched"]


def test_a_carrying_final_chunk_is_the_decode_step_and_the_chunk(pool):
    """``chunk_final`` with the pool's rows against ``decode_fn`` and then
    ``chunk_final`` into a slot that holds no request: the first token and its
    key, the slot's stripe, state and length, the other rows' next tokens,
    keys, keys and values. The slot the chunk activates is no decode row of
    its launch: its next token is the first token, its key the request's."""
    cfg, fns, params, cache, rows, prompt = pool
    one, = fns["chunk_mid"](params, (fns["new_stripe"](STRIPE),), *_chunk(prompt, 0))
    one, = fns["chunk_mid"](params, (one,), *_chunk(prompt, CHUNK))
    final = (*_chunk(prompt, 2 * CHUNK), jnp.int32(3), jnp.float32(0.8), jnp.int32(5),
             jax.random.PRNGKey(77))
    live = np.asarray([True, True, True, False])
    step_tokens, step_cache, step_keys, _ = _decode(fns, params, cache, rows)
    want_tok, want_key, want_cache, _, want_stats = fns["chunk_final"](
        params, dict(step_cache), dict(one), *final)
    got_tok, got_key, got_cache, _, got_stats, got_tokens, got_keys = fns["chunk_final"](
        params, dict(cache), dict(one), *final, dict(rows, live=jnp.asarray(live)))
    assert int(got_tok) == int(want_tok)
    np.testing.assert_array_equal(got_key, want_key)
    length = np.asarray(cache["length"])
    np.testing.assert_array_equal(got_cache["length"], [*(length[:3] + 1), len(prompt)])
    np.testing.assert_array_equal(got_cache["length"], want_cache["length"])
    for name in ("k", "v", *(n for n in STATE_LEAVES if n in want_cache)):
        np.testing.assert_allclose(got_cache[name][:, 3], want_cache[name][:, 3], **TOL)
    _assert_slots_equal(got_cache, step_cache, cache, live, length, slots=range(3))
    np.testing.assert_array_equal(got_tokens, [*np.asarray(step_tokens)[:3], int(want_tok)])
    np.testing.assert_array_equal(got_keys[:3], step_keys[:3])
    np.testing.assert_array_equal(got_keys[3], want_key)
    if cfg.moe_experts:
        assert got_stats.shape == want_stats.shape == (2, len(moe_stats_names(cfg)))
        np.testing.assert_array_equal(got_stats[0], want_stats[0])  # the middle chunks'
        assert int(got_stats[1, 1]) == int(want_stats[1, 1]) + SLOTS * cfg.moe_top_k * (
            int(want_stats[1, 0]))


def test_a_launch_that_carries_none_leaves_the_pool_as_it_was(pool):
    """No live row: the pool's cache, state, lengths, tokens and keys come
    out to the bit as they went in, and the stripe is the chunk's alone."""
    cfg, fns, params, cache, rows, prompt = pool
    one = fns["new_stripe"](STRIPE)
    want, = fns["chunk_mid"](params, (one,), *_chunk(prompt, 0))
    (got,), tokens, after, keys = fns["chunk_mid"](
        params, (one,), *_chunk(prompt, 0), dict(cache),
        dict(rows, live=jnp.zeros((SLOTS,), bool)))
    for name in cache:
        np.testing.assert_array_equal(after[name], cache[name])
    np.testing.assert_array_equal(tokens, rows["tokens"])
    np.testing.assert_array_equal(keys, rows["keys"])
    for name in ("k", "v", "length", *(n for n in STATE_LEAVES if n in want)):
        np.testing.assert_allclose(got[name], want[name], **TOL)


# ------------------------------------------------------------------ the loop


# model, and whether a pool of it carries: layers alike under one loop do (the
# dense model, and the same with routed experts in every layer); a stack of
# several traced bodies and a latent pool do not (``JaxEngine.__init__``)
FAMILIES = {
    "dense": (dict(model_id="tiny"), True),
    "routed": (dict(model_id="tiny", model_kwargs=dict(moe_experts=4, moe_top_k=2)), True),
    "routed-window": (dict(model_id="laguna-tiny"), False),
    "state-space": (dict(model_id="nemotron-tiny"), False),
    "latent": (dict(model_id="kanana-tiny"), False),
}
CARRYING = [name for name, (_, carries) in FAMILIES.items() if carries]


def _engine(family, **engine_kw):
    kw = dict(max_num_seqs=4, max_seq_len=128, prefill_chunk=16, prefill_buckets=(8, 16, 32),
              max_concurrent_admissions=4, enable_prefix_caching=False, dtype="float32")
    kw.update(engine_kw)
    return JaxEngine(LLMConfig(model=ModelConfig(seed=3, **FAMILIES[family][0]),
                               engine=EngineConfig(**kw)))


def _flat(eng):
    return dict(eng._n)


def _grew(eng, before):
    return {k: v - before[k] for k, v in eng._n.items() if v != before[k]}


@pytest.mark.parametrize("family, runahead", [(name, 1) for name in CARRYING] + [("dense", 0)])
def test_requests_admitted_beside_decoding_rows_get_the_tokens_they_get_alone(family, runahead):
    """A request decodes a long answer while three more are admitted, their
    prompts of one to four chunks: the chunk launches carry the first one's
    (then the others') decode steps. Each request, greedy or seeded, gets at
    float32 the tokens it gets when the engine serves it alone, where no
    launch carries anything; also with no run-ahead, where a step that decoded
    a slot is fetched in the pass after the chunk that gave it its first
    token."""
    eng = _engine(family, decode_runahead=runahead)
    try:
        assert all(pool.carries for pool in eng._pools)
        rng = np.random.default_rng(23)
        prompts = [[int(t) for t in rng.integers(1, 250, n)] for n in (7, 52, 21, 40)]
        sampling = [
            SamplingParams(max_tokens=60, temperature=0.0, ignore_eos=True),
            SamplingParams(max_tokens=9, temperature=0.9, seed=4, ignore_eos=True),
            SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True),
            SamplingParams(max_tokens=7, temperature=1.1, top_k=6, seed=8, ignore_eos=True),
        ]
        before = _flat(eng)
        alone = [eng.generate(prompt_token_ids=ids, sampling_params=sp).token_ids
                 for ids, sp in zip(prompts, sampling)]
        assert "decode_steps_in_chunk" not in _grew(eng, before)
        before = _flat(eng)
        first = eng.submit(prompt_token_ids=prompts[0], sampling_params=sampling[0])
        while len(first.out_tokens) < 2:  # it decodes
            assert not first.done.wait(0.001)
        rest = [eng.submit(prompt_token_ids=ids, sampling_params=sp)
                for ids, sp in zip(prompts[1:], sampling[1:])]
        for req in (first, *rest):
            eng._await_done(req)
            assert req.error is None
        assert [req.out_tokens for req in (first, *rest)] == alone
        grew = _grew(eng, before)
        assert 0 < grew["decode_steps_in_chunk"] <= grew["decode_steps"]
    finally:
        eng.shutdown()


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
        for name in ("_chunk_mid_jit", "_chunk_final_jit"):
            def recording(*args, inner=getattr(eng, name), name=name, **kw):
                seen.append((name, len(args), sorted(kw)))
                return inner(*args, **kw)
            setattr(eng, name, recording)
        rng = np.random.default_rng(5)
        sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
        first = eng.submit(prompt_token_ids=[int(t) for t in rng.integers(1, 250, 6)],
                           sampling_params=sp)
        while len(first.out_tokens) < 2:
            assert not first.done.wait(0.001)
        second = eng.submit(prompt_token_ids=[int(t) for t in rng.integers(1, 250, 40)],
                            sampling_params=sp)
        for req in (first, second):
            eng._await_done(req)
            assert req.error is None
        assert {("_chunk_mid_jit", 5, ()), ("_chunk_final_jit", 10, ())} == {
            (name, n, tuple(kw)) for name, n, kw in seen}
        assert eng._n["decode_steps_in_chunk"] == 0 and eng._n["decode_steps"] > 0
        del eng._chunk_mid_jit, eng._chunk_final_jit

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


@pytest.mark.parametrize("family", list(FAMILIES))
def test_an_engine_warms_one_form_of_each_program_and_a_burst_compiles_none(family):
    """A pool compiles one form of each chunk program, the one its launches
    run: a middle chunk a row count, a final chunk a width, one decode
    program (the engine before this one compiled the first final chunk
    twice, once for the pool's cache as it was made). Requests admitted
    beside decoding rows then reach no program that was not run."""
    with _Compiles() as warm:
        eng = _engine(family, prefill_buckets=(8, 16, 32, 64))
    try:
        pool = eng._pools[0]
        mid, finals = eng._chunk_widths(pool)
        mine = {name: warm.names.count(f"jit({name})")
                for name in ("chunk_mid", "chunk_final", "decode_fn")}
        assert mine == {"chunk_mid": pool.chunk_rows if mid else 0,
                        "chunk_final": len(finals), "decode_fn": 1}
        rng = np.random.default_rng(3)
        sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
        with _Compiles() as burst:
            first = eng.submit(prompt_token_ids=[int(t) for t in rng.integers(1, 250, 5)],
                               sampling_params=sp)
            while len(first.out_tokens) < 2:
                assert not first.done.wait(0.001)
            reqs = [first] + [
                eng.submit(prompt_token_ids=[int(t) for t in rng.integers(1, 250, n)],
                           sampling_params=SamplingParams(max_tokens=4, ignore_eos=True,
                                                          temperature=t, seed=s))
                for n, t, s in ((3, 0.0, None), (20, 0.9, None), (40, 0.7, 3), (70, 0.0, None))]
            for req in reqs:
                eng._await_done(req)
                assert req.error is None
        assert burst.names == []
        assert pool.carries == FAMILIES[family][1]
        assert (eng._n["decode_steps_in_chunk"] > 0) == pool.carries
    finally:
        eng.shutdown()
