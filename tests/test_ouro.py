"""ByteDance Ouro-2.6B (``LlamaConfig.ouro_2_6b``; ``ouro-tiny`` at test size):
one stack of layers run ``loop_passes`` times a token with the same weights, a
cache row for every pass and layer, a norm on each branch's way out, the final
norm inside the loop and the exit gate that picks the pass the head reads. The
model is held to the plain reference of family ``looped_dense``
(``benchmark/reference_looped_dense.py``: float32, a sequence at a time, no
cache, nothing of ``ray_tpu``) on the benchmark's seeded weights: the whole
pass, ``prefill`` then ``decode_step`` through the cache under every split of
a prompt into chunks, a padded row, a row that is not live, and the exit rule
below a threshold of 1. The accepted families' lowered programs are pinned in
``tests/test_decode_block_programs.py`` and ``tests/test_carried_decode_scopes.py``."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import (
    LlamaConfig, decode_step, forward, init_kv_cache, init_params, loss_fn, prefill,
)
from ray_tpu.models.patterned import _param_shapes, decode_forward

CFG = LlamaConfig.ouro_tiny()
L, P = CFG.n_layers, CFG.loop_passes
# what benchmark/families/looped_dense.py reads, for the reference
PUBLISHED = {
    "head_dim": 16, "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["full_attention"] * 2, "max_position_embeddings": 128, "model_type": "ouro",
    "num_attention_heads": 4, "num_hidden_layers": 2, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 3, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 320,
}
T = 12
# float32 against float32 under ``highest``: the two sum a row's scores and a
# matmul's products in another order, and the stream passes 3 x 2 layers and 3
# final norms; logits and normed streams are of size 1. Measured 4e-6 at most
# over the tests below. bfloat16 anywhere a float32 is stated (the stream, the
# cache, the gate's sum) reads 1e-2 (``test_bfloat16_is_seen``), a wrong cache
# row, a missing branch norm or a skipped pass 0.5 and more.
TOL = dict(atol=5e-5, rtol=1e-4)


def _family():
    from benchmark.families import looped_dense

    return looped_dense


@pytest.fixture(scope="module")
def model():
    """(the benchmark's seeded params, tokens [4, T], the reference's whole pass a row)."""
    family = _family()
    params = family.make_params(5, PUBLISHED, jnp.float32)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (4, T), 0, CFG.vocab_size))
    ref = family.Reference(PUBLISHED, jax.local_devices()[:1])
    want = ref.forward_rows(params, list(tokens), kv_rows=range(4))
    return params, tokens, want


def _kv_of(cache, row: int, n: int):
    """Row ``row``'s first ``n`` positions of every cache row, as the reference
    hands them out: [passes * layers, n, K, D]."""
    return [np.asarray(cache[name][:, row, :, :n]).transpose(0, 2, 1, 3) for name in ("k", "v")]


_PRE = jax.jit(lambda p, c, t, n, s: prefill(p, c, t, CFG, lengths=n, start_pos=s))
_DEC = jax.jit(lambda p, c, t: decode_step(p, c, t, CFG))


def test_the_family_maps_the_published_keys_onto_the_tiny_preset():
    family = _family()
    assert LlamaConfig.ouro_tiny(**family.model_kwargs(PUBLISHED)) == CFG
    assert {k: s for k, (s, _) in family.param_shapes(PUBLISHED).items()} == _param_shapes(CFG)
    assert family.param_count(PUBLISHED) == CFG.num_params()
    pl = patterned.plan(CFG)
    assert (pl.bodies, pl.period, pl.reps, pl.whole) == (1, 1, L, True)
    # a row a pass and layer
    cache = init_kv_cache(CFG, 3, 32)
    assert cache["k"].shape == cache["v"].shape == (P * L, 3, CFG.n_kv_heads, 32, CFG.head_dim)
    assert family.kv_bytes_per_token(PUBLISHED, 4) == 2 * cache["k"][:, 0, :, 0].nbytes


def test_the_published_preset_has_the_published_sizes():
    cfg = LlamaConfig.ouro_2_6b()
    assert cfg.num_params() == 2_667_974_657 and (cfg.loop_passes, cfg.exit_threshold) == (4, 1.0)
    shapes = patterned.stripe_cache_shapes(cfg, 12, 384)
    assert shapes["k"] == shapes["v"] == (192, 12, 16, 384, 128)
    assert patterned.plan(cfg).bodies == 1


def test_defaults_are_no_operation():
    """A pass count of 1, no branch norm and a threshold of 1 are what every
    other model has: no new leaf, no further cache row."""
    cfg = LlamaConfig.tiny(layer_types=("full",) * 2, heads_per_layer=(4, 4),
                           mlp_types=("dense",) * 2)
    assert (cfg.loop_passes, cfg.branch_norm, cfg.exit_threshold) == (1, False, 1.0)
    assert not {"attn_out_norm", "mlp_out_norm", "exit_w", "exit_b"} & set(_param_shapes(cfg))
    assert patterned.stripe_cache_shapes(cfg, 1, 8)["k"][0] == cfg.n_layers


def test_forward_is_the_references_whole_pass(model):
    params, tokens, want = model
    hidden, passes = jax.jit(
        lambda p, t: patterned.forward_hidden(p, t, CFG, passes=True))(params, jnp.asarray(tokens))
    logits = jax.jit(lambda p, t: forward(p, t, CFG))(params, jnp.asarray(tokens))
    for b in range(tokens.shape[0]):
        np.testing.assert_allclose(passes["hidden"][:, b], want["hidden"][b], **TOL)
        np.testing.assert_allclose(passes["pdf"][:, b], want["pdf"][b], **TOL)
        np.testing.assert_array_equal(passes["exit"][b], want["exit"][b])
        np.testing.assert_allclose(logits[b], want["logits"][b], **TOL)
        # at the published threshold the head reads the last pass
        assert (want["exit"][b] == P - 1).all()
        np.testing.assert_allclose(hidden[b], want["hidden"][b][-1], **TOL)


# every split of a 5-token prompt into chunks, the rest a token at a time
SPLITS = [c for n in range(1, 6) for c in itertools.product(range(1, 6), repeat=n) if sum(c) == 5]


@pytest.mark.parametrize("chunks", SPLITS, ids=lambda c: "+".join(map(str, c)))
def test_prefill_then_decode_through_the_cache(model, chunks):
    """Each pass reads the earlier chunks' rows of its own pass; the logits of
    a chunk's last token and of every step, and all pass-major keys and
    values, are the reference's."""
    params, tokens, want = model
    B = tokens.shape[0]
    cache, at = init_kv_cache(CFG, B, 32), 0
    for n in chunks:
        logits, cache = _PRE(params, cache, jnp.asarray(tokens[:, at:at + n]),
                             jnp.full((B,), n, jnp.int32), jnp.full((B,), at, jnp.int32))
        at += n
    got = [logits]
    for i in range(at, T - 1):
        logits, cache = _DEC(params, cache, jnp.asarray(tokens[:, i]))
        got.append(logits)
    got = np.stack(got, axis=1)
    assert (np.asarray(cache["length"]) == T - 1).all()
    for b in range(B):
        np.testing.assert_allclose(got[b], want["logits"][b][at - 1:T - 1], **TOL)
        for have, ref in zip(_kv_of(cache, b, T - 1), want["kv"][b]):
            np.testing.assert_allclose(have, ref[:, :T - 1], **TOL)


def test_the_decode_kernel_reads_each_passes_own_row(model):
    """A stripe of whole 128-position blocks takes the decode step through
    ``ops/decode_attention.py`` (interpreted here), which is handed the pass's
    row of the same rank-5 leaves."""
    params, tokens, want = model
    B = tokens.shape[0]
    cache = init_kv_cache(CFG, B, 128)
    assert patterned.reads_blocks(128, cache["k"], *jax.tree.leaves(params))
    logits, cache = _PRE(params, cache, jnp.asarray(tokens[:, :7]), jnp.full((B,), 7, jnp.int32),
                         jnp.zeros((B,), jnp.int32))
    for i in range(7, 10):
        logits, cache = _DEC(params, cache, jnp.asarray(tokens[:, i]))
    for b in range(B):
        np.testing.assert_allclose(logits[b], want["logits"][b][9], **TOL)
        for have, ref in zip(_kv_of(cache, b, 10), want["kv"][b]):
            np.testing.assert_allclose(have, ref[:, :10], **TOL)


def test_a_padded_row_beside_a_full_one_writes_nothing_in_any_pass(model):
    params, tokens, want = model
    lens = np.asarray([8, 3, 8, 5], np.int32)
    cache = init_kv_cache(CFG, 4, 32)
    logits, cache = _PRE(params, cache, jnp.asarray(tokens[:, :8]), jnp.asarray(lens),
                         jnp.zeros((4,), jnp.int32))
    np.testing.assert_array_equal(cache["length"], lens)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(logits[b], want["logits"][b][n - 1], **TOL)
        for name, ref in zip(("k", "v"), want["kv"][b]):
            np.testing.assert_allclose(_kv_of(cache, b, n)[name == "v"], ref[:, :n], **TOL)
            assert not np.asarray(cache[name][:, b, :, n:]).any()  # every pass's row


def test_a_row_that_is_not_live_writes_nothing_in_any_pass(model):
    """The rows of a decode step beside a prompt's chunk (``beside``): a live
    row's step is the reference's, a dead one leaves all ``passes * layers``
    rows of its slot and its length as they were."""
    params, tokens, want = model
    pool = init_kv_cache(CFG, 3, 32)
    _, pool = _PRE(params, pool, jnp.asarray(tokens[:3, :6]), jnp.full((3,), 6, jnp.int32),
                   jnp.zeros((3,), jnp.int32))
    before = jax.tree.map(np.asarray, pool)
    live = jnp.asarray([True, False, True])
    stripe = dict(init_kv_cache(CFG, 1, 32), loop_stats=jnp.zeros((2 + P,), jnp.int32))
    first, stripe, rode, pool = jax.jit(lambda p, one, c: prefill(
        p, one, jnp.asarray(tokens[3:, :8]), CFG, lengths=jnp.asarray([5]),
        start_pos=jnp.zeros((1,), jnp.int32), beside=(c, jnp.asarray(tokens[:3, 6]), live),
    ))(params, stripe, pool)
    np.testing.assert_allclose(first[0], want["logits"][3][4], **TOL)
    np.testing.assert_array_equal(pool["length"], [7, 6, 7])
    for name in ("k", "v"):
        np.testing.assert_array_equal(pool[name][:, 1], before[name][:, 1])
    for b in (0, 2):
        np.testing.assert_allclose(rode[b], want["logits"][b][6], **TOL)
        for have, ref in zip(_kv_of(pool, b, 7), want["kv"][b]):
            np.testing.assert_allclose(have, ref[:, :7], **TOL)
    # one forward of ``passes`` passes; the chunk's row and the two live rows read the last
    np.testing.assert_array_equal(stripe["loop_stats"], [1, P] + [0] * (P - 1) + [3])


@pytest.mark.parametrize("threshold", [0.5, 0.8, 1.0])
def test_rows_of_one_launch_pick_their_own_pass(model, threshold):
    """The gate scaled so that its chances spread: below a threshold of 1 the
    rows of a decode step and a final chunk's sampled rows stop at different
    passes, each as the reference's rule says, and the head reads that pass's
    stream; at 1 every row reads the last."""
    params, tokens, _ = model
    # (at a threshold of 1 the seeded gate as it is: scaled, its float32 shares
    # round to 1 before the last pass in some rows, which both sides then pick)
    params = dict(params, exit_w=params["exit_w"] * (6.0 if threshold < 1 else 1.0))
    cfg = dataclasses.replace(CFG, exit_threshold=threshold)
    ref = _family().Reference(dict(PUBLISHED, early_exit_threshold=threshold),
                              jax.local_devices()[:1])
    want = ref.forward_rows(params, list(tokens))
    lens = np.asarray([9, 4, 7, 6], np.int32)
    cache = dict(init_kv_cache(cfg, 4, 32), loop_stats=jnp.zeros((2 + P,), jnp.int32))
    logits, cache = jax.jit(lambda p, c: prefill(
        p, c, jnp.asarray(tokens[:, :9]), cfg, lengths=jnp.asarray(lens)))(params, cache)
    exits = np.asarray([want["exit"][b][n - 1] for b, n in enumerate(lens)])
    for b, n in enumerate(lens):
        np.testing.assert_allclose(logits[b], want["logits"][b][n - 1], **TOL)
    np.testing.assert_array_equal(cache["loop_stats"], [1, P, *np.bincount(exits, minlength=P)])
    # a decode step of all four rows, each at its own length
    step, cache = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))(
        params, cache, jnp.asarray(tokens[np.arange(4), lens]))
    step_exits = np.asarray([want["exit"][b][n] for b, n in enumerate(lens)])
    for b, n in enumerate(lens):
        np.testing.assert_allclose(step[b], want["logits"][b][n], **TOL)
    np.testing.assert_array_equal(
        cache["loop_stats"], [2, 2 * P, *np.bincount(np.r_[exits, step_exits], minlength=P)])
    both = np.r_[exits, step_exits]
    assert len(set(both)) > 1 if threshold < 1 else (both == P - 1).all()
    # the whole-sequence pass picks the same
    _, passes = jax.jit(lambda p, t: patterned.forward_hidden(p, t, cfg, passes=True))(
        params, jnp.asarray(tokens))
    np.testing.assert_array_equal(passes["exit"], np.stack(want["exit"]))


def test_bfloat16_is_seen(model):
    """The tolerance is tight enough that bfloat16 where float32 is stated fails."""
    params, tokens, want = model
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    logits = np.asarray(forward(low, jnp.asarray(tokens), cfg), np.float32)
    err = np.abs(logits - np.stack(want["logits"])).max()
    assert err > 100 * TOL["atol"]
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(logits, np.stack(want["logits"]), **TOL)


def test_the_programs_own_initialisation_starts_the_gate_unbiased():
    params = jax.jit(lambda k: init_params(k, CFG))(jax.random.PRNGKey(0))
    assert not np.asarray(params["exit_b"]).any() and params["exit_w"].shape == (CFG.d_model,)
    assert all((np.asarray(params[n]) == 1).all() for n in ("attn_out_norm", "mlp_out_norm"))


def test_what_a_looped_stack_does_not_mix_with_is_refused():
    with pytest.raises(ValueError, match="need layer_types"):
        LlamaConfig.tiny(loop_passes=2)
    for kw in (dict(mlp_types=("sparse",) * 2, moe_experts=4), dict(block_length=4),
               dict(loop_passes=0), dict(exit_threshold=1.5)):
        with pytest.raises(ValueError, match="loop_passes"):
            patterned.plan(LlamaConfig.ouro_tiny(**kw))


def test_training_refuses_the_looped_stack_by_name(model):
    params, tokens, _ = model
    with pytest.raises(NotImplementedError, match="loop_passes=3.*served, not trained"):
        loss_fn(params, {"tokens": jnp.asarray(tokens)}, CFG)


def test_all_positions_of_a_launch_can_be_asked_for(model):
    """``decode_forward`` without ``logits_at``: every position's logits, each
    from its own pass's stream (here the last), as ``forward``'s."""
    params, tokens, want = model
    cache = init_kv_cache(CFG, 2, 32)
    positions = jnp.broadcast_to(jnp.arange(6)[None], (2, 6))
    logits, cache = decode_forward(params, cache, jnp.asarray(tokens[:2, :6]), positions, CFG,
                                   start_pos=jnp.zeros((2,), jnp.int32))
    for b in range(2):
        np.testing.assert_allclose(logits[b], want["logits"][b][:6], **TOL)
