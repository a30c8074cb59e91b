"""Model-layer tests: forward/loss/sharded-train-step/decode consistency.

Correctness harness style per SURVEY §7 ("compare against full-attention on
small shapes") — everything runs on the virtual 8-device CPU mesh from
conftest.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    LlamaConfig,
    init_params,
    forward,
    loss_fn,
    init_kv_cache,
    prefill,
    decode_step,
)
from ray_tpu.models.training import make_train_step
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

CFG = LlamaConfig.tiny()


def test_forward_shapes():
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jnp.ones((2, 16), jnp.int32)
    logits = forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert jnp.isfinite(logits).all()


def test_loss_decreases_under_training():
    mesh = build_mesh(MeshSpec(dp=2, tp=4))
    init_fn, step_fn = make_train_step(CFG, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, CFG.vocab_size, (8, 33)))}
    losses = []
    for _ in range(5):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_sharded_forward_matches_unsharded():
    params = init_params(jax.random.PRNGKey(1), CFG)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, CFG.vocab_size, (4, 16))
    )
    ref = forward(params, tokens, CFG)
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    out = jax.jit(lambda p, t: forward(p, t, CFG, mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-4, atol=2e-4)


def test_ring_attention_model_matches_full():
    cfg = LlamaConfig.tiny(attention="ring")
    params = init_params(jax.random.PRNGKey(2), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 32))
    )
    ref = forward(params, tokens, cfg)  # no mesh -> full attention
    mesh = build_mesh(MeshSpec(sp=4, tp=2))
    out = jax.jit(lambda p, t: forward(p, t, cfg, mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3)


# prefill and decode against ``forward``, and a padded prefill against an
# unpadded one: cases of the one body's tests in ``tests/test_patterned_padded.py``


def test_gqa_heads():
    cfg = LlamaConfig.tiny(n_heads=4, n_kv_heads=1)
    params = init_params(jax.random.PRNGKey(4), cfg)
    logits = forward(params, jnp.ones((1, 8), jnp.int32), cfg)
    assert jnp.isfinite(logits).all()


def test_param_count_formula():
    params = init_params(jax.random.PRNGKey(0), CFG)
    actual = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    assert actual == CFG.num_params()


def test_moe_model_ep_mesh_matches_dense_path():
    """MoE FLAGSHIP variant: ep=2 sharded routing equals the single-device
    dense-path evaluation of the same params."""
    mesh = build_mesh(MeshSpec(dp=4, ep=2))
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, CFG.vocab_size, (4, 16)), jnp.int32
    )

    def both(**kw):
        cfg = LlamaConfig.tiny(n_layers=2, moe_experts=4, moe_top_k=2,
                               moe_capacity_factor=8.0, **kw)
        params = init_params(jax.random.PRNGKey(1), cfg)
        dense = loss_fn(params, {"tokens": tokens}, cfg)
        sharded = jax.jit(
            lambda p, b: loss_fn(p, b, cfg, mesh)
        )(params, {"tokens": tokens})
        return float(dense), float(sharded)

    # sharded dispatch splits capacity per token-shard; with a generous
    # capacity factor no tokens drop on either path, and the task loss is
    # the same number
    np.testing.assert_allclose(*both(moe_aux_weight=0.0), rtol=1e-5)
    # the load-balancing term is a statistic of each token shard on the
    # sharded path and of the whole batch on the dense one: the totals
    # differ by that much and no more
    np.testing.assert_allclose(*both(), rtol=5e-3)


def test_moe_train_step_learns():
    cfg = LlamaConfig.tiny(n_layers=2, moe_experts=4, moe_top_k=2)
    mesh = build_mesh(MeshSpec(dp=4, ep=2))
    init_fn, step_fn = make_train_step(cfg, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 33)), jnp.int32
    )
    state, m1 = step_fn(state, {"tokens": tokens})
    for _ in range(4):
        state, m2 = step_fn(state, {"tokens": tokens})
    assert np.isfinite(float(m1["loss"]))
    assert float(m2["loss"]) < float(m1["loss"])


def test_attention_init_scale_keeps_gradients_from_exploding_with_depth():
    """Each attention projection is initialised from the contraction it takes
    part in (d_model, or heads*head_dim for wo), not from shape[-2] of its
    [e, h, hd] layout. With the latter q/k/v were 11-20x too large at real
    widths, the softmax saturated, and on the chip the gradient norm grew
    ~140x every two layers until nothing trained."""
    cfg = LlamaConfig.tiny(
        d_model=256, n_heads=8, n_kv_heads=2, d_ff=512, n_layers=2,
        dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    want = {
        "wq": cfg.d_model**-0.5, "wk": cfg.d_model**-0.5,
        "wv": cfg.d_model**-0.5, "wo": (cfg.n_heads * cfg.head_dim) ** -0.5,
    }
    for name, std in want.items():
        np.testing.assert_allclose(float(params[name].std()), std, rtol=0.05)

    def grad_norm(n_layers):
        c = LlamaConfig.tiny(
            d_model=256, n_heads=8, n_kv_heads=2, d_ff=512,
            n_layers=n_layers, dtype=jnp.float32,
        )
        p = init_params(jax.random.PRNGKey(0), c)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, c.vocab_size, (2, 65))
        )
        g = jax.grad(loss_fn)(p, {"tokens": tokens}, c)
        return float(
            jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        )

    shallow, deep = grad_norm(2), grad_norm(8)
    assert deep < 10 * shallow, (shallow, deep)


def test_optimizer_moments_take_their_parameters_sharding():
    """The moments are zeros that do not depend on the parameters: unless
    init_fn says where they go, the compiler puts every one of them whole
    on the first device (seen on four chips: 5.4 GB on chip 0, 0.6 GB on
    the others, until the first step spread them)."""
    mesh = build_mesh(MeshSpec(fsdp=4), devices=jax.devices()[:4])
    init_fn, _ = make_train_step(CFG, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    adam = state.opt_state[1][0]
    sharded = 0
    for name, p in state.params.items():
        assert adam.mu[name].sharding == p.sharding, name
        assert adam.nu[name].sharding == p.sharding, name
        sharded += len({s.device for s in adam.mu[name].addressable_shards}) == 4
    assert sharded >= 8


# -- the prompt chunk's cache write: contiguous blocks against the scatter ----

# the chunk under test: the cache's stripe, the chunk's padded width T, each
# row's (start, length); `decode`: greedy decode steps run after it
KV_WRITE_CASES = {
    "first_chunk_at_0": dict(stripe=128, T=32, rows=[(0, 32)]),
    "middle_chunk_at_256": dict(
        stripe=1024, T=256, rows=[(256, 256)], with_logits=False
    ),
    "final_chunk_padded_then_decode": dict(
        stripe=128, T=64, rows=[(32, 44)], decode=4
    ),
    "prefix_hit_start_10": dict(stripe=128, T=32, rows=[(10, 32)]),
    "bucket_passes_stripe_end": dict(
        stripe=128, T=64, rows=[(100, 27)], decode=1
    ),
    "valid_rows_past_stripe_end_dropped": dict(
        stripe=128, T=64, rows=[(100, 64)]
    ),
    "start_at_or_past_stripe_end": dict(
        stripe=128, T=32, rows=[(128, 32)]
    ),
    "batch_of_two_as_compare_py": dict(
        stripe=516, T=512, rows=[(0, 96), (0, 300)], decode=4
    ),
    "batch_wider_than_block_cap_scatters": dict(
        stripe=64, T=16, rows=[(i, 16 - i) for i in range(9)], decode=1
    ),
    "moe": dict(
        cfg_kw=dict(moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0),
        stripe=128, T=64, rows=[(32, 44)], decode=2,
    ),
    "lora": dict(
        lora=True, stripe=128, T=64, rows=[(32, 44), (10, 64)], decode=2
    ),
}


@pytest.mark.parametrize("name", sorted(KV_WRITE_CASES))
def test_prefill_block_write_equals_scatter(name):
    """``prefill`` writes each row's keys and values as one contiguous block;
    the cache and the logits must equal, exactly, what the ``mode="drop"``
    scatter leaves (``decode_forward`` without ``start_pos``): padding and
    rows past the stripe's end keep the cache's old bytes, a window that
    would pass the stripe's end is not shifted by the clamp, and decode steps
    after it read the same slots."""
    from ray_tpu.models.llama import init_lora_stack
    from ray_tpu.models.patterned import _BLOCK_WRITE_MAX_BATCH, decode_forward

    case = KV_WRITE_CASES[name]
    cfg = LlamaConfig.tiny(**case.get("cfg_kw", {}))
    params = init_params(jax.random.PRNGKey(11), cfg)
    rng = np.random.default_rng(11)
    S, T, rows = case["stripe"], case["T"], case["rows"]
    B = len(rows)
    start = jnp.asarray([r[0] for r in rows], jnp.int32)
    lengths = jnp.asarray([r[1] for r in rows], jnp.int32)
    with_logits = case.get("with_logits", True)
    lora_kw = {}
    if case.get("lora"):
        stack = init_lora_stack(cfg, 2, 4)
        lora_kw = dict(
            loras={
                k: jnp.asarray(rng.normal(0, 0.1, v.shape), v.dtype)
                for k, v in stack.items()
            },
            adapter_ids=jnp.asarray([1, 2][:B], jnp.int32),
        )

    # old bytes everywhere: what a write must keep is seen only if it differs
    # from zero. Slots under `start` stand for the earlier chunks or prefix.
    shape = init_kv_cache(cfg, B, S)["k"].shape
    cache = {
        "k": jnp.asarray(rng.normal(0, 1, shape), cfg.dtype),
        "v": jnp.asarray(rng.normal(0, 1, shape), cfg.dtype),
        "length": start,
    }
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, T)), jnp.int32)

    def scatter_prefill(params, cache, tokens, lengths, start):
        rel = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        logits, out = decode_forward(
            params, dict(cache), tokens, rel + start[:, None], cfg,
            rel < lengths[:, None], with_logits=with_logits,
            logits_at=lengths - 1 if with_logits else None, **lora_kw,
        )
        out["length"] = start + lengths
        return (logits[:, 0] if with_logits else None), out

    def block_prefill(params, cache, tokens, lengths, start):
        return prefill(
            params, dict(cache), tokens, cfg, lengths=lengths,
            start_pos=start, with_logits=with_logits, **lora_kw,
        )

    # a scatter into the cache: its result has the cache's five axes (the
    # grouped matmul of a MoE layer scatters into its own 1-D group tables)
    cache_scatter = re.compile(r":\w+\[\d+,\d+,\d+,\d+,\d+\] = scatter\[")
    text = str(jax.make_jaxpr(block_prefill)(params, cache, tokens, lengths, start))
    if B <= _BLOCK_WRITE_MAX_BATCH:
        assert not cache_scatter.search(text) and "dynamic_update_slice" in text
    else:
        assert cache_scatter.search(text)
    assert cache_scatter.search(str(
        jax.make_jaxpr(scatter_prefill)(params, cache, tokens, lengths, start)
    ))

    ref_logits, ref = jax.jit(scatter_prefill)(params, cache, tokens, lengths, start)
    got_logits, got = jax.jit(block_prefill)(params, cache, tokens, lengths, start)
    for key in ("k", "v", "length"):
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(ref[key]))
    # the write did something, and kept the old bytes past each row's length
    assert not np.array_equal(np.asarray(got["k"]), np.asarray(cache["k"])) or all(
        s >= S for s, _ in rows
    )
    for b, (s, n) in enumerate(rows):
        np.testing.assert_array_equal(
            np.asarray(got["k"][:, b, :, min(s + n, S):]),
            np.asarray(cache["k"][:, b, :, min(s + n, S):]),
        )
        np.testing.assert_array_equal(
            np.asarray(got["v"][:, b, :, :min(s, S)]),
            np.asarray(cache["v"][:, b, :, :min(s, S)]),
        )
    if with_logits:
        np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(ref_logits))
        nxt = jnp.argmax(ref_logits, -1)
    else:
        assert got_logits is None
        nxt = tokens[:, 0]
    dec = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, **lora_kw))
    for _ in range(case.get("decode", 0)):
        ref_step, ref = dec(params, ref, nxt)
        got_step, got = dec(params, got, nxt)
        np.testing.assert_array_equal(np.asarray(got_step), np.asarray(ref_step))
        for key in ("k", "v", "length"):
            np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(ref[key]))
        nxt = jnp.argmax(ref_step, -1)
