"""The write kernel (``ops/cache_write.py``: a decode step's new keys and
values, both tensors and every row in one call a layer) against the
``mode="drop"`` scatter it replaces, interpreted on the CPU, bit for bit on the
whole cache: the kernel alone at the shapes and positions that bound it, then
inside the model's loops (``models/patterned.py decode_forward``: the layer
loop, a looped stack's pass loop, alone and beside a prompt's chunk) against
the same program with the gate closed; then the gate itself
(``models/patterned.py writes_rows``). Compilation for a described v5e:
``tests/test_chip_compile_ouro.py``, ``tests/test_chip_compile_served.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, decode_step, init_kv_cache, init_params, prefill
from ray_tpu.ops.cache_write import rows_in_stripe, tile_positions, write_rows_in_place

L, S, D = 3, 48, 128  # three 16-position tiles of bfloat16, six of float32


def _scatter(c_all, new, l, pos, valid):
    """``models/patterned.py _cache_writer``'s scatter of new [B, K, D]."""
    B, K, _ = new.shape
    pos = pos if valid is None else jnp.where(valid, pos, c_all.shape[3])
    return c_all.at[l, jnp.arange(B)[:, None, None], jnp.arange(K)[None, :, None],
                    pos[:, None, None]].set(new[:, :, None], mode="drop")


def _caches(dtype, B, K):
    keys = jax.random.split(jax.random.PRNGKey(B * 31 + K), 4)
    ck, cv = (jax.random.normal(k, (L, B, K, S, D), dtype) for k in keys[:2])
    new_k, new_v = (jax.random.normal(k, (B, K, D), dtype) for k in keys[2:])
    return ck, cv, new_k, new_v


def _static(write, ck, cv, new_k, new_v):
    return write(ck, cv, 1, new_k, new_v)


def _layer_loop(write, ck, cv, new_k, new_v):  # the layer index traced, the caches carried round
    return jax.lax.fori_loop(
        0, L, lambda l, c: write(*c, l, new_k * (l + 1).astype(new_k.dtype), new_v), (ck, cv))


def _pass_loop(write, ck, cv, new_k, new_v):  # ``_run_passes``: row ``t * layers + l``
    def a_pass(t, c):
        return jax.lax.fori_loop(0, 1, lambda l, c: write(
            *c, t * 1 + l, new_k, new_v * (t + 2).astype(new_v.dtype)), c)

    return jax.lax.fori_loop(0, L, a_pass, (ck, cv))


TILE_ENDS = [16, 31, 24, 0, S - 1]  # a tile's first, last and a middle row; the stripe's ends
CASES = [
    pytest.param(jnp.bfloat16, 2, TILE_ENDS, None, _static, id="bfloat16-2-heads"),
    pytest.param(jnp.float32, 8, TILE_ENDS, None, _static, id="float32-8-heads"),
    pytest.param(jnp.bfloat16, 16, TILE_ENDS, None, _static, id="bfloat16-16-heads"),
    pytest.param(jnp.bfloat16, 2, [S, 5, S + 7, 2**30], None, _static, id="at-and-past-the-end"),
    pytest.param(jnp.float32, 2, [S, 5, S + 7, -1], None, _static, id="float32-past-the-end"),
    pytest.param(jnp.bfloat16, 8, [3, 17, 40], [True, False, True], _static, id="a-dead-row"),
    pytest.param(jnp.bfloat16, 2, [3, 17], [False, False], _static, id="every-row-dead"),
    pytest.param(jnp.bfloat16, 2, [7, S, 32], [True, True, False], _layer_loop,
                 id="under-the-layer-loop"),
    pytest.param(jnp.float32, 8, [7, 47], None, _pass_loop, id="under-the-pass-loop"),
]


@pytest.mark.parametrize("dtype, K, pos, valid, how", CASES)
def test_the_kernel_leaves_the_scatters_bytes(dtype, K, pos, valid, how):
    ck, cv, new_k, new_v = _caches(dtype, len(pos), K)
    pos = jnp.asarray(pos, jnp.int32)
    valid = None if valid is None else jnp.asarray(valid)
    assert S % tile_positions(ck) == 0

    def kernel(ck, cv, l, new_k, new_v):
        return tuple(write_rows_in_place(ck, cv, l, new_k, new_v, *rows_in_stripe(pos, valid, S)))

    def scatter(ck, cv, l, new_k, new_v):
        keep = pos >= 0  # (a scatter wraps a negative index round; no caller has one)
        keep = keep if valid is None else keep & valid
        return _scatter(ck, new_k, l, pos, keep), _scatter(cv, new_v, l, pos, keep)

    have = jax.jit(lambda *a: how(kernel, *a))(ck, cv, new_k, new_v)
    want = jax.jit(lambda *a: how(scatter, *a))(ck, cv, new_k, new_v)
    for h, w, old in zip(have, want, (ck, cv)):
        np.testing.assert_array_equal(np.asarray(h, np.float32), np.asarray(w, np.float32))
        if how is _static:  # the other layers' rows are the old ones
            np.testing.assert_array_equal(np.asarray(h[0], np.float32), np.asarray(old[0], np.float32))


def test_a_stripe_that_is_no_whole_number_of_tiles_is_refused():
    ck, cv, new_k, new_v = _caches(jnp.bfloat16, 2, 2)
    with pytest.raises(ValueError, match="whole number"):  # (and a 64-wide head likewise)
        write_rows_in_place(ck[:, :, :, :40], cv[:, :, :, :40], 0, new_k, new_v,
                            *rows_in_stripe(jnp.zeros((2,), jnp.int32), None, 40))


# ---------------------------------------------------------------- in the model

MODELS = {
    "layer-loop": LlamaConfig.tiny(dtype=jnp.bfloat16, n_heads=2, n_kv_heads=2, head_width=128),
    "pass-loop": LlamaConfig.ouro_tiny(n_heads=2, n_kv_heads=2, head_width=128),
}


def _programs(cfg):
    def alone(p, c, t):
        return decode_step(p, c, t, cfg)[1]

    def beside(p, one, c, chunk, t, live):
        _, one, _, c = prefill(p, one, chunk, cfg, lengths=jnp.asarray([5]),
                               start_pos=jnp.zeros((1,), jnp.int32), beside=(c, t, live))
        return one, c

    return alone, beside


@pytest.mark.parametrize("model", list(MODELS))
def test_a_step_alone_and_beside_a_chunk_leaves_the_scatters_cache(model, monkeypatch):
    """A decode step of three rows (one past its stripe's end alone, one not
    live beside the chunk), through the kernel and with the gate closed: the
    pool's whole cache, every layer's and pass's row, holds the same bytes."""
    cfg = MODELS[model]
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab_size)
    pool = init_kv_cache(cfg, 3, 32)
    _, pool = prefill(params, pool, tokens[:3, :6], cfg, lengths=jnp.asarray([6, 4, 6]),
                      start_pos=jnp.zeros((3,), jnp.int32))
    pool["length"] = pool["length"].at[2].set(32)  # a dead slot's length runs on
    one = init_kv_cache(cfg, 1, 32)
    if cfg.loop_passes > 1:
        one["loop_stats"] = jnp.zeros((2 + cfg.loop_passes,), jnp.int32)
    live = jnp.asarray([True, False, True])

    def run():
        alone, beside = _programs(cfg)
        stepped = jax.jit(alone)(params, pool, tokens[:3, 6])
        return stepped, jax.jit(beside)(params, one, stepped, tokens[3:, :8], tokens[:3, 7], live)

    scopes = jax.jit(_programs(cfg)[1]).lower(
        params, one, pool, tokens[3:, :8], tokens[:3, 7], live).as_text(debug_info=True)
    assert "beside/kv_write/cache_write_rows" in scopes
    have = run()
    monkeypatch.setattr(patterned, "writes_rows", lambda *a, **kw: False)
    want = run()
    np.testing.assert_array_equal(have[0]["length"], [7, 5, 33])
    np.testing.assert_array_equal(have[1][1]["length"], [8, 5, 34])
    for h, w in zip(jax.tree.leaves(have), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(h, np.float32), np.asarray(w, np.float32))
    assert np.asarray(have[0]["k"][:, 0, :, 6], np.float32).any()  # (and something was written)


# -------------------------------------------------------------------- the gate


def _asked(T, from_start, *arrays, latent):
    """``writes_rows`` asked with the arrays and, in a trace, with their tracers."""
    traced = []
    jax.jit(lambda *tracers: traced.append(
        patterned.writes_rows(T, from_start, *tracers, latent=latent))).lower(*arrays)
    return patterned.writes_rows(T, from_start, *arrays, latent=latent), traced[0]


GATE = [
    ("a-decode-steps-rows", dict(), True),
    ("a-latent-cache", dict(latent=True), False),
    ("a-64-wide-head", dict(width=64), False),  # on the chip and interpreted alike
    ("a-block-of-four-a-row", dict(T=4), False),
    ("a-one-token-chunk-from-its-start", dict(from_start=True), False),
    ("a-stripe-of-no-whole-tiles", dict(stripe=40), False),
    ("a-mesh-of-two-devices", dict(mesh=2), False),
    ("a-mesh-of-one-device", dict(mesh=1), True),
]


@pytest.mark.parametrize("case, what, answer", GATE, ids=[c for c, _, _ in GATE])
def test_the_gate_reads_shapes_and_answers_arrays_and_tracers_alike(case, what, answer):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    cache_k = jnp.zeros((2, 2, 2, what.get("stripe", 32), what.get("width", 128)), jnp.bfloat16)
    weight = jnp.zeros((8, 8), jnp.bfloat16)
    if "mesh" in what:  # the key-value heads over ``tp``, as ``llm/spmd.py`` places them
        mesh = build_mesh(MeshSpec(tp=what["mesh"]), devices=jax.devices()[:what["mesh"]])
        cache_k = jax.device_put(cache_k, NamedSharding(mesh, P(None, None, "tp", None, None)))
    asked = _asked(what.get("T", 1), what.get("from_start", False), cache_k, weight,
                   latent=what.get("latent", False))
    assert asked == (answer, answer)


def test_a_pool_asks_the_gate_with_its_own_arrays():
    """``llm/engine.py _Pool.writes_rows`` (what ``get_stats()["pools"][i]
    ["decode_write"]`` names ``kernel`` or ``scatter``): the gate's answer for
    the pool's cache and the engine's parameters, a token a row."""
    from ray_tpu.llm.engine import _Pool

    for width, answer in ((128, True), (16, False)):
        cfg = LlamaConfig.tiny(n_heads=2, n_kv_heads=2, head_width=width)
        params = init_params(jax.random.PRNGKey(0), cfg)
        pool = _Pool(32, 2, cfg, params)
        assert pool.writes_rows is answer
        assert _asked(1, False, pool.cache["k"], *jax.tree.leaves(params), latent=False) == (
            answer, answer)
