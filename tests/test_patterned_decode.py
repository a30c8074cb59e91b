"""The decode step of ``models/patterned.py``: through the kernel it gives the
einsum's tokens, over a mesh it keeps the einsum; the two forms of the expert
layer, the rotary tables against a NumPy transcription of the published code,
and the published depth's parameter count."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, decode_step, init_kv_cache, init_params, prefill
from ray_tpu.models.patterned import _moe_decode_ffn
from tests.patterned_models import CFG, MODELS, _count_kernel_calls, _model


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(7), CFG)


@pytest.mark.parametrize("model", ["dense", "dense-lora", "moe", "laguna"])
def test_decode_steps_through_the_kernel_give_the_einsums_tokens(monkeypatch, model):
    """16 greedy ``decode_step``s over a cache of whole blocks, which go
    through ``ops/decode_attention.py`` (interpreted), against the same steps
    with the kernel's selection switched off: the einsum over the whole
    stripe that every decode step ran before. Row 0 crosses a block's end on
    its way, row 1 stays inside the first block, and the patterned model's
    window starts mid-block (three blocks a stripe: the tiny models' rows hold
    few bytes a position and take the longest block that divides it, 128)."""
    from ray_tpu.ops.decode_attention import BLOCK

    cfg, params, lora_kw, _, _ = _model(model)
    lengths = jnp.asarray([BLOCK - 6, 30], jnp.int32)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, BLOCK), 0, cfg.vocab_size)

    traced = _count_kernel_calls(monkeypatch)

    def greedy(read_blocks):
        if not read_blocks:
            monkeypatch.setattr(patterned, "reads_blocks", lambda *a: False)
        step = jax.jit(lambda cache, toks: decode_step(params, cache, toks, cfg, **lora_kw()))
        logits, cache = prefill(params, init_kv_cache(cfg, 2, 3 * BLOCK), prompt, cfg,
                                lengths=lengths, **lora_kw())
        tokens, rows = [], [logits]
        for _ in range(16):
            tokens.append(jnp.argmax(rows[-1], -1))
            logits, cache = step(cache, tokens[-1])
            rows.append(logits)
        return np.asarray(jnp.stack(tokens)), np.asarray(jnp.stack(rows))

    tokens, logits = greedy(True)
    through_the_kernel = len(traced)
    want_tokens, want_logits = greedy(False)
    # one call a layer of the traced stack: the leading layers and one period
    assert through_the_kernel == (5 if cfg.layer_types else 1) == len(traced)
    np.testing.assert_array_equal(tokens, want_tokens)
    np.testing.assert_allclose(logits, want_logits, atol=5e-5, rtol=1e-4)


def _placed(how, cfg, slots, stripe):
    """``decode_step``'s arguments and ``jit`` options as each caller places
    them, on four virtual devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    def cache_on(mesh):  # ``llm/spmd.py``: key-value heads over ``tp``, made where they lie
        kv = NamedSharding(mesh, P(None, None, "tp", None, None))
        shardings = {"k": kv, "v": kv, "length": NamedSharding(mesh, P())}
        return jax.jit(lambda: init_kv_cache(cfg, slots, stripe), out_shardings=shardings)(), shardings

    if how == "one-device":  # ``JaxEngine`` with no mesh
        return init_params(jax.random.PRNGKey(7), cfg), init_kv_cache(cfg, slots, stripe), {}
    if how == "mesh-of-one-device":  # ``JaxEngine(config, mesh=<a mesh of one device>)``
        mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
        cache, _ = cache_on(mesh)
        return init_params(jax.random.PRNGKey(7), cfg, mesh=mesh), cache, {}
    mesh = build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
    params = init_params(jax.random.PRNGKey(7), cfg, mesh=mesh)
    if how == "engine-tp2":  # ``JaxEngine(tensor_parallel_degree=2)``: only the parameters on the mesh
        return params, init_kv_cache(cfg, slots, stripe), {}
    cache, shardings = cache_on(mesh)  # ``llm/spmd.py`` and, through it, ``llm/gang.py``
    return params, cache, dict(out_shardings=(NamedSharding(mesh, P()), shardings))


@pytest.mark.parametrize("how, kernel_calls", [
    ("one-device", 1), ("mesh-of-one-device", 1), ("engine-tp2", 0), ("spmd-tp2", 0)])
def test_a_decode_step_over_a_mesh_keeps_the_einsum(monkeypatch, how, kernel_calls):
    """``reads_blocks`` sees a mesh on the type of what the step is traced
    with: one kernel call a traced layer where everything lies on one device,
    none where the parameters or the cache lie on four, placed and jitted as
    ``llm/spmd.py`` and a ``JaxEngine`` under ``tensor_parallel_degree`` do
    (a Pallas call under the partitioner would be handed the whole gathered
    cache). Asked with the arrays themselves, as the engine asks for its
    counter, it answers what the trace does."""
    from ray_tpu.ops.decode_attention import BLOCK

    cfg = MODELS["dense"]
    params, cache, options = _placed(how, cfg, 2, BLOCK)
    traced = _count_kernel_calls(monkeypatch)
    step = jax.jit(lambda params, cache, toks: decode_step(params, cache, toks, cfg),
                   donate_argnums=(1,), **options)
    asked = patterned.reads_blocks(BLOCK, cache["k"], *jax.tree.leaves(params))
    logits, _ = step(params, cache, jnp.asarray([3, 5], jnp.int32))
    assert len(traced) == kernel_calls and asked == bool(kernel_calls)
    assert bool(jnp.isfinite(logits).all())
    assert not patterned.reads_blocks(BLOCK + 8, jnp.zeros(1))  # no whole blocks: the einsum anywhere


def test_grouped_expert_form_equals_every_expert_form(params):
    """``_moe_decode_ffn`` sorts tokens by expert; the same sum with every
    expert run over every token and a zero weight where a token did not
    choose it, written out here."""
    row, k, E = 2, CFG.moe_top_k, CFG.moe_experts
    for tokens in (3, 80):  # a decode batch, a chunk: less and more than one row tile
        h = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens, CFG.d_model), jnp.float32)
        grouped, stats = _moe_decode_ffn(params, row, h, CFG)
        g = h[0]
        probs = jax.nn.softmax(g @ params["moe_router"][row], axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        weights = (jax.nn.one_hot(idx, E) * (top / top.sum(-1, keepdims=True))[..., None]).sum(1)
        act = jax.nn.silu(jnp.einsum("gd,edf->egf", g, params["moe_w_gate"][row])) * jnp.einsum(
            "gd,edf->egf", g, params["moe_w_up"][row])
        every = jnp.einsum("egd,ge->gd", jnp.einsum("egf,efd->egd", act, params["moe_w_down"][row]), weights)
        shared = (jax.nn.silu(g @ params["moe_shared_gate"][row]) * (g @ params["moe_shared_up"][row])
                  ) @ params["moe_shared_down"][row]
        np.testing.assert_allclose(grouped[0], CFG.moe_routed_scale * every + shared, atol=2e-5, rtol=1e-4)
        layer_steps, assignments, touched, fullest = (int(x) for x in stats)
        assert (layer_steps, assignments) == (1, tokens * k)
        assert touched == len(set(np.asarray(idx).reshape(-1).tolist()))
        assert fullest == np.bincount(np.asarray(idx).reshape(-1)).max()


def _yarn_numpy(dim, base, factor, original, beta_fast, beta_slow):
    """transformers ``_compute_yarn_parameters``, transcribed."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2).astype(np.float32) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2).astype(np.float32) - low) / (high - low), 0, 1)
    inv_freq_extrapolation_factor = 1 - ramp
    return (inv_freq_interpolation * (1 - inv_freq_extrapolation_factor)
            + inv_freq_extrapolation * inv_freq_extrapolation_factor)


@pytest.mark.parametrize("cfg", [CFG, LlamaConfig.laguna_xs2()], ids=["tiny", "published"])
def test_yarn_and_the_half_rotation_against_numpy(cfg):
    inv, factor = patterned.rope_inv_freq(cfg, "full")
    rot = int(cfg.head_dim * cfg.rope_partial)
    want = _yarn_numpy(rot, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_len,
                       cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert factor == cfg.yarn_attention_factor and len(inv) == rot // 2
    # low frequencies are interpolated (divided by the factor), high ones kept
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, rot, 2) / rot)
    np.testing.assert_allclose(inv[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(inv[-1], plain[-1] / cfg.yarn_factor, rtol=1e-5)
    inv_s, factor_s = patterned.rope_inv_freq(cfg, "sliding")
    assert factor_s == 1.0 and len(inv_s) == cfg.head_dim // 2
    # the rotation itself: first `rot` dims rotated in halves, the rest untouched
    x = np.random.default_rng(0).normal(size=(1, 3, 2, cfg.head_dim)).astype(np.float32)
    pos = np.asarray([[0, 5, 901]], np.int32)
    got = np.asarray(patterned._rope(jnp.asarray(x), jnp.asarray(pos), inv, factor))
    ang = pos[..., None].astype(np.float64) * want
    cos, sin = np.cos(ang)[:, :, None, :] * factor, np.sin(ang)[:, :, None, :] * factor
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    np.testing.assert_allclose(got[..., :rot // 2], x1 * cos - x2 * sin, atol=2e-4)
    np.testing.assert_allclose(got[..., rot // 2:rot], x2 * cos + x1 * sin, atol=2e-4)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])


def test_published_depth_counts_its_parameters_and_traces_one_period():
    cfg = LlamaConfig.laguna_xs2()
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert n == cfg.num_params()
    assert abs(n / 33.44e9 - 1) < 1e-3
    pl = patterned.plan(cfg)
    assert (pl.lead, pl.period, pl.reps, cfg.n_layers - pl.tail_from) == (1, 4, 9, 3)
    # the served cut: layer 0 and one period, every layer its own body
    cut = LlamaConfig.laguna_xs2(n_layers=5)
    assert cut.layer_types == ("full", "sliding", "sliding", "sliding", "full")
    assert abs(cut.num_params() / 3.87e9 - 1) < 5e-3
