"""The plain reference against ``models/llama.py`` at a tiny size, in
float32, and the int8 control failing the same comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, reference, weights
from benchmark.families import dense_gqa

CONFIG = dict(
    hidden_size=64, intermediate_size=128, vocab_size=256, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_hidden_layers=2, tie_word_embeddings=False,
    rope_theta=1e6, rms_norm_eps=1e-5, sliding_window=None,
)
# float32 against float32 on the CPU agrees to rounding (measured 6e-7 on
# logits, 4e-8 on the loss); int8 weights read 3e-2 on logits. The limit sits
# between, a decade from either.
LIMIT = 1e-3


@pytest.fixture(scope="module")
def setup():
    from ray_tpu.models.llama import LlamaConfig

    params = weights.make_params(2**31 + 5, CONFIG, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 256, (4, 33), dtype=np.int32)
    cfg = LlamaConfig(**dense_gqa.model_kwargs(CONFIG), max_seq_len=64, dtype=jnp.float32,
                      remat=False, fused_ce=True)
    return params, tokens, cfg, reference.Reference(CONFIG)


def program_numbers(params, tokens, cfg):
    import optax

    from ray_tpu.models.llama import forward, loss_fn

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": jnp.asarray(tokens)}, cfg))(params)
        logits = forward(params, jnp.asarray(tokens[:, :-1]), cfg)
    return float(loss), float(optax.global_norm(grads)), np.asarray(logits)


def test_weights_same_seed_same_values(setup):
    params = setup[0]
    again = weights.make_params(2**31 + 5, CONFIG, jnp.float32)
    other = weights.make_params(6, CONFIG, jnp.float32)
    assert all((params[k] == again[k]).all() for k in params)
    assert any((params[k] != other[k]).any() for k in params)
    assert float(jnp.std(params["w_down"])) == pytest.approx(128 ** -0.5, rel=0.05)


def test_program_agrees_with_reference(setup):
    params, tokens, cfg, ref = setup
    loss, gnorm, logits = program_numbers(params, tokens, cfg)
    ref_loss, ref_gnorm = ref.loss_and_grad_norm(params, tokens)
    assert abs(loss - ref_loss) / ref_loss < 1e-5
    assert abs(gnorm - ref_gnorm) / ref_gnorm < 1e-5
    assert reference.rel_rms(logits, ref.logits(params, tokens[:, :-1])) < LIMIT
    last = ref.logits(params, tokens[:, :-1], last=8)
    assert reference.rel_rms(logits[:, -8:], last) < LIMIT


def test_prefill_then_decode_agrees_with_full_forward(setup):
    from ray_tpu.models.llama import decode_step, init_kv_cache, prefill

    params, tokens, cfg, ref = setup
    row = tokens[0, :24]
    padded = np.zeros((1, 32), np.int32)
    padded[0, :20] = row[:20]
    cache = init_kv_cache(cfg, 1, 40)
    logits, cache = prefill(params, cache, jnp.asarray(padded), cfg,
                            lengths=jnp.asarray([20], jnp.int32))
    got = [np.asarray(logits)]
    for i in range(20, 24):
        logits, cache = decode_step(params, cache, jnp.asarray(row[i:i + 1]), cfg)
        got.append(np.asarray(logits))
    want = ref.logits(params, row[None], last=5)[0]
    assert reference.rel_rms(np.concatenate(got), want) < LIMIT


def test_int8_control_fails_the_comparison(setup):
    params, tokens, cfg, ref = setup
    cut = weights.int8_roundtrip(jax.tree.map(jnp.copy, params))
    _, _, logits = program_numbers(cut, tokens, cfg)
    err = reference.rel_rms(logits, ref.logits(params, tokens[:, :-1]))
    assert err > 10 * LIMIT, err
    assert all((cut[k] == params[k]).all() for k in params if "norm" in k)


def first_moments(params, tokens, cfg, clip=1.0, b1=0.9):
    """Adam's first moment after one step of the clipped optimizer, as the
    train cell's step program leaves it."""
    import optax

    from ray_tpu.models.llama import loss_fn

    optimizer = optax.chain(optax.clip_by_global_norm(clip), optax.adamw(1e-3, b1=b1, b2=0.95))
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: loss_fn(p, {"tokens": jnp.asarray(tokens)}, cfg))(params)
    _, state = optimizer.update(grads, optimizer.init(params), params)
    return optax.tree_utils.tree_get(state, "mu")


@pytest.mark.parametrize("stride", [1, 7])
def test_gradient_sample_agrees_and_int8_control_fails(setup, stride):
    params, tokens, cfg, ref = setup
    sample = compare.GradSample(stride)
    _, ref_norm = ref.loss_and_grad_norm(params, tokens, visit=sample.visit)
    assert {name for name, _ in sample.ref} == set(params)
    assert sum(1 for name, layer in sample.ref if name == "wo") == CONFIG["num_hidden_layers"]
    sound = compare.grad_errors(sample, first_moments(params, tokens, cfg), 0.9, 1.0, ref_norm)
    assert sound["grad_rel_rms"] < LIMIT and sound["grad_rel_rms_direction"] < LIMIT
    assert max(sound["grad_rel_rms_by_leaf"].values()) < LIMIT
    cut = weights.int8_roundtrip(jax.tree.map(jnp.copy, params))
    control = compare.grad_errors(sample, first_moments(cut, tokens, cfg), 0.9, 1.0, ref_norm)
    assert control["grad_rel_rms"] > 10 * LIMIT, control


def test_engine_probe_reads_the_engines_own_cache(setup):
    """The prompts through a real engine's loop (a middle chunk, a final
    chunk, batched decode), its cache against the reference's keys and
    values; the int8 control fails the same comparison."""
    from ray_tpu.llm import EngineConfig, LLMConfig, ModelConfig
    from ray_tpu.llm.engine import JaxEngine

    params, _, _, ref = setup
    config = dict(CONFIG, vocab_size=512)
    params = weights.make_params(11, config, jnp.float32)
    ref = reference.Reference(config)
    probe = {"prompt_lens": [20, 90], "decode_steps": 3, "stripe": 128}
    engine = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=1,
                          model_kwargs=dense_gqa.model_kwargs(config)),
        engine=EngineConfig(dtype="float32", max_num_seqs=4, max_seq_len=256, prefill_chunk=64),
    ))
    try:
        rows = compare.probe_rows(5, probe)
        with jax.default_matmul_precision("highest"):
            engine.params = params
            got = compare.serve_program(engine, rows, probe)
            sound = compare.serve_errors(got, ref, params, rows, probe)
            engine.params = weights.int8_roundtrip(jax.tree.map(jnp.copy, params))
            control = compare.serve_errors(
                compare.serve_program(engine, rows, probe), ref, params, rows, probe)
    finally:
        engine.shutdown()
    assert sound["engine_generated"] == [4, 4]
    assert [len(e["tokens"]) for e in got["engine"]] == [23, 93]
    for key in ("logits_rel_rms", "kv_prefill_rel_rms", "kv_decode_rel_rms"):
        assert sound[key] < LIMIT, (key, sound)
        assert control[key] > 10 * LIMIT, (key, control)


def test_tied_embeddings_gradient(setup):
    import optax

    from ray_tpu.models.llama import LlamaConfig, loss_fn

    _, tokens, _, _ = setup
    config = dict(CONFIG, tie_word_embeddings=True)
    params = weights.make_params(3, config, jnp.float32)
    assert "unembed" not in params
    cfg = LlamaConfig(**dense_gqa.model_kwargs(config), max_seq_len=64, dtype=jnp.float32,
                      remat=False, fused_ce=False)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": jnp.asarray(tokens)}, cfg))(params)
    ref_loss, ref_gnorm = reference.Reference(config).loss_and_grad_norm(params, tokens)
    assert abs(float(loss) - ref_loss) / ref_loss < 1e-5
    assert abs(float(optax.global_norm(grads)) - ref_gnorm) / ref_gnorm < 1e-5
