"""``engine.params`` is the engine's to normalise: the attention's input
projections lie head-major on the device, and a swap compiles nothing. One
parametrised test, a file of its own so that a worker has it alone."""

import numpy as np
import pytest

from ray_tpu.llm import (
    EngineConfig,
    JaxEngine,
    LLMConfig,
    ModelConfig,
    SamplingParams,
)

pytestmark = pytest.mark.timeout(600) if hasattr(pytest.mark, "timeout") else []

_HELD = {
    "dense": (dict(model_id="tiny"), ("wk", "wq", "wv")),
    "moe": (
        dict(model_id="tiny", model_kwargs={
            "moe_experts": 4, "moe_top_k": 2, "moe_capacity_factor": 8.0}),
        ("wk", "wq", "wv"),
    ),
    "laguna-tiny": (dict(model_id="laguna-tiny"), ("wk", "wq_full", "wq_sliding", "wv")),
    # the head axis sharded over tp: the layout is each shard's
    "dense-tp2-of-four-devices": (dict(model_id="tiny"), ("wk", "wq", "wv")),
}


def _orders(tree):
    return {k: tuple(v.format.layout.major_to_minor) for k, v in tree.items()}


@pytest.mark.parametrize("kind", sorted(_HELD))
def test_engine_holds_attention_input_projections_head_major(kind, monkeypatch):
    """``engine.params`` is the engine's to normalise: the stacked
    ``[.., e, h, hd]`` leaves lie head-major on the device, everything seen
    from outside stays, a swap relays and compiles nothing, and the tokens
    are those of an engine that keeps the default layout."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from tests.engine_helpers import Compiles

    model, held = _HELD[kind]
    tp = 2 if "tp2" in kind else 1
    mesh = build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4]) if tp > 1 else None
    cfg = LLMConfig(
        model=ModelConfig(tokenizer="byte", seed=3, **model),
        engine=EngineConfig(
            max_num_seqs=4, max_seq_len=128, dtype="float32", prefill_chunk=16,
            prefill_buckets=(8, 16, 32), tensor_parallel_degree=tp,
            # keys and values another tree wrote would outlive the swap
            enable_prefix_caching=False),
    )
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(32, 127, n)] for n in (5, 37)]
    greedy = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)

    def tokens(eng):
        return [eng.generate(prompt_token_ids=ids, sampling_params=greedy).token_ids
                for ids in prompts]

    def fresh(seed, eng):
        return llama.init_params(jax.random.PRNGKey(seed), eng.model_cfg, mesh=mesh)

    eng = JaxEngine(cfg, mesh=mesh)
    try:
        made = fresh(3, eng)  # what ``init_params`` gave this engine
        assert list(eng.params) == list(made)
        for name, leaf in eng.params.items():
            want = made[name]
            assert (leaf.shape, leaf.dtype, leaf.sharding) == (want.shape, want.dtype, want.sharding), name
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want))
        default = {k: tuple(range(v.ndim)) for k, v in made.items()}
        assert _orders(made) == default
        assert _orders(eng.params) == {**default, **dict.fromkeys(held, (0, 2, 1, 3))}
        assert eng.get_stats()["params_relaid"] == {
            "leaves": len(held), "bytes": sum(made[k].nbytes for k in held)}

        tokens(eng), tokens(eng)  # every program these prompts use is compiled
        held_programs, counts = dict(eng._programs), dict(eng._program_counts)
        with Compiles() as compiles:
            eng.params = None
            assert eng.params is None
            assert eng.get_stats()["params_relaid"] == {"leaves": 0, "bytes": 0}
            other = fresh(11, eng)
            eng.params = other
            # copied, not donated: the caller's tree stays whole, as made
            assert not any(v.is_deleted() for v in other.values())
            assert _orders(other) == default
            # the other leaves are the caller's own buffers, committed where they lie

            def buffers(x):
                return [shard.data.unsafe_buffer_pointer() for shard in x.addressable_shards]

            assert all(buffers(eng.params[k]) == buffers(v) and eng.params[k].committed
                       for k, v in other.items() if k not in held)
            assert _orders(eng.params) == {**default, **dict.fromkeys(held, (0, 2, 1, 3))}
            swapped = tokens(eng)
        # on one device each form is the executable it was, none compiled and
        # none refused the new tree; over a mesh the ``jit``s found their own
        assert eng._programs == held_programs and eng._program_counts == counts
        assert not [n for n in compiles.names if any(
            program in n for program in ("decode_fn", "chunk_mid", "chunk_final"))], compiles.names

        ids = jnp.asarray([prompts[1]], jnp.int32)
        np.testing.assert_allclose(
            np.asarray(llama.forward(eng.params, ids, eng.model_cfg)),
            np.asarray(llama.forward(other, ids, eng.model_cfg)),
            rtol=1e-5, atol=1e-5,
        )
        # a restored checkpoint's leaves are the host's: those the rule names
        # go to the device as ``init_params`` would have placed them
        eng.params = {k: np.asarray(v) for k, v in fresh(11, eng).items()}
        for name in held:
            leaf = eng.params[name]
            assert tuple(leaf.format.layout.major_to_minor) == (0, 2, 1, 3)
            assert leaf.sharding == made[name].sharding
        assert eng.get_stats()["params_relaid"]["leaves"] == len(held)
    finally:
        eng.shutdown()

    monkeypatch.setattr(llama, "serving_layouts", lambda names: {})
    plain = JaxEngine(cfg, mesh=mesh)
    try:
        plain.params = fresh(11, plain)
        assert _orders(plain.params) == default
        assert plain.get_stats()["params_relaid"] == {"leaves": 0, "bytes": 0}
        assert tokens(plain) == swapped
    finally:
        plain.shutdown()
