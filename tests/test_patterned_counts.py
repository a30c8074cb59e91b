"""What an engine counts of a patterned model's decode steps, each test on
engines of its own: no routing and no window for a dense model, the positions
a step reads by the blocks each slot's bounds cover, and the form its decode
steps take (kernel or einsum; one device, a mesh of one, a mesh of four)."""

import jax
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams
from tests.patterned_models import _count_kernel_calls


def _routing(eng):
    c = eng.get_stats()["counters"]
    return {k: dict(c[k]) for k in c if k.startswith("moe_")}, c


def test_a_dense_engine_counts_no_routing_and_no_window():
    eng = JaxEngine(LLMConfig(model=ModelConfig(model_id="tiny", seed=1),
                              engine=EngineConfig(max_num_seqs=2, max_seq_len=64, dtype="float32")))
    try:
        eng.generate("hello there", sampling_params=SamplingParams(max_tokens=4, ignore_eos=True))
        routing, c = _routing(eng)
        assert all(v == {"decode": 0, "chunk_mid": 0, "chunk_final": 0} for v in routing.values())
        # the four routing counts, the assignments held (PR 35) and the blocks (PR 45)
        assert len(routing) == 6
        assert c["decode_kv_tokens_window"] == 0 < c["decode_kv_tokens_global"]
    finally:
        eng.shutdown()


def test_positions_read_counts_the_blocks_each_slots_bounds_cover():
    """Two requests at once in stripes of three 128-position blocks (laguna-tiny's
    rows hold few bytes a position, so its block is the longest that divides
    the stripe: 128 of 384), the longer crossing the first block's end while it
    decodes: ``decode_kv_positions_read`` (and the window layers' ``_window``)
    are ``ops/decode_attention.py positions_read`` summed over the lengths the
    loop held at each launch, no less than the tokens needed and no more than
    the active slots' whole stripes."""
    from ray_tpu.ops.decode_attention import BLOCK, positions_read

    stripe = 3 * BLOCK
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="laguna-tiny", seed=3),
        engine=EngineConfig(max_num_seqs=4, max_seq_len=stripe, dtype="float32",
                            prefill_chunk=64, prefill_buckets=(16, 32, 64),
                            max_concurrent_admissions=1),  # nothing here speaks of rows
    ))
    try:
        window = eng.model_cfg.sliding_window
        want = {"full": 0, "window": 0, "whole": 0}
        launch, carried = eng._decode, eng._carried
        held = eng._pools[0].position_bytes  # a position of a row, a layer
        assert eng._pools[0].decode_block == BLOCK == eng.get_stats()["pools"][0]["decode_block"]

        def count(requests):  # called by the loop right before it counts
            for r in requests:
                n = len(r.prompt_token_ids) + len(r.out_tokens)
                want["full"] += eng._decode_n_steps * positions_read(0, n, stripe, held)
                want["window"] += eng._decode_n_steps * positions_read(n - window, n, stripe, held)
                want["whole"] += eng._decode_n_steps * stripe

        def counting(pool, *args):
            count(r for r in pool.slots if r is not None)
            return launch(pool, *args)

        def counting_carried(pool, carry, next_tokens):  # a step a chunk launch carried
            count(carry.values() if isinstance(carry, dict) else ())  # or why it carried none
            return carried(pool, carry, next_tokens)

        eng._decode, eng._carried = counting, counting_carried
        rng = np.random.default_rng(9)
        p = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
        reqs = [eng.submit(prompt_token_ids=[int(t) for t in rng.integers(32, 127, n)],
                           sampling_params=p) for n in (BLOCK - 10, 21)]
        for r in reqs:
            assert r.done.wait(timeout=120)
        c = eng.get_stats()["counters"]
        assert c["decode_kv_positions_read"] == want["full"] > 0
        assert c["decode_kv_positions_read_window"] == want["window"] > 0
        assert c["decode_kv_tokens_global"] <= c["decode_kv_positions_read"] <= want["whole"]
        assert c["decode_kv_tokens_window"] <= c["decode_kv_positions_read_window"] <= want["whole"]
        assert want["whole"] == c["decode_slot_steps"] * stripe
        # some steps read one block of the long request's stripe, some both; the
        # window never more than two
        assert BLOCK * c["decode_slot_steps"] < c["decode_kv_positions_read"] < want["whole"]
        assert c["decode_kv_positions_read_window"] <= c["decode_kv_positions_read"]
    finally:
        eng.shutdown()


# (key-value heads, a head's width) of ``tiny``'s own rows and of rows as wide as
# the eight-head cells': 256 and 4,096 float32 bytes a position
HEADS = {"two-heads": {}, "eight-heads": {"n_heads": 8, "n_kv_heads": 8, "head_width": 64}}


@pytest.mark.parametrize("placed, heads, block", [
    ("no-mesh", "two-heads", 512), ("no-mesh", "eight-heads", 128),
    ("mesh-of-one-device", "two-heads", 512), ("tp2", "two-heads", None)])
def test_an_engine_counts_the_form_its_decode_steps_take(monkeypatch, placed, heads, block):
    """The engine's counter and the body's choice are one answer
    (``patterned.reads_blocks``, asked once a pool): where the traced decode
    program calls the kernel, ``decode_kv_positions_read`` counts the blocks
    the pool's own ``decode_block`` covers (``ops/decode_attention.py
    block_size`` of the pool's cache: two narrow heads walk a 1,024-position
    stripe in blocks of 512, eight wide ones in blocks of 128), and
    ``get_stats()["pools"]`` says the block; where it keeps the einsum
    (parameters over a mesh), whole stripes and no block. A mesh of one device
    is one device on both sides."""
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = {"no-mesh": None,
            "mesh-of-one-device": build_mesh(MeshSpec(), devices=jax.devices()[:1]),
            "tp2": build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])}[placed]
    traced = _count_kernel_calls(monkeypatch)
    stripe = 1024
    eng = JaxEngine(LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=3, model_kwargs=HEADS[heads]),
        engine=EngineConfig(max_num_seqs=2, max_seq_len=stripe, dtype="float32",
                            prefill_buckets=(16, 32), enable_prefix_caching=False,
                            tensor_parallel_degree=2 if placed == "tp2" else 1),
    ), mesh=mesh)
    try:
        out = eng.generate(prompt_token_ids=list(range(40, 60)), sampling_params=SamplingParams(
            max_tokens=8, temperature=0.0, ignore_eos=True))
        assert len(out.token_ids) == 8
        c = eng.get_stats()["counters"]
        [pool] = eng._pools
        # the body's choice as the engine's own decode program is traced: here,
        # where the engine restored its executables and traced none
        eng._decode_jit.lower(eng.params, pool.cache, pool.dev_tokens, *pool.sampler(), pool.keys)
        assert pool.reads_blocks == bool(traced) == (placed != "tp2")
        assert pool.decode_block == block == eng.get_stats()["pools"][0]["decode_block"]
        # (heads narrower than a lane tile: ``patterned.writes_rows`` keeps the scatter;
        # a pool of 128-wide heads: ``tests/test_cache_write.py``)
        assert eng.get_stats()["pools"][0]["decode_write"] == "scatter"
        per_slot_step = block or stripe  # 28 positions at most: one block
        assert c["decode_kv_positions_read"] == c["decode_slot_steps"] * per_slot_step > 0
        assert c["decode_kv_positions_read_window"] == 0  # no window layers in this model
    finally:
        eng.shutdown()
