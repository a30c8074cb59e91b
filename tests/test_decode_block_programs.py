"""The engine's decode program and its carrying final chunk (``llm/engine.py
programs``) at each serving cell's published shape, lowered on the CPU (the
kernels interpreted: their buffers and loops are in the text) and read without
locations, so that a change shows in the cells it moves and in no other.
PR 54: the decode kernel's block follows from what a position of the cache
holds (``ops/decode_attention.py block_size``); the two cells at two key-value
heads (ZAYA1-8B, Nemotron-3-Super) walk 512-position blocks where that PR's
parent walked 128 (compilation of those for a described v5e:
``tests/test_chip_compile_zaya.py``). PR 56: the block cell's carrying chunk
programs (SDAR-30B-A3B-Chat: the pool's block step rides through them) are
pinned at their own text. PR 58: a decode step's new keys and values go through
``ops/cache_write.py``'s kernel in the cells whose cache is stripes of 128-wide
heads (pinned at their own text), and the latent cells' and the block cell's
programs are the parent's, digest for digest (Granite's 64-wide heads never
reach a kernel on the chip: ``tests/test_granite.py``; its 40 layers lower in a
minute, so it is not here)."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm.engine import programs
from ray_tpu.models.llama import init_kv_cache, init_params
from tests.chip_compile import (
    _block_diffusion_cut,
    _convolved_attention_cut,
    _delta_rule_cut,
    _served_config,
    _sparse_latent_cut,
    _state_space_cut,
)

CHUNK = 128  # a final chunk's width: a prefill bucket of every cell
# cell -> (its served cut, slots, stripe)
CELLS = {
    "mistral7b-serve-saturated": (lambda: _served_config("mistral-7b-serve-l16"), 32, 1024),
    "laguna-xs2-serve-mixed": (lambda: _served_config("laguna-xs.2-serve-l5"), 32, 4096),
    "solar-open2-serve-long-chat": (_delta_rule_cut, 64, 8192),
    "kanana2-serve-docs-shared": (lambda: _served_config("kanana-2-30b-a3b-serve-l5"), 24, 24576),
    "dots3-note-serve-docs-shared": (_sparse_latent_cut, 16, 24576),
    "nemotron3-super-serve-chat": (_state_space_cut, 64, 2048),
    "zaya1-8b-serve-long-chat": (_convolved_attention_cut, 64, 4608),
    "sdar-30b-a3b-serve-block-chat": (_block_diffusion_cut, 64, 4096),
}
# the first 16 hex digits of the SHA-256 of each program's StableHLO without
# locations (``lower(..).as_text()``) as the parent commit (PR 53) lowered it,
# taken from a checkout of the parent with this file's ``_lowered``
# (a latent pool's chunk programs never carry: ``llm/engine.py _takes_rows``;
# the state-space and delta-rule cuts' chunks take a quarter of a minute to lower)
_PARENT = {
    ("mistral7b-serve-saturated", "decode_fn"): "feebb7d987dd8027",
    ("laguna-xs2-serve-mixed", "decode_fn"): "af625bfacf07515b",
    ("solar-open2-serve-long-chat", "decode_fn"): "93af8f49f281ce5a",
    ("kanana2-serve-docs-shared", "decode_fn"): "f2abae5cfc9a02ef",
    ("dots3-note-serve-docs-shared", "decode_fn"): "b255e7098b6ba86b",
    ("nemotron3-super-serve-chat", "decode_fn"): "9aa337ad53fa4c5b",
    ("zaya1-8b-serve-long-chat", "decode_fn"): "b8956e479f648cae",
    ("mistral7b-serve-saturated", "chunk_final"): "52561f2c35675b20",
    ("laguna-xs2-serve-mixed", "chunk_final"): "6d8d9cc0af48cef2",
    ("zaya1-8b-serve-long-chat", "chunk_final"): "3a15e4d74eb12c90",
}
MOVED = ("nemotron3-super-serve-chat", "zaya1-8b-serve-long-chat")
# since PR 58 a decode step's rows of a token each write their new keys and
# values through ``ops/cache_write.py``'s kernel (interpreted here: its loops
# and its buffers of a tile a slot are in the text) wherever the cache is
# stripes of 128-wide heads: those cells' programs at this checkout's own text.
# The latent cells' stay the parent's (``_PARENT``), the block cell's too
# (``_BLOCKS``: its rows are a block wide)
_WRITTEN = {
    ("mistral7b-serve-saturated", "decode_fn"): "666be791594f5522",
    ("laguna-xs2-serve-mixed", "decode_fn"): "b3020969e9ccf43a",
    ("solar-open2-serve-long-chat", "decode_fn"): "cf9f1c059ed04fef",
    ("nemotron3-super-serve-chat", "decode_fn"): "bf32985579735571",
    ("zaya1-8b-serve-long-chat", "decode_fn"): "dcc40671d92523dd",
    ("mistral7b-serve-saturated", "chunk_final"): "6524e780ab0cb542",
    ("laguna-xs2-serve-mixed", "chunk_final"): "29fb189a2e4fbad0",
    ("zaya1-8b-serve-long-chat", "chunk_final"): "48eff01acf7f50bf",
}
# the block pool's chunk programs carry its block step since PR 56 (the rows a
# block wide: ``llm/engine.py programs``): that checkout's own text, pinned so
# that a later change to what they lower to shows here
_BLOCKS = {
    ("sdar-30b-a3b-serve-block-chat", "chunk_final"): "48a4e5837c05de7b",
    ("sdar-30b-a3b-serve-block-chat", "chunk_mid"): "a9fe887b0a27645f",
}


@functools.lru_cache(maxsize=None)
def _lowered(cell, program):
    """The engine's ``program`` at ``cell``'s shape: every slot's decode step
    with its sampler, or a 128-token chunk of one prompt (the final one, or
    of a block pool the one-row middle one too) that carries the pool's
    step."""
    make, slots, stripe = CELLS[cell]
    cfg = make()
    fns, sds = programs(cfg), jax.ShapeDtypeStruct
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, slots, stripe))
    sampler = (sds((slots,), jnp.float32), i32(slots), sds((slots, 2), jnp.uint32))
    if program == "decode_fn":
        args = (params, cache, i32(slots), *sampler)
    else:
        one = jax.eval_shape(lambda: fns["new_stripe"](stripe))
        riders = dict(zip(("tokens", "temps", "top_ks", "keys", "live"),
                          (i32(slots), *sampler, sds((slots,), jnp.bool_))))
        if cfg.block_length:  # its block state where the others hand over tokens
            del riders["tokens"]
            riders["block"] = jax.eval_shape(lambda: fns["new_block"](slots))
        chunk = (i32(1, CHUNK), i32(1), i32(1))
        args = (params, (one,), *chunk, cache, riders) if program == "chunk_mid" else (
            params, cache, one, *chunk, i32(), sds((), jnp.float32), i32(), sds((2,), jnp.uint32),
            riders)
    return jax.jit(fns[program]).lower(*args).as_text()


def _digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("cell, program", [key for key in _PARENT if key not in _WRITTEN])
def test_a_latent_cell_lowers_to_the_parents_text(cell, program):
    assert _digest(_lowered(cell, program)) == _PARENT[cell, program]


@pytest.mark.parametrize("cell, program", list(_WRITTEN))
def test_a_cell_whose_steps_write_through_the_kernel_is_pinned_at_its_own_text(cell, program):
    """The write kernel's buffer of keys (a tile of 16 positions of every
    key-value head a slot) is in the interpreted text, and the text is no
    longer the one ``_PARENT`` holds."""
    _, slots, _ = CELLS[cell]
    heads = 2 if cell in MOVED else 8
    text = _lowered(cell, program)
    assert _digest(text) == _WRITTEN[cell, program] != _PARENT[cell, program]
    assert f"tensor<{slots}x{heads}x16x128xbf16>" in text


@pytest.mark.parametrize("cell, program", [key for key in _PARENT if key[0] in MOVED])
def test_two_key_value_heads_walk_512_position_blocks(cell, program):
    """The kernel's double buffer of keys (two buffers of 2 heads of a block
    of 128-wide rows) is in the interpreted text: 512 positions where the
    parent's held 128, and nothing else of the program moved with it (the
    text is as long but for the digits)."""
    text = _lowered(cell, program)
    assert _digest(text) != _PARENT[cell, program]
    assert "tensor<2x2x512x128xbf16>" in text and "tensor<2x2x128x128xbf16>" not in text


@pytest.mark.parametrize("cell, program", list(_BLOCKS))
def test_the_block_cells_carrying_chunks_hold_one_folded_kernel_for_the_rows(cell, program):
    """The kernel's double buffer of keys for the 64 slots' blocks (four
    key-value heads: 256 positions a block of the walk) is in the interpreted
    text once a layer loop, beside no buffer of another block."""
    text = _lowered(cell, program)
    assert _digest(text) == _BLOCKS[cell, program]
    assert "tensor<2x4x256x128xbf16>" in text and "tensor<2x4x128x128xbf16>" not in text


if __name__ == "__main__":  # ``python3 -m tests.test_decode_block_programs``: this checkout's digests
    for key in (*_PARENT, *_BLOCKS):  # (``_WRITTEN``'s keys are among ``_PARENT``'s)
        print(f'    {key}: "{_digest(_lowered(*key))}",', flush=True)
