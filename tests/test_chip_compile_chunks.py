"""The chunk programs without the decode kernel, the latent programs and the
engine's decode program; compiled at real widths for a described v5e
(``tests/chip_compile.py`` says how, and what that proves)."""

import re

import jax
import jax.numpy as jnp
import pytest

from tests.chip_compile import (
    _SERVED,
    _engine_programs,
    _engine_text,
    _program_text,
    _served_config,
    _served_programs,
    native_kernels,
    no_compile_cache,
    one_chip,
)


def _same_program(text):
    """Compiled text without what names a source line: metadata, the
    stack-frame tables, a Mosaic kernel's bytecode; instructions renumbered
    by first appearance."""
    import re

    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r'backend_config="[^"]*"', "", text)
    text = "\n".join(
        line for line in text.splitlines()
        if not line.startswith(("FileNames", "FunctionNames", "FileLocations", "StackFrames"))
        and not re.match(r"\d+ ", line))
    seen = {}
    return re.sub(r"%[\w.\-]+", lambda m: seen.setdefault(m.group(0), f"%{len(seen)}"), text)


@pytest.mark.parametrize("program", ["chunk_mid", "chunk_final"])
@pytest.mark.parametrize("served", sorted(_SERVED))
def test_chunk_programs_are_what_they_are_without_the_decode_kernel(
        served, program, one_chip, no_compile_cache, native_kernels, monkeypatch):
    """A prompt's chunk (``T > 1``) keeps the einsum: the 256-token ``prefill``
    without and with logits compiles to the text it compiles to with the
    kernel's selection switched off, source lines apart."""
    from ray_tpu.models import patterned

    cfg = _served_config(served)
    slots, stripe, _ = _SERVED[served]
    text = _same_program(_program_text(_served_programs(cfg, slots, stripe, one_chip)[program]))
    assert "decode_attention" not in text
    monkeypatch.setattr(patterned, "reads_blocks", lambda *a: False)
    # new functions, so that they are traced anew
    assert _same_program(
        _program_text(_served_programs(cfg, slots, stripe, one_chip)[program])) == text


def test_latent_programs_hold_nothing_as_long_as_the_stripe_and_copy_no_leaf(
        one_chip, no_compile_cache, native_kernels, monkeypatch):
    """The Kanana-2 cell's decode step (24 slots of 24,576) and a 256-token
    final chunk over one such stripe, 5 layers at published widths, the
    parameters in the formats the engine's rule gives. The decode step reads
    its latents through the kernel (one call in layer 0's body, one in the
    expert layers' loop, beside the three grouped matmuls); neither program's
    temporaries follow the stripe (a [32, 256, 24576] float32 score block
    alone is 0.8 GB; the chunk walks 1,024-position key blocks up to its
    row's length, in plain XLA: PR 52's kernel is not given 32 heads); and neither relays ``wq_latent`` whole, which both did
    with the leaf head-major or in the default layout (a 192-wide head is no
    whole number of lane tiles: 0.45 of a 9.06 ms decode step on the chip,
    PERF.md section 6, PR 33)."""
    cfg = _served_config("kanana-2-30b-a3b-serve-l5")
    whole_leaf = "bf16[5,2048,32,192]"

    def compiled(name):
        fn, args = _served_programs(cfg, 24, 24576, one_chip)[name]
        return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()

    def relays(text):
        return [line.strip()[:120] for line in text.splitlines()
                if " copy(" in line and line.split(" = ", 1)[-1].startswith(whole_leaf)]

    step = compiled("decode_step")
    text = step.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 5
    assert step.memory_analysis().temp_size_in_bytes < 64e6
    assert relays(text) == []
    chunk = compiled("chunk_final")
    assert chunk.memory_analysis().temp_size_in_bytes < 256e6
    assert relays(chunk.as_text()) == []
    # 32 heads of 256 queries are 32 KB of float32 scores a key position, under the size
    # the chunk kernel is given (``models/patterned.py chunk_walks``): the walk stays
    assert "latent_chunk_attention" not in chunk.as_text()
    # the guard sees the copy where the leaf is head-major as the other models' are
    from ray_tpu.models import llama

    monkeypatch.setattr(llama, "EMBED_MINOR", llama.HEAD_MAJOR)
    assert relays(compiled("decode_step").as_text())


@pytest.mark.parametrize("served", sorted(_SERVED))
def test_the_engines_decode_program_is_decode_step_and_the_one_sampler(
        served, one_chip, no_compile_cache, native_kernels):
    """``jit_decode_fn`` is not touched by what groups the chunk programs:
    the engine's ``decode_fn`` compiles to the text of ``decode_step`` over
    every slot with the one sampler over its rows, spelled out here as the
    engine had it before chunk programs took rows (PR 34), source lines
    apart; since PR 50 the rows' candidates are ``ops/topk.py top_k``'s, of
    all rows at once (at these widths its two stages), and the draw is mapped
    over the rows."""
    from ray_tpu.models.llama import decode_step
    from ray_tpu.ops import topk

    cfg = _served_config(served)
    fn, donated, args = _engine_programs(served, one_chip)["decode_fn"]
    K = min(64, cfg.vocab_size)
    assert topk.two_stage(cfg.vocab_size, K)

    def draw(greedy, vals, idxs, temp, top_k, key):
        rank_ok = jnp.arange(K) < top_k
        scaled = jnp.where(rank_ok, vals / jnp.maximum(temp, 1e-6), -jnp.inf)
        key, sub = jax.random.split(key)
        sampled = idxs[jax.random.categorical(sub, scaled)]
        tok = jnp.where(temp <= 0.0, greedy, sampled).astype(jnp.int32)
        return tok, key

    def decode_fn(params, cache, tokens, temps, top_ks, keys):
        if cfg.moe_experts:
            cache = dict(cache, moe_stats=jnp.zeros((4,), jnp.int32))
        logits, cache = decode_step(params, cache, tokens, cfg)
        stats = cache.pop("moe_stats", None)
        with jax.named_scope("sampling"):
            next_tokens, new_keys = jax.vmap(draw)(
                jnp.argmax(logits, -1), *topk.top_k(logits, K), temps, top_ks, keys)
        return next_tokens, cache, new_keys, stats

    def same(text):  # a Mosaic kernel's bytecode names source lines too
        import re

        return _same_program(re.sub(r"backend_config=\{.*?\}(?=[,)\s]|$)", "", text, flags=re.M))

    assert same(_engine_text((fn, donated, args))) == same(_engine_text((decode_fn, donated, args)))
