"""The served dense and patterned programs read a layer's projection slice in
place and the cache where it lies; compiled at real widths for a described v5e
(``tests/chip_compile.py`` says how, and what that proves)."""

import re

import pytest

from tests.chip_compile import (
    _SERVED,
    _kv_writes,
    _ops_outside_fusions,
    _program_text,
    _served_config,
    _served_programs,
    native_kernels,
    no_compile_cache,
    one_chip,
)


def _projection_slice_ops(text, e, head_width=128):
    """The operations of the entry computation and of the layer loop's body
    (not of a fusion's own computation) whose result, or one of whose
    results, has the shape of one layer's slice of a stacked attention input
    projection: ``[1, e, h, 128]`` or ``[e, h, 128]``. A matmul that reads
    the stacked leaf in place leaves none: its fusion takes the leaf whole
    and the layer's index."""
    import re

    shape = re.compile(r"\[(?:1,)?%d,\d+,%d\]" % (e, head_width))
    return [
        line.strip()[:200] for _, result, op, line in _ops_outside_fusions(text)
        if shape.search(result) and op not in ("parameter", "get-tuple-element")
        and not op.endswith("-done")
    ]


@pytest.mark.parametrize("program", ["decode_step", "chunk_mid"])
@pytest.mark.parametrize("served", sorted(_SERVED))
def test_served_programs_read_a_layers_projection_slice_in_place(
        served, program, one_chip, no_compile_cache, native_kernels):
    """``decode_step`` over every slot and the 256-token
    ``prefill(..., with_logits=False)`` at the serving cells' shapes, the
    parameters in the formats the engine's rule gives
    (``models/llama.py serving_layouts``): no operation outside a matmul's
    own fusion yields a layer's slice of ``wq``, ``wk`` or ``wv``. Under the
    default layout each slice is copied first (tiles over heads x head
    width, contraction over ``d_model``): 3 such operations in Mistral's
    decode step, 5 in its chunk, 13 and 24 in Laguna's (PERF.md section 6,
    PR 29; on the chip 1.1 of a 14.7 ms decode step)."""
    cfg = _served_config(served)
    slots, stripe, e = _SERVED[served]

    def count(relaid):
        programs = _served_programs(cfg, slots, stripe, one_chip, relaid)
        return _projection_slice_ops(_program_text(programs[program]), e)

    assert count(True) == []
    assert count(False)  # the guard sees the copies where the layout is the default


# ---- the decode step's read of the cache, and the chunk programs beside it ---


def _yields_a_layer_of_the_cache(text, slots, stripe, heads=8, width=128):
    """The operations, in any computation, whose result is one layer of the
    cache (``[slots, 8, stripe, 128]``, with or without a leading 1), and the
    copies of the whole cache."""
    import re

    layer = re.compile(r"= \w+\[(?:1,)?%d,%d,%d,%d\]\S* (?!parameter|get-tuple-element)"
                       % (slots, heads, stripe, width))
    whole = re.compile(r"= \w+\[\d+,%d,%d,%d,%d\]\S* copy\(" % (slots, heads, stripe, width))
    return [line.strip()[:160] for line in text.splitlines()
            if layer.search(line) or whole.search(line)]


@pytest.mark.parametrize("served", sorted(_SERVED))
def test_decode_step_reads_the_cache_where_it_lies(
        served, one_chip, no_compile_cache, native_kernels, monkeypatch):
    """``decode_step`` at the serving cells' shapes runs one
    ``decode_attention`` kernel a traced layer on the carried cache whole:
    nothing in the program yields a layer of the cache (the einsum's
    ``ck_all[l]`` is a 134 MB slice a tensor and layer in Mistral's cell, 268
    MB in Laguna's, read whole at any length: PERF.md section 6, PR 31) and
    nothing copies the cache."""
    from ray_tpu.models import patterned

    cfg = _served_config(served)
    slots, stripe, _ = _SERVED[served]
    text = _program_text(_served_programs(cfg, slots, stripe, one_chip)["decode_step"])
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line and "attn_core" in line]
    # one a traced layer: Mistral's stack is one loop body, Laguna's five layers
    # are the leading one and one period
    assert len(kernels) == (cfg.n_layers if cfg.layer_types else 1), kernels
    assert _yields_a_layer_of_the_cache(text, slots, stripe) == []
    # and as many calls of the write kernel (keys and values together) in the
    # place of two scatters a traced layer
    written, scattered = _kv_writes(text)
    assert (len(written), scattered) == (len(kernels), [])

    monkeypatch.setattr(patterned, "reads_blocks", lambda *a: False)
    monkeypatch.setattr(patterned, "writes_rows", lambda *a, **kw: False)
    einsum = _program_text(_served_programs(cfg, slots, stripe, one_chip)["decode_step"])
    assert _yields_a_layer_of_the_cache(einsum, slots, stripe)  # the guard sees the slices
    written, scattered = _kv_writes(einsum)  # and the scatters
    assert (written, len(scattered)) == ([], 2 * len(kernels))
