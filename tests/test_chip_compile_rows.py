"""A middle chunk of several rows copies no stripe a layer; compiled at real
widths for a described v5e (``tests/chip_compile.py`` says how, and what that
proves)."""

import re

import pytest

from tests.chip_compile import (
    _SERVED,
    _engine_programs,
    _engine_text,
    _ops_outside_fusions,
    _served_config,
    native_kernels,
    no_compile_cache,
    one_chip,
)


def _whole_stripe_ops(text, layers, stripe, heads=8, width=128):
    """(computation, operation) of everything outside a fusion's own
    computation whose result has the shape of whole scratch stripes
    (``[layers, rows, 8, stripe, 128]``) or of a layer of them."""
    import re

    shape = re.compile(r"\[(?:%d,)?\d+,%d,%d,%d\]" % (layers, heads, stripe, width))
    return [
        (computation, op) for computation, result, op, _ in _ops_outside_fusions(text)
        if shape.search(result) and not op.endswith("-done")
        and op not in ("parameter", "get-tuple-element", "tuple", "while", "bitcast")
    ]


# whole-stripe operations that are not a layer's in-place block write, more in
# the program of several rows than in the 1-row one: the copies that stack the
# rows' stripes (keys and values) and hand each row's back
_STACKING_COPIES = {
    ("mistral-7b-serve-l16", 2): 5, ("mistral-7b-serve-l16", 4): 7,
    ("laguna-xs.2-serve-l5", 2): 9, ("laguna-xs.2-serve-l5", 4): 23,
}


@pytest.mark.parametrize("rows", [2, 4])
@pytest.mark.parametrize("served", sorted(_SERVED))
def test_a_middle_chunk_of_several_rows_copies_no_stripe_a_layer(
        served, rows, one_chip, no_compile_cache, native_kernels):
    """The engine's ``chunk_mid`` with two and with four rows of 256 tokens
    at the serving cells' shapes: the rows' scratch stripes are stacked once
    a launch and handed back once, and the layers write into the stack in
    place. Outside the in-place block writes (two a row and traced layer, as
    in the 1-row program) the program holds at most ``_STACKING_COPIES`` more
    whole-stripe operations than the 1-row one: a number that follows the
    rows and not the layers (a copy a layer would add 16 in Mistral's cell, 5
    in Laguna's, a tensor and row; an undonated stripe cost 0.64 ms a layer on
    the chip, PERF.md section 6, PR 27; Laguna's count holds the pieces the
    compiler moves a stripe in). In Mistral's cell the layers are one
    loop body, which holds nothing but those writes."""
    cfg = _served_config(served)
    stripe = _SERVED[served][1]

    def ops(n):
        text = _engine_text(_engine_programs(served, one_chip, n)["chunk_mid"])
        return _whole_stripe_ops(text, cfg.n_layers, stripe)

    def copies(found):
        return [op for _, op in found if op != "dynamic-update-slice"]

    one, several = ops(1), ops(rows)
    assert len(copies(several)) - len(copies(one)) <= _STACKING_COPIES[served, rows], (one, several)
    writes = lambda found: sum(op == "dynamic-update-slice" for _, op in found)  # noqa: E731
    assert writes(several) == rows * writes(one)
    if not cfg.layer_types:  # one loop body for all layers
        in_loop = [op for computation, op in several if "region" in computation]
        assert in_loop == ["dynamic-update-slice"] * 2 * rows, several
