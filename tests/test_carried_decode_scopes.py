"""What a chunk launch carried has a name of its own: the operations that
only the pool's decode rows run lie under one more ``jax.named_scope`` part,
``beside``, in front of the name they have in ``decode_fn``
(``models/patterned.py _Rows.scope``; ``llm/engine.py programs``
``sample_riders``), so that a device trace can tell a carried step's own time
from the chunk's (``benchmark/carried.py``). Here the lowered carrying
``chunk_final`` and one-row ``chunk_mid`` of each family that carries, read
with their locations; a name is metadata, so without locations their text is
the parent's. The subject: ``tests/test_carried_decode.py``."""

import collections
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark import carried, scopes
from ray_tpu.llm.engine import programs
from ray_tpu.models.llama import LlamaConfig, init_kv_cache, init_params

SLOTS, STRIPE, CHUNK = 4, 128, 16
# family -> (preset, what its rows' read of their cache or state is named)
FAMILIES = {
    "dense": ("tiny", "attn_core"),
    "windowed-experts": ("laguna_tiny", "attn_core/window"),
    "state-space": ("nemotron_tiny", "attn_core/ssm_mixer/ssm_step"),
    "delta-rule": ("solar_tiny", "attn_core/kda_mixer/kda_step"),
    "convolutional-tails": ("zaya_tiny", "attn_qkv/cca_conv"),
    # generation by diffusion over blocks: the rows are a forward of a block a slot
    "blocks": ("sdar_tiny", "attn_core/block"),
}
# the first 16 hex digits of the SHA-256 of each carrying form's StableHLO
# without locations (``lower(..).as_text()``) as the parent commit (PR 52)
# lowered it, taken from a checkout of the parent with this file's ``_lowered``
_PARENT = {
    ("dense", "chunk_final"): "ef74b26375622778",
    ("dense", "chunk_mid"): "adb578fed44663f6",
    ("windowed-experts", "chunk_final"): "ed671625db43d9ea",
    ("windowed-experts", "chunk_mid"): "4c1fc7c730080012",
    ("state-space", "chunk_final"): "5cd2491c7ea977a1",
    ("state-space", "chunk_mid"): "4be34e7198b8636b",
    ("delta-rule", "chunk_final"): "78830791e03be437",
    ("delta-rule", "chunk_mid"): "37d39aafb2cac1f1",
    ("convolutional-tails", "chunk_final"): "a5c80515f49adf17",
    ("convolutional-tails", "chunk_mid"): "0d881a78bb076337",
    # a block pool's chunk programs carry since PR 56: its own text, taken there
    ("blocks", "chunk_final"): "9e7f8acc18a57b03",
    ("blocks", "chunk_mid"): "6deeb8815fa2ef6e",
}
CASES = [(family, program) for family in FAMILIES for program in ("chunk_final", "chunk_mid")]


@pytest.fixture(scope="module")
def lowered():
    """(preset, program, with the pool's rows) -> the lowered program, once a
    form: a four-slot pool, a 16-token chunk of one prompt."""
    done = {}

    def lower(preset, program, rows=True):
        if (preset, program, rows) not in done:
            done[preset, program, rows] = _lowered(getattr(LlamaConfig, preset)(), program, rows)
        return done[preset, program, rows]

    return lower


def _lowered(cfg, program, rows):
    fns, sds = programs(cfg), jax.ShapeDtypeStruct
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, SLOTS, STRIPE))
    one = jax.eval_shape(lambda: fns["new_stripe"](STRIPE))
    sampler = (sds((SLOTS,), jnp.float32), i32(SLOTS), sds((SLOTS, 2), jnp.uint32))
    riders = dict(zip(("tokens", "temps", "top_ks", "keys", "live"),
                      (i32(SLOTS), *sampler, sds((SLOTS,), jnp.bool_))))
    if cfg.block_length:  # the pool hands over its block state where the others hand over tokens
        block = jax.eval_shape(lambda: fns["new_block"](SLOTS))
        riders = dict(riders, block=block)
        del riders["tokens"]
    chunk = (i32(1, CHUNK), i32(1), i32(1))
    args = {
        "decode_fn": (params, cache, i32(SLOTS), *sampler),
        "block_step": (params, cache, riders.get("block"), *sampler),
        "chunk_mid": (params, (one,), *chunk, *((cache, riders) if rows else ())),
        "chunk_final": (params, cache, one, *chunk, i32(), sds((), jnp.float32), i32(),
                        sds((2,), jnp.uint32), *((riders,) if rows else ())),
    }[program]
    return jax.jit(fns[program]).lower(*args)


def _operations(low):
    """[(operation, its location's name path)] of a lowered program (a layer
    traced in a loop's body has a path of its own, one traced in line the
    program's in front: the readers skip ``jit(..)`` parts). An operation with
    regions (a scatter, a loop) is located where its last region closes."""
    text = low.as_text(debug_info=True)
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    out, open_ops = [], []
    for line in text.splitlines():
        op = re.search(r'= "?((?:stablehlo|chlo)\.[\w.]+)', line)
        at = re.search(r' loc\((#loc\d*|"[^"]*")[^ ]*$', line)
        if line.lstrip().startswith(("module ", "func.func ")):
            open_ops.append(None)  # closes as an operation with regions does
        elif op and not at:
            open_ops.append(op.group(1))
        elif at and (op or line.lstrip().startswith("}")):
            name = op.group(1) if op else open_ops.pop()
            path = at.group(1).strip('"') if at.group(1)[0] == '"' else named.get(at.group(1), "")
            if name is not None:
                out.append((name, path))
    assert not open_ops
    return out


def _under(ops, name, beside):
    """The histogram of the operations the benchmark's reader books to the
    scope ``name`` (``benchmark/scopes.py scope_of``), those under a
    ``beside`` part or those under none."""
    return collections.Counter(
        op for op, path in ops
        if carried.is_beside(path) == beside and scopes.scope_of(path) == name)


@pytest.mark.parametrize("family, program", CASES)
def test_a_carrying_form_without_locations_is_the_parents_text(family, program, lowered):
    """A scope is a name: the operations, their order, types and attributes
    are what the parent traced, to the letter."""
    text = lowered(FAMILIES[family][0], program).as_text()
    assert "beside" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _PARENT[family, program]


@pytest.mark.parametrize("family, program", CASES)
def test_what_the_rows_run_alone_lies_under_beside_and_nothing_of_the_chunks_does(
        family, program, lowered):
    """Every cache write, decode read, state step and rider sample of the
    pool's rows is named ``beside/<the name it has in decode_fn>``, and no
    operation of the chunk's own set: under each name both sets run, what
    lies outside ``beside`` is, operation for operation, what the chunk alone
    lowers to."""
    preset, read = FAMILIES[family]
    ops = _operations(lowered(preset, program))
    alone = _operations(lowered(preset, program, rows=False))
    assert not [path for _, path in alone if carried.is_beside(path)]
    paths = {path for _, path in ops if carried.is_beside(path)}
    # the part stands once, and in front of the names the readers know
    for parts in (path.split("/") for path in paths):
        assert parts.count("beside") == 1
        assert scopes.scope_of("/".join(parts[:parts.index("beside")])) is None
    # the rows' scatter into the pool's cache, their read, their sampler
    assert _under(ops, "kv_write", True)["stablehlo.scatter"] >= 2
    assert any("beside/" + read + "/" in path for path in paths), sorted(paths)
    assert _under(ops, "attn_core", True) and _under(ops, "sampling", True)
    # the chunk's own set: its read and (a middle chunk: a final chunk's copy
    # into the slot is another form beside rows) its write, as it runs alone
    assert _under(ops, "attn_core", False) == _under(alone, "attn_core", False)
    assert _under(ops, "sampling", False) == _under(alone, "sampling", False)
    if program == "chunk_mid":
        assert _under(ops, "kv_write", False) == _under(alone, "kv_write", False)
    if program == "chunk_mid" or family == "blocks":
        # nothing reads the chunk's rows behind the last layer (a prompt of a
        # model that generates by blocks samples nothing): the head is the rows' alone
        assert _under(ops, "lm_head", True) and not _under(ops, "lm_head", False)
    else:  # a final chunk's head multiplies the prompt's last token and the rows as one matrix
        assert _under(ops, "lm_head", False) and not _under(ops, "lm_head", True)
    # a state step is the rows', a scan the chunk's; Zaya's tails are each set's own
    for step, scan in (("ssm_step", "ssm_scan"), ("kda_step", "kda_scan")):
        assert all(carried.is_beside(path) for _, path in ops if step in path)
        assert not [path for path in paths if scan in path]
    if family == "convolutional-tails":
        assert [path for _, path in ops if "cca_conv" in path and not carried.is_beside(path)]


def _bare(path):
    """A location's name path without the parts JAX writes for a traced
    function (``jit(..)``) and without ``beside``."""
    return "/".join(part for part in path.split("/")
                    if part != carried.PART and not re.match(r"p?jit\(", part))


@pytest.mark.parametrize("program", ["chunk_final", "chunk_mid"])
def test_what_only_the_block_rows_run_is_named_as_in_the_block_step(program, lowered):
    """A pool that generates by blocks: every operation under ``beside`` in a
    carrying chunk program is, by operation and name, one ``jit_block_step``
    runs under the name without that part (the rows' scatter, the folded read
    under ``attn_core/block``, the head, ``sampling/confidence`` and
    ``sampling/unmask``: one function behind the logits for both,
    ``llm/engine.py programs block_after``), and none of the block step's own
    scopes stands outside ``beside``."""
    ops = _operations(lowered("sdar_tiny", program))
    step = {(op, _bare(path)) for op, path in _operations(lowered("sdar_tiny", "block_step"))}
    beside = {(op, _bare(path)) for op, path in ops if carried.is_beside(path)}
    assert beside and beside <= step, sorted(beside - step)[:10]
    for name in ("attn_core/block/decode_attention", "kv_write/scatter", "lm_head",
                 "sampling/confidence", "sampling/unmask"):
        assert any(name in path for _, path in beside), name
    for name in ("attn_core/block", "lm_head", "sampling"):
        assert not [path for _, path in ops if name in path and not carried.is_beside(path)], name
    assert "beside" not in lowered("sdar_tiny", "block_step").as_text(debug_info=True)


def test_a_middle_chunks_last_feed_forward_is_the_rows_alone_where_it_is_traced_on_its_own(lowered):
    """A stack whose last layer is a body of its own (``decode_forward``'s
    ``narrow``): behind its mixer a middle chunk runs the feed-forward for the
    decode rows alone, under ``beside/moe_ffn``; the layers before it multiply
    both sets' rows as one matrix and keep their name."""
    ops = _operations(lowered("laguna_tiny", "chunk_mid"))
    assert _under(ops, "moe_ffn", True) and _under(ops, "moe_ffn", False)
    assert any("beside/moe_ffn/experts/" in path for _, path in ops)
    # layers alike under one loop: every feed-forward is both sets'
    assert not _under(_operations(lowered("tiny", "chunk_mid")), "ffn", True)


@pytest.mark.parametrize("preset, program, rows", [
    *((preset, "decode_fn", True) for preset, _ in FAMILIES.values() if preset != "sdar_tiny"),
    ("kanana_tiny", "chunk_mid", False), ("kanana_tiny", "chunk_final", False),
    ("kanana_tiny", "decode_fn", True),
])
def test_the_decode_program_and_a_latent_pools_chunk_programs_hold_no_such_part(
        preset, program, rows, lowered):
    """``decode_fn``'s rows are its first set, and a latent pool's chunk
    launches take no rows (``JaxEngine.__init__``)."""
    assert "beside" not in lowered(preset, program, rows).as_text(debug_info=True)
