"""A prompt chunk's read of a latent cache through the Pallas kernel
(``ops/latent_chunk_attention.py``, interpreted here) against the walk in plain
XLA that it replaces and that stays as its fallback, so it is the oracle:
``models/patterned.py _latent_reader``'s ``read`` under both answers of
``chunk_walks``, on the same queries, cache and mask. And the one place that
decides between them. The kernel at real widths for a described chip:
``tests/test_chip_compile_dots3.py``."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import patterned
from ray_tpu.models.llama import LlamaConfig, init_kv_cache, init_params
from ray_tpu.ops import latent_chunk_attention as kernel

STRIPE = 640  # five key blocks of 128
MODELS = {"kanana": LlamaConfig.kanana_tiny(), "dots3": LlamaConfig.dots3_tiny()}


@pytest.fixture(autouse=True)
def any_size(monkeypatch):
    """The tiny models' scores are far under the size ``chunk_walks`` gives
    the kernel; the tests of the size itself ask the served configurations."""
    monkeypatch.setattr(patterned, "_CHUNK_KERNEL_MIN_SCORE_BYTES", 0)


@pytest.fixture(scope="module")
def params():
    return {name: init_params(jax.random.PRNGKey(3), cfg) for name, cfg in MODELS.items()}


def _context(cfg, params, kind, starts, T, walk, monkeypatch, indexed):
    """Every head's context [B, T, H, v] of ``T`` queries a row from
    ``starts`` over a seeded cache, read as ``walk`` says."""
    B = len(starts)
    key = jax.random.PRNGKey(T + 7 * B + starts[0])
    cache = init_kv_cache(cfg, B, STRIPE)
    names = patterned._STRIPES_OF_KIND[kind]
    for i, name in enumerate((*names, "k_index") if indexed else names):
        cache[name] = jax.random.normal(jax.random.fold_in(key, i), cache[name].shape)
    positions = jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(T)[None, :]
    d = patterned.latent_dims(cfg, kind)
    H = {k: h for k, h, _ in patterned.plan(cfg).kinds}[kind]
    q = (jax.random.normal(jax.random.fold_in(key, 8), (B, T, H, d.nope)),
         jax.random.normal(jax.random.fold_in(key, 9), (B, T, H, d.rope)))
    if walk == "einsum":
        monkeypatch.setattr(patterned, "chunk_walks", lambda cfg, *a: dict.fromkeys(
            patterned._LATENT_KINDS, "einsum"))
    lay = types.SimpleNamespace(kind=kind, attn_i=1)
    read, select, absorbed = patterned._latent_reader(cfg, params, cache, positions)
    chosen = ()
    if indexed:  # the mask ``_kept`` makes of the index scores: 8 positions a query
        index = (jax.random.normal(jax.random.fold_in(key, 10),
                                   (B, T, cfg.index_heads, cfg.index_head_dim)),
                 jax.random.uniform(jax.random.fold_in(key, 11), (B, T, cfg.index_heads)))
        chosen = (select(index, cache["k_index"], lay),)
        assert int(chosen[0].sum(-1).max()) == cfg.index_topk
    out = read(q, cache[names[0]], cache[names[1]], lay, *chosen)
    assert walk == "einsum" or absorbed[kind] == (not patterned._chunk_expands(cfg, T, kind))
    if absorbed[kind]:
        out = jnp.einsum("bthr,hrv->bthv", out, params[f"wuv_{kind}"][1])
    return out


_ROWS = {"one-row": (300,), "two-rows": (37, 381), "nothing-cached": (0,)}


@pytest.mark.parametrize("form", ("absorbed", "expanded"))
@pytest.mark.parametrize("T, rows", [
    (32, "two-rows"), (64, "nothing-cached"), (128, "one-row"), (256, "two-rows")])
@pytest.mark.parametrize("model, kind", [
    ("kanana", "latent"), ("dots3", "latent"), ("dots3", "latent_sliding")])
def test_the_kernel_reads_what_the_walk_reads(monkeypatch, params, model, kind, T, rows, form):
    """Plain latent layers (Kanana's), indexed ones under ``_kept``'s mask of 8
    positions a query, sliding ones (a window of 5: the walk starts in the
    block the earliest query's window starts in); every chunk bucket; rows
    that start in different key blocks; a first chunk with nothing cached,
    whose first query sees one position, its own; both forms at every width
    (the rule, ``_chunk_expands``, is put aside: the kernel and the walk take
    the form given, as ``benchmark/tools/latent_chunk_forms.py`` gives it)."""
    cfg, starts = MODELS[model], _ROWS[rows]
    indexed = model == "dots3" and kind == "latent"
    monkeypatch.setattr(patterned, "_chunk_expands", lambda cfg, T, *kind: form == "expanded")
    assert patterned.chunk_walks(cfg, STRIPE, T, params[model]["embed"])[kind] == "kernel"
    got = _context(cfg, params[model], kind, starts, T, "kernel", monkeypatch, indexed)
    want = _context(cfg, params[model], kind, starts, T, "einsum", monkeypatch, indexed)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_the_kernel_expands_one_tile_of_queries_of_whole_lane_tiles(monkeypatch):
    """Where the rule asks for the expanded form and the kernel has none for
    the shapes, the kernel stays and is absorbed: a chunk of several query
    tiles (a second tile would expand every block again), and on the chip a
    head whose keys are no whole lane tiles (dots3-note-prev's sliding layers:
    192 numbers a head)."""
    dots3 = LlamaConfig.dots3_note_prev(n_layers=5)
    full, sliding = (patterned.latent_dims(dots3, kind) for kind in patterned._LATENT_KINDS)
    assert kernel.expands(128, 256, 24576, full) and not kernel.expands(128, 512, 24576, full)
    assert kernel.expands(64, 256, 24576, sliding)  # interpreted: any width
    monkeypatch.setattr(kernel, "interpret", lambda: False)
    assert kernel.expands(128, 256, 24576, full) and not kernel.expands(64, 256, 24576, sliding)


def test_a_block_no_query_of_a_tile_sees_costs_no_matrix_work():
    """The bounds the kernel walks between, a tile of queries: up to the block
    of the tile's last query, under a window from the block the first query's
    window starts in; never past the stripe."""
    positions = jnp.asarray([[100 + t for t in range(64)], [600 + t for t in range(64)]])
    lo, hi = kernel._bounds(positions, 32, 128, 5, None)
    assert lo.tolist() == [0, 0, 0, 0] and hi.tolist() == [1, 1, 4, 4]
    lo, hi = kernel._bounds(positions, 32, 128, 5, 5)
    assert lo.tolist() == [0, 1, 4, 4] and hi.tolist() == [1, 1, 4, 4]


def _on_a_mesh(x):
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    return jax.device_put(x, NamedSharding(mesh, P()))


@pytest.mark.parametrize("case, stripe, T, place, walk", [
    ("a-chunk-bucket", 256, 64, None, "kernel"),
    ("several-query-tiles", 1024, 512, None, "kernel"),
    ("a-mesh", 256, 64, _on_a_mesh, "einsum"),
    ("a-stripe-of-no-whole-blocks", 192, 64, None, "einsum"),
    ("a-width-of-no-whole-tiles", 256, 30, None, "einsum"),
    ("one-token-a-row", 256, 1, None, "einsum"),
])
def test_one_place_decides_kernel_or_einsum(case, stripe, T, place, walk):
    """``chunk_walks``, asked as the engine asks (the arrays themselves) and
    as the trace asks (their tracers): the kernel for a whole number of query
    tiles over a stripe of whole blocks on one device, else the walk in plain
    XLA; every latent kind of the model gets an answer."""
    cfg = MODELS["dots3"]
    leaf = jnp.zeros((4, 4))
    if place is not None:
        if len(jax.devices()) < 2:
            pytest.skip("one device")
        leaf = place(leaf)
    asked = patterned.chunk_walks(cfg, stripe, T, leaf)
    assert asked == {"latent": walk, "latent_sliding": walk}
    traced = []
    jax.jit(lambda x: (traced.append(patterned.chunk_walks(cfg, stripe, T, x)), x)[1])(leaf)
    assert traced == [asked]
    assert patterned.chunk_walks(MODELS["kanana"], stripe, T, leaf) == {"latent": walk}


@pytest.mark.parametrize("T, full, sliding, kanana", [
    (32, "einsum", "einsum", "einsum"), (64, "einsum", "einsum", "einsum"),
    (128, "kernel", "einsum", "einsum"), (256, "kernel", "kernel", "einsum")])
def test_the_kernel_is_given_scores_of_64_kb_a_key_position_or_more(monkeypatch, T, full,
                                                                    sliding, kanana):
    """The served configurations' chunk buckets over a 24,576-position stripe
    on the chip: dots3-note-prev's 128 heads from 128 queries up, its 64
    sliding heads at 256; Kanana-2's 32 heads at no width (32 KB at most: its
    chunk programs stay the walk's, PERF.md section 6, PR 52)."""
    monkeypatch.undo()  # the size as it stands
    monkeypatch.setattr(kernel, "interpret", lambda: False)
    leaf = jnp.zeros(1)
    assert patterned.chunk_walks(LlamaConfig.dots3_note_prev(n_layers=5), 24576, T, leaf) == {
        "latent": full, "latent_sliding": sliding}
    assert patterned.chunk_walks(LlamaConfig.kanana2_30b_a3b(n_layers=5), 24576, T, leaf) == {
        "latent": kanana}
