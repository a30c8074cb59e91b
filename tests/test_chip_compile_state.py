"""The decode steps of the models that keep a state a slot (state-space, delta-
rule, Granite's) move it where it lies, the state-space final chunk fits, and
the other families hold nothing of that path; compiled at real widths for a
described v5e (``tests/chip_compile.py`` says how, and what that proves)."""

import re

import jax
import jax.numpy as jnp
import pytest

from tests.chip_compile import (
    _SERVED,
    _delta_rule_cut,
    _granite_whole,
    _served_config,
    _served_programs,
    _state_space_cut,
    native_kernels,
    no_compile_cache,
    one_chip,
)


def _fusions_on_a_layer_of_the_state(text, scope="ssm_step"):
    """The fusions under ``scope`` with a layer of the Nemotron cut's state
    among their operands or results, in any view of its heads
    ([64, 128, 64, 128], [64, 8, 16, 64, 128], with or without the layers in
    front)."""
    import re

    a_layer = re.compile(r"f32\[(?:\d+,)?64,(?:128|8,16),64,128\]")
    fusions = {m.group(1): line for line in text.splitlines()
               if (m := re.search(r" fusion\(.*calls=(%[\w.\-]+)", line)) and scope in line}
    computation, found = None, set()
    for line in text.splitlines():
        if line and not line[0].isspace():
            computation = line.split()[0]
        elif computation in fusions and a_layer.search(line):
            found.add(computation)
    return sorted(fusions[c].strip()[:160] for c in found)


def test_state_space_decode_step_moves_its_state_where_it_lies(
        one_chip, no_compile_cache, native_kernels, monkeypatch):
    """The Nemotron-3-Super cell's decode step (64 slots of 2,048; 5 Mamba-2
    blocks, 5 expert blocks holding 128 of 512 experts, one GQA block, at
    published widths) compiles for the chip beside 9.3 GB of weights: the 1.3
    GB of float32 state is updated in the donated cache (no copy of the leaf,
    temporaries far under one layer's 0.27 GB) by one ``ssm_step`` kernel a
    block on the leaf whole, and no fusion under that scope reads or writes a
    layer of the state (XLA's own two made three passes over it: PERF.md
    section 6, PR 38); the held banks go through the grouped-matmul kernels
    whole, and the attention block reads its stripe through the decode
    kernel."""
    from ray_tpu.ops import ssm

    def compiled():
        fn, args = _served_programs(_state_space_cut(), 64, 2048, one_chip)["decode_step"]
        return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()

    step = compiled()
    text = step.as_text()
    state = "f32[5,64,128,64,128]"
    assert [line.strip()[:120] for line in text.splitlines()
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(state)] == []
    assert step.memory_analysis().temp_size_in_bytes < 128e6
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("moe_ffn/experts" in line for line in kernels) >= 2  # up and down, relu^2 between
    assert sum("attn_core" in line and "ssm_mixer" not in line for line in kernels) == 1
    assert sum("attn_core/ssm_mixer/ssm_step" in line for line in kernels) == 5
    assert _fusions_on_a_layer_of_the_state(text) == []
    for scope in ("ssm_mixer/ssm_step", "ssm_mixer/ssm_conv", "moe_ffn/moe_latent_proj"):
        assert scope in text, scope
    # every token of a decode step is real: the convolution's next tail is a
    # slice of its inputs, not a gather by each row's own end
    assert [line.strip()[:120] for line in text.splitlines()
            if " gather(" in line and "ssm_conv" in line] == []
    # the guard sees XLA's two fusions where the plain line runs
    monkeypatch.setattr(ssm, "step_groups", lambda *a: None)
    assert len(_fusions_on_a_layer_of_the_state(compiled().as_text())) >= 2


def test_state_space_final_chunk_fits_at_its_widest(one_chip, no_compile_cache, native_kernels):
    """The cell's widest final chunk (1,024 tokens into one stripe, the
    chunked scan over eight 128-token chunks a block): temporaries under 0.6
    GB beside the weights and a 1.5 GB pool."""
    from ray_tpu.models.llama import prefill

    cfg = _state_space_cut()
    _, (params, stripe, _, n, s) = _served_programs(cfg, 64, 2048, one_chip)["chunk_final"]
    tokens = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, o, t, n, s: prefill(p, o, t, cfg, lengths=n, start_pos=s), donate_argnums=(1,)
    ).lower(params, stripe, tokens, n, s).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 600e6
    assert "ssm_mixer/ssm_scan" in compiled.as_text()


@pytest.mark.parametrize("served", sorted(_SERVED) + ["kanana-2-30b-a3b-serve-l5"])
def test_the_other_families_decode_steps_hold_nothing_of_the_state_space_path(
        served, one_chip, no_compile_cache, native_kernels):
    """A model whose blocks all have attention and a feed-forward carries keys,
    values and lengths alone through its decode step: no state-space scope, no
    latent projection of experts, no fifth routing count."""
    cfg = _served_config(served)
    slots, stripe = {"kanana-2-30b-a3b-serve-l5": (24, 24576)}.get(served) or _SERVED[served][:2]
    fn, args = _served_programs(cfg, slots, stripe, one_chip)["decode_step"]
    assert set(args[1]) == {"k", "v", "length"}
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
    # the operations, not the table of source files behind them: an inner
    # ``jit`` keeps the files that traced it first, and this process may have
    # run ``tests/test_kda_shares.py`` or ``tests/test_ssm_step.py`` before
    ops = text.split("\nFileNames", 1)[0]
    assert "ssm_" not in ops and "kda_" not in ops and "moe_latent_proj" not in ops


def test_delta_rule_decode_step_moves_its_state_where_it_lies(
        one_chip, no_compile_cache, native_kernels):
    """The Solar-Open2 cell's decode step (64 slots of 8,192; three delta-rule
    layers and one gated attention layer, each with 40 of 320 experts held, at
    published widths) compiles for the chip beside 6.6 GB of weights: the 0.8
    GB of float32 state is updated in the donated cache (no copy of the leaf,
    temporaries far under one layer's 0.27 GB) by one ``kda_step`` kernel a
    layer on the leaf whole, under ``kda_mixer``; the held banks go through
    the grouped-matmul kernels whole, the attention layer reads its stripe
    through the decode kernel and its gate is a channel's."""
    fn, args = _served_programs(_delta_rule_cut(), 64, 8192, one_chip)["decode_step"]
    assert set(args[1]) == {"k", "v", "length", "kda_state", "kda_conv"}
    step = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = step.as_text()
    state = "f32[3,64,64,128,128]"
    assert [line.strip()[:120] for line in text.splitlines()
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(state)] == []
    assert step.memory_analysis().temp_size_in_bytes < 128e6
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("moe_ffn/experts" in line for line in kernels) >= 3  # gate, up and down
    assert sum("attn_core" in line and "kda_mixer" not in line for line in kernels) == 1
    assert sum("attn_core/kda_mixer/kda_step" in line for line in kernels) == 3
    for scope in ("attn_qkv/kda_mixer", "kda_mixer/kda_step", "kda_mixer/kda_conv",
                  "attn_out/kda_mixer", "attn_out/gate"):
        assert scope in text, scope
    # every token of a decode step is real: the convolutions' next tail is a
    # slice of their inputs, not a gather by each row's own end
    assert [line.strip()[:120] for line in text.splitlines()
            if " gather(" in line and "kda_conv" in line] == []


def test_granite_decode_step_compiles_whole_and_moves_its_state_where_it_lies(
        one_chip, no_compile_cache, native_kernels):
    """The Granite cell's decode step, the model whole (24 slots of 4,096; 36
    Mamba-2 and 4 GQA layers at published widths, four periods of ten under
    one loop) compiles for the chip beside 6.4 GB of weights: the 1.8 GB of
    float32 state is updated in the donated cache by one ``ssm_step`` kernel a
    mamba layer of a period (nine: a tile is the one group's 64 heads, 2 MB),
    and the attention layers, whose heads are 64 wide, half a lane tile, keep
    the einsum over their stripes (the decode kernel's copies take no part of
    a lane tile: the chip's compiler refused it, "slice shape along dimension
    4 must be aligned to tiling (128), but is 64")."""
    fn, args = _served_programs(_granite_whole(), 24, 4096, one_chip)["decode_step"]
    step = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = step.as_text()
    state = "f32[36,24,64,64,128]"
    assert [line.strip()[:120] for line in text.splitlines()
            if " copy(" in line and line.split(" = ", 1)[-1].startswith(state)] == []
    assert step.memory_analysis().temp_size_in_bytes < 256e6
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("attn_core/ssm_mixer/ssm_step" in line for line in kernels) == 9
    assert sum("attn_core" in line and "ssm_mixer" not in line for line in kernels) == 0
