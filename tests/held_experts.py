"""An expert layer that holds a share of its experts, under routings made to
order: what ``tests/test_kda_shares.py`` and ``tests/test_ssm_shares.py``
share to hold the block form of ``models/patterned.py _moe_decode_ffn``
against the form that works on every assignment (the one a model that holds
all its experts keeps, which is the parent's program operation for
operation)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import patterned
from ray_tpu.models.llama import init_params

BANKS = ("moe_w_gate", "moe_w_up", "moe_w_down")
# what fell on the held experts, by the block's rows C and all G*k made
HELD = {
    "every-assignment": lambda C, made: made,
    "none": lambda C, made: 0,
    "a-block-exactly": lambda C, made: C,
    "a-block-and-one": lambda C, made: C + 1,
}


def routing(cfg, tokens: int, on_held: int) -> np.ndarray:
    """Choices [tokens, k] of which ``on_held`` in all fall on the experts
    ``cfg`` holds, spread evenly over the tokens, the rest on absent ones; a
    token's choices are distinct and the experts take turns."""
    E, k, held, first = cfg.moe_experts, cfg.moe_top_k, cfg.moe_experts_held, cfg.moe_experts_first
    absent = [e for e in range(E) if not first <= e < first + held]
    out = np.empty((tokens, k), np.int32)
    for g in range(tokens):
        here = on_held // tokens + (g < on_held % tokens)
        assert here <= min(k, held) and k - here <= len(absent)
        out[g, :here] = first + (g + np.arange(here)) % held
        out[g, here:] = [absent[(g + i) % len(absent)] for i in range(k - here)]
    return out


def forced(monkeypatch, choices: np.ndarray):
    """The router's choice replaced by ``choices``, with weights of its own
    for each, renormalised a token."""
    vals = np.random.default_rng(3).uniform(0.2, 1.0, choices.shape).astype(np.float32)
    vals /= vals.sum(-1, keepdims=True)
    monkeypatch.setattr(
        "ray_tpu.parallel.moe.topk_gates",
        lambda params, x, k: (None, jnp.asarray(vals), jnp.asarray(choices)))


def held_against_every_row(cfg, tokens: int, row: int = 2):
    """(the layer of ``cfg``'s share, its counts by name, the same routing
    through the form that works on every assignment with the absent experts'
    down matrices zero, the shared expert alone) on ``tokens`` unit rows."""
    whole = dataclasses.replace(cfg, moe_experts_held=0, moe_experts_first=0)
    params = init_params(jax.random.PRNGKey(5), whole)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, tokens, cfg.d_model))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_eps)
    here = slice(cfg.moe_experts_first, cfg.moe_experts_first + cfg.moe_experts_held)
    share = {**params, **{k: params[k][:, here] for k in BANKS if k in params}}
    y, stats = patterned._moe_decode_ffn(share, row, h, cfg)
    down = jnp.zeros_like(params["moe_w_down"]).at[:, here].set(params["moe_w_down"][:, here])
    want, _ = patterned._moe_decode_ffn({**params, "moe_w_down": down}, row, h, whole)
    shared = patterned._shared_expert(
        {k: params[k][row] for k in params if k.startswith("moe_shared_")}, h[0])
    return y[0], dict(zip(patterned.moe_stats_names(cfg), np.asarray(stats))), want[0], shared


def counts_of(cfg, choices: np.ndarray) -> dict:
    """The four counts the parent's form made of ``choices``."""
    first, held = cfg.moe_experts_first, cfg.moe_experts_held
    load = np.bincount(choices.reshape(-1), minlength=cfg.moe_experts)[first:first + held]
    return {"assignments": choices.size, "assignments_held": load.sum(),
            "experts_touched": (load > 0).sum(), "max_expert_load": load.max()}


def check_a_block_at_a_time(cfg, tokens: int, block: int, fell: str, monkeypatch, atol: float):
    """``cfg``'s share under the routing ``HELD[fell]`` of ``tokens`` tokens,
    whose block is ``block`` sorted rows: the layer is what the form that
    works on every row gives, token for token, the blocks counted are the
    blocks that held anything (one where nothing did), and the other counts
    are what that form made of the same choices."""
    made = tokens * cfg.moe_top_k
    assert patterned.held_block(made, cfg.moe_experts_held, cfg.moe_experts) == block < made
    on_held = HELD[fell](block, made)
    choices = routing(cfg, tokens, on_held)
    forced(monkeypatch, choices)
    y, counts, want, shared = held_against_every_row(cfg, tokens)
    np.testing.assert_allclose(y, want, atol=atol, rtol=1e-5)
    assert counts.pop("layer_steps") == 1
    assert counts.pop("passes") == max(1, -(-on_held // block)) == {
        "every-assignment": made // block, "none": 1, "a-block-exactly": 1, "a-block-and-one": 2}[fell]
    assert counts == counts_of(cfg, choices) and counts["assignments_held"] == on_held
    if fell == "none":
        np.testing.assert_allclose(y, shared, atol=1e-6)
    else:
        assert float(jnp.abs(y - shared).max()) > 1e-3
