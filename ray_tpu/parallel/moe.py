"""Expert-parallel mixture-of-experts with all_to_all token routing.

The reference has no native MoE/expert parallelism (delegated to vLLM engine
kwargs, SURVEY §2.4). Here: experts are sharded over the ``ep`` mesh axis;
tokens are routed top-k with a fixed capacity (static shapes for XLA), shipped
to their experts with ``jax.lax.all_to_all`` over ICI, transformed, and
combined back weighted by router probabilities. Switch-Transformer style
dispatch/combine, dense-einsum formulation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def moe_init(key, num_experts: int, d_model: int, d_ff: int, dtype=jnp.float32):
    """Params for a SwiGLU expert bank + router."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale_in = d_model**-0.5
    scale_out = d_ff**-0.5
    return {
        "router": jax.random.normal(k1, (d_model, num_experts), dtype) * scale_in,
        "w_gate": jax.random.normal(k2, (num_experts, d_model, d_ff), dtype) * scale_in,
        "w_up": jax.random.normal(k3, (num_experts, d_model, d_ff), dtype) * scale_in,
        "w_down": jax.random.normal(k4, (num_experts, d_ff, d_model), dtype) * scale_out,
    }


def _expert_ffn(params, x):
    """x: [E_local, C_total, d] — SwiGLU per expert."""
    gate = jnp.einsum("ecd,edf->ecf", x, params["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", x, params["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up, params["w_down"])


def topk_gates(params, x, top_k: int):
    """Router probabilities + renormalized top-k gate values.

    The single source of truth for the gate math — shared by the capacity
    path below and the dropless serving path
    (``models/patterned._moe_decode_ffn``); the decode-vs-forward exactness test
    pins the two staying numerically identical.

    Two scorings. A router of ``router`` alone: softmax over the experts,
    the k largest, renormalised. A router that also has a selection ``bias``
    [E] (DeepSeek-V3's ``noaux_tc`` without groups): the sigmoid of each
    logit, the top k chosen by score plus bias, which is no part of their
    weights: those are the chosen scores themselves over their sum.

    At ``top_k`` 1 the renormalised weight is 1 whatever the router scored:
    the layer's output then ignores the router's confidence. No served model
    routes one expert through this function; the one that does route one
    (ZAYA1: an MLP router with a stream of its own through the depth, the
    chosen expert weighted by its probability itself) goes through
    ``models/patterned.py _mlp_route``, which renormalises only where k > 1.

    Returns (probs [G, E] f32, gate_vals [G, k] f32, gate_idx [G, k])."""
    logits = (x @ params["router"]).astype(jnp.float32)  # [G, E]
    if "bias" in params:
        probs = jax.nn.sigmoid(logits)
        _, gate_idx = jax.lax.top_k(probs + params["bias"].astype(jnp.float32), top_k)
        gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # [G, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, gate_idx


def _route(params, x, num_experts: int, top_k: int, capacity: int):
    """Shared top-k routing: dispatch/combine one-hot tensors + aux loss
    inputs. Single source of truth for the routing math — ``_moe_local``
    (sharded) and ``moe_dense`` must stay numerically identical.

    Returns (disp [G,E,C], comb [G,E,C], aux scalar).
    """
    G, d = x.shape
    E, C = num_experts, capacity

    probs, gate_vals, gate_idx = topk_gates(params, x, top_k)

    # Position of each (token, choice) within its expert's capacity buffer.
    onehot_e = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # [G, k, E]
    flat = onehot_e.reshape(G * top_k, E)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(G, top_k, E)
    pos = (pos_in_expert * onehot_e).sum(-1)  # [G, k]
    keep = (pos < C).astype(x.dtype)  # drop overflow beyond capacity

    oe = onehot_e.astype(x.dtype)  # [G, k, E]
    oc = jax.nn.one_hot(pos, C, dtype=x.dtype)  # [G, k, C]
    # dispatch[g,e,c]: token g occupies slot c of expert e.
    disp = jnp.einsum("gke,gkc,gk->gec", oe, oc, keep)
    # combine[g,e,c]: dispatch weighted by (renormalized) gate value.
    comb = jnp.einsum("gke,gkc,gk->gec", oe, oc, keep * gate_vals.astype(x.dtype))

    # Aux load-balancing loss (Switch style): mean_prob · mean_assignment.
    me = probs.mean(axis=0)  # [E]
    ce = onehot_e.astype(jnp.float32).sum(axis=1).mean(axis=0)  # [E]
    aux = (me * ce).sum() * E
    return disp, comb, aux


def _moe_local(params, x, *, axis_name: str, num_experts: int, top_k: int, capacity: int, token_axes: tuple = ()):
    """Per-device body under shard_map.

    x: [G_local, d] local tokens; experts sharded over ``axis_name``
    (params' leading expert dim is E_local = E / ep locally).
    """
    ep = jax.lax.psum(1, axis_name)
    G, d = x.shape
    E = num_experts
    C = capacity

    E_l = E // ep

    disp, comb, aux = _route(params, x, E, top_k, C)
    expert_in = jnp.einsum("gd,gec->ecd", x, disp)  # [E, C, d]

    # Ship buffers to expert owners over ICI. Symmetric untiled all_to_all on
    # the leading (destination-device) dim is its own inverse.
    a = expert_in.reshape(ep, E_l, C, d)
    b = jax.lax.all_to_all(a, axis_name, split_axis=0, concat_axis=0, tiled=False)
    # b: [ep(src), E_l, C, d] -> [E_l, ep*C, d]
    expert_tokens = b.transpose(1, 0, 2, 3).reshape(E_l, ep * C, d)

    out = _expert_ffn(params, expert_tokens)  # [E_l, ep*C, d]

    back = out.reshape(E_l, ep, C, d).transpose(1, 0, 2, 3)  # [ep(dst), E_l, C, d]
    ret = jax.lax.all_to_all(back, axis_name, split_axis=0, concat_axis=0, tiled=False)
    returned = ret.reshape(E, C, d)

    y = jnp.einsum("ecd,gec->gd", returned, comb)

    # psum the aux loss over token shards so every device sees the global
    # value (the routing itself computed the local-shard statistic).
    if token_axes:
        aux = jax.lax.pmean(aux, axis_name=token_axes)
    return y, aux


def moe_dense(
    params,
    x,
    *,
    num_experts: int,
    top_k: int = 2,
    capacity_factor: float = 1.25,
):
    """Single-device (no mesh / ep=1) evaluation of the same routed MoE:
    identical dispatch/combine math as ``_moe_local`` minus the all_to_all,
    so MoE configs run unchanged on one chip or an ep=1 mesh.

    x: [tokens, d] -> (y: [tokens, d], aux scalar).
    """
    C = max(1, int(capacity_factor * x.shape[0] * top_k / num_experts))
    disp, comb, aux = _route(params, x, num_experts, top_k, C)
    expert_in = jnp.einsum("gd,gec->ecd", x, disp)
    out = _expert_ffn(params, expert_in)
    y = jnp.einsum("ecd,gec->gd", out, comb)
    return y, aux


def moe_layer(
    params,
    x,
    mesh: Mesh,
    *,
    axis_name: str = "ep",
    num_experts: int,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    x_spec: Optional[P] = None,
    tokens_axis_names: tuple = ("dp", "sp"),
):
    """Apply an expert-parallel MoE FFN.

    Args:
      params: from ``moe_init`` — expert dim sharded over ``axis_name``.
      x: [tokens, d_model] (token dim sharded over ``tokens_axis_names``).
    Returns (y: [tokens, d_model], aux_loss scalar).
    """
    ep = mesh.shape[axis_name]
    if num_experts % ep:
        raise ValueError(f"num_experts {num_experts} not divisible by ep={ep}")
    token_axes = tuple(a for a in tokens_axis_names if a in mesh.axis_names and mesh.shape[a] > 1)
    if x_spec is None:
        x_spec = P(token_axes if token_axes else None, None)
    n_token_shards = 1
    for a in token_axes:
        n_token_shards *= mesh.shape[a]
    local_tokens = x.shape[0] // max(n_token_shards, 1)
    capacity = max(1, int(capacity_factor * local_tokens * top_k / num_experts))

    params_spec = {
        "router": P(None, None),
        "w_gate": P(axis_name, None, None),
        "w_up": P(axis_name, None, None),
        "w_down": P(axis_name, None, None),
    }
    fn = jax.shard_map(
        functools.partial(
            _moe_local,
            axis_name=axis_name,
            num_experts=num_experts,
            top_k=top_k,
            capacity=capacity,
            token_axes=token_axes,
        ),
        mesh=mesh,
        in_specs=(params_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    y, aux = fn(params, x)
    return y, aux
