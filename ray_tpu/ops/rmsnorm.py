"""Fused RMSNorm Pallas kernel (forward + custom VJP).

One VMEM pass per row-block: mean-square reduction, rsqrt, scale, and the
weight multiply — no intermediate [rows, features] tensors round-tripping
through HBM. Backward recomputes the cheap rsqrt from the saved input
(remat-friendly: nothing but x and w is saved).

Layout: rows on the grid, features resident in VMEM (d_model ≤ a few K for
the models here; one feature row is far under the 16MB VMEM budget).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops._common import interpret, pad_rows, pick_block


def _fwd_kernel(x_ref, w_ref, o_ref, *, eps: float):
    # all math in fp32; cast to the OUTPUT dtype last so mixed-precision
    # inputs (bf16 x, fp32 w) never promote past the pinned out ref dtype
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(ms + eps)
    o_ref[:] = (x * scale * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_fwd_2d(x2, w, eps):
    if x2.shape[0] == 0:
        return x2
    x2, orig_rows = pad_rows(x2)
    rows, d = x2.shape
    block = pick_block(rows, d)
    # all refs 2-D: 1-D operands hit XLA/Mosaic layout mismatches on TPU
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
        interpret=interpret(),
    )(x2, w.reshape(1, d))
    return out[:orig_rows]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x, w, eps: float = 1e-5):
    """rmsnorm(x) * w over the last axis; any leading batch shape."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    out = _rmsnorm_fwd_2d(x.reshape(-1, d), w, eps)
    return out.reshape(*lead, d)


def _fwd(x, w, eps):
    return rmsnorm(x, w, eps), (x, w)


def _bwd(eps, res, g):
    # dx closed form: with s = rsqrt(ms+eps), y = x*s*w:
    #   dx = s * (g*w) - x * s^3 / d * sum(g*w*x)
    x, w = res
    xf = x.astype(jnp.float32)
    gf = (g * w).astype(jnp.float32)
    d = x.shape[-1]
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    s = jax.lax.rsqrt(ms + eps)
    dot = jnp.sum(gf * xf, axis=-1, keepdims=True)
    dx = (s * gf - xf * (s**3) * dot / d).astype(x.dtype)
    dw = jnp.sum(
        (g * (xf * s).astype(g.dtype)).reshape(-1, d), axis=0
    ).astype(w.dtype)
    return dx, dw


rmsnorm.defvjp(_fwd, _bwd)
