"""Int8 blockwise quantization Pallas kernels.

Per-row absmax int8 (guide pattern #19): weights stored at 1/2 the bf16
footprint (HBM capacity + bandwidth for serving); dequantize fuses the
scale multiply on the way back to bf16. Stochastic-rounding-free symmetric
quantization — adequate for inference weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops._common import interpret, pad_rows, pick_block


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    q_ref[:] = q
    s_ref[:] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, o_ref, *, out_dtype):
    q = q_ref[:].astype(jnp.float32)
    o_ref[:] = (q * s_ref[:]).astype(out_dtype)


# scales travel as [rows, 1] (2-D: 1-D operands hit XLA/Mosaic layout
# mismatches on TPU); the public API squeezes/expands at the boundary


def quantize_int8(x) -> tuple:
    """[rows, cols] float -> (int8 values, fp32 per-row scales [rows])."""
    x, orig_rows = pad_rows(x)
    rows, cols = x.shape
    block = pick_block(rows, cols)
    q, s = pl.pallas_call(
        _quant_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows, cols), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ),
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((block, cols), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((block, cols), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ),
        interpret=interpret(),
    )(x)
    return q[:orig_rows], s[:orig_rows, 0]


def dequantize_int8(q, scales, dtype=jnp.bfloat16):
    orig_rows = q.shape[0]
    q, _ = pad_rows(q)
    scales, _ = pad_rows(scales)
    rows, cols = q.shape
    block = pick_block(rows, cols)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, out_dtype=dtype),
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, cols), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, cols), lambda i: (i, 0)),
        interpret=interpret(),
    )(q, scales.reshape(rows, 1))
    return out[:orig_rows]
