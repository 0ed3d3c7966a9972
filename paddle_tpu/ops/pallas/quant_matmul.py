"""Pallas int8×int8→int32 blocked matmul with fused dequantize.

Reference capability: the cutlass-backed int8 kernels behind the PTQ
`convert` inference path (python/paddle/quantization/, cmake/external/
cutlass.cmake). TPU-native: the MXU multiplies int8 at 2× bf16
throughput; this kernel keeps A/B tiles int8 in VMEM, accumulates int32
on the MXU, and applies the per-tensor (x) / per-channel (w) scales in
the epilogue — one pass, no int32 matrix in HBM.

`quantized_matmul(x_i8, w_i8, sx, sw)` ≈ (x_i8 * sx) @ (w_i8 * sw).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import on_tpu

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 256


def available() -> bool:
    return on_tpu()


def _qmm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_scr, *, nk):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ki == nk - 1)
    def _done():
        # fused dequant epilogue: per-tensor x scale, per-channel w scale
        o_ref[...] = (acc_scr[...].astype(jnp.float32)
                      * sx_ref[0, 0] * sw_ref[...]).astype(o_ref.dtype)


def quantized_matmul(x, w, scale_x, scale_w, block_m=DEFAULT_BLOCK_M,
                     block_n=DEFAULT_BLOCK_N, block_k=DEFAULT_BLOCK_K,
                     interpret=False, out_dtype=jnp.float32):
    """x: int8 [M, K]; w: int8 [K, N]; scale_x scalar; scale_w scalar or
    [N]. Returns dequantized [M, N] in ``out_dtype``. Pallas kernel on
    TPU (or under ``interpret=True``) for block-divisible shapes; the
    XLA int32-accumulate composition elsewhere."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = w.shape[1]
    bm = min(block_m, m)
    bn = min(block_n, n)
    bk = min(block_k, k)
    # scales ride as 2-D operands: Mosaic refuses a 1-D (bn,) block
    # (its T(256) layout does not match XLA's T(1024) for 1-D f32)
    sw = jnp.broadcast_to(jnp.asarray(scale_w, jnp.float32), (n,))[None]
    sx = jnp.asarray(scale_x, jnp.float32).reshape(1, 1)
    if not (available() or interpret) or m % bm or n % bn or k % bk:
        # off-TPU and ragged shapes: plain XLA path (int32 accumulate)
        acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * sx * sw).astype(out_dtype)

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, nk=grid[2]),
        name="quant_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, sx, sw)


def quantize_tensor(x, per_channel_axis=None):
    """Symmetric int8 quantization helper: returns (q_int8, scale)."""
    if per_channel_axis is None:
        amax = jnp.max(jnp.abs(x))
        scale = amax / 127.0 + 1e-12
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return q, scale
    axes = tuple(i for i in range(x.ndim) if i != per_channel_axis)
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = amax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.reshape(-1)
