"""Pallas fused GEMM + bias + activation epilogue (TPU).

Reference capability: cublasLt epilogue fusion —
paddle/fluid/operators/fused/fused_gemm_epilogue_op.cu (+ cublaslt.h,
attn_gemm.h), exposed as fused_linear/fused_linear_activation
(python/paddle/incubate/nn/functional/fused_matmul_bias.py).

TPU-native design: a blocked matmul on the MXU whose epilogue (bias add +
gelu/relu) runs in VMEM right after the K-loop accumulation — the bias/
activation never round-trips through HBM. The backward is expressed as
two more fused GEMMs (dx = dz' @ W^T, dW = x^T @ dz') plus a bias-grad
row reduction, where dz' = dz * act'(pre) recomputed from the saved
pre-activation-free inputs (custom_vjp, remat style).

XLA usually fuses simple epilogues by itself; this kernel exists for the
cases it does not (relu_grad/gelu_grad recompute chains) and for API
parity. `fused_gemm_epilogue(..., use_pallas=False)` falls back to the
jnp composition, which XLA fuses on any backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import on_tpu

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512

__all__ = ["fused_gemm_epilogue"]


def _act(z, activation):
    if activation == "relu":
        return jnp.maximum(z, 0.0)
    if activation == "gelu":
        return jax.nn.gelu(z, approximate=True)
    return z


def _fit(b, n):
    while b > 128 and n % b != 0:
        b //= 2
    return min(b, n)


def _mm_kernel(x_ref, w_ref, b_ref, o_ref, acc_scr, *, nk, activation,
               has_bias):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    acc_scr[:] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _epilogue():
        z = acc_scr[:]
        if has_bias:
            z = z + b_ref[...].astype(jnp.float32)   # [1, bn] broadcasts
        o_ref[...] = _act(z, activation).astype(o_ref.dtype)


def _gemm_epilogue_pallas(x, w, bias, activation, interpret=False):
    """x: [M, K], w: [K, N], bias: [N] or None -> act(x@w + bias)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = w.shape[1]
    bm, bn, bk = _fit(DEFAULT_BM, m), _fit(DEFAULT_BN, n), _fit(
        DEFAULT_BK, k)
    grid = (m // bm, n // bn, k // bk)
    # uniform kernel arity: a missing bias becomes a zeros row (one [1,N]
    # VMEM read per output tile — negligible against the K loop)
    b_row = (bias if bias is not None
             else jnp.zeros((n,), x.dtype)).reshape(1, n)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
    ]
    args = [x, w, b_row]
    kernel = functools.partial(_mm_kernel, nk=grid[2],
                               activation=activation, has_bias=True)

    return pl.pallas_call(
        kernel,
        name="gemm_epilogue",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)


def _ref(x, w, bias, activation):
    z = x @ w
    if bias is not None:
        z = z + bias
    return _act(z.astype(jnp.float32), activation).astype(x.dtype)


def _pallas_ok(x, w):
    m, k = x.shape
    n = w.shape[1]
    return (on_tpu() and m % _fit(DEFAULT_BM, m) == 0
            and n % _fit(DEFAULT_BN, n) == 0
            and k % _fit(DEFAULT_BK, k) == 0
            and min(m, n, k) >= 128)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_gemm_epilogue(x, w, bias, activation="none"):
    """act(x @ w + bias); x [.., K] flattened to 2-D internally."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if _pallas_ok(x2, w):
        out = _gemm_epilogue_pallas(x2, w, bias, activation)
    else:
        out = _ref(x2, w, bias, activation)
    return out.reshape(lead + (w.shape[1],))


def _fge_fwd(x, w, bias, activation):
    return fused_gemm_epilogue(x, w, bias, activation), (x, w, bias)


def _fge_bwd(activation, res, g):
    x, w, bias = res
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    g2 = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    if activation != "none":
        # recompute pre-activation once; scale the cotangent by act'(z)
        z = x2 @ w.astype(jnp.float32)
        if bias is not None:
            z = z + bias.astype(jnp.float32)
        _, dact = jax.vjp(lambda t: _act(t, activation), z)
        (g2,) = dact(g2)
    dx = (g2 @ w.astype(jnp.float32).T).astype(x.dtype).reshape(x.shape)
    dw = (x2.T @ g2).astype(w.dtype)
    db = g2.sum(0).astype(bias.dtype) if bias is not None else None
    return dx, dw, db


fused_gemm_epilogue.defvjp(_fge_fwd, _fge_bwd)
