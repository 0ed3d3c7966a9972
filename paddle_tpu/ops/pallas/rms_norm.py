"""Pallas RMSNorm kernel (+ custom VJP).

Reference equivalent: rms_norm CUDA kernel named in the north star; in the
reference snapshot RMSNorm is Python-composed (SURVEY §2.4). Here: one fused
VMEM pass per row-block — x is read once, normalized on the VPU, scaled by
the (broadcast) weight; backward recomputes the rstd instead of storing
activations (bandwidth-bound op, recompute is free).
"""
import functools

import jax
import jax.numpy as jnp

from . import on_tpu


def available() -> bool:
    return on_tpu()


def _ref_fwd(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    return (xf * rstd * w.astype(jnp.float32)).astype(x.dtype)


def _kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o_ref[:] = (x * rstd * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _pick_block_rows(rows, block_rows):
    br = min(block_rows, rows)
    while rows % br:
        br //= 2
    return max(br, 1)


def _pallas_fwd(x, w, eps, block_rows=256):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    orig_shape = x.shape
    d = x.shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    br = _pick_block_rows(rows, block_rows)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        name="rms_norm_fwd",
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((d,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
    )(x2, w)
    return out.reshape(orig_shape)


def _bwd_kernel(x_ref, w_ref, g_ref, dx_ref, dw_ref, dw_scr, *, eps,
                nblocks):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)          # [1, d]
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = x * rstd
    gw = g * w
    dx = rstd * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dw_scr[:] += jnp.sum(g * xhat, axis=0, keepdims=True)

    @pl.when(i == nblocks - 1)
    def _finalize():
        dw_ref[:] = dw_scr[:]


def _pallas_bwd(x, w, g, eps, block_rows=256, interpret=False):
    """Single fused pass: reads x/g once per row block, emits dx and the
    accumulated dw (reference capability: dedicated rms_norm grad kernel;
    XLA's fusion is close for this bandwidth-bound op — kept because the
    fused dw accumulation avoids a second x read)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    orig_shape = x.shape
    d = x.shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    g2 = g.reshape(rows, d)
    br = _pick_block_rows(rows, block_rows)
    nblocks = rows // br
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, nblocks=nblocks),
        name="rms_norm_bwd",
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2, w.reshape(1, d), g2)
    return dx.reshape(orig_shape), dw.reshape(d).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, w, eps=1e-6):
    if available():
        return _pallas_fwd(x, w, eps)
    return _ref_fwd(x, w, eps)


def _fwd(x, w, eps):
    return rms_norm(x, w, eps), (x, w)


def _ref_bwd(x, w, g, eps):
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xf * rstd
    gw = gf * wf
    d = x.shape[-1]
    dx = rstd * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum((gf * xhat).reshape(-1, d), axis=0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


def _bwd(eps, res, g):
    x, w = res
    if available():
        return _pallas_bwd(x, w, g, eps)
    return _ref_bwd(x, w, g, eps)


rms_norm.defvjp(_fwd, _bwd)
