"""Tensor-parallel primitive ops.

Reference: python/paddle/distributed/fleet/layers/mpu/mp_ops.py — the
autograd-transparent PyLayers `_c_identity` (fwd copy / bwd allreduce),
`_mp_allreduce` (fwd allreduce / bwd copy), `_c_split`, `_c_concat`, and
`_c_softmax_with_cross_entropy` over the CUDA collective ops.

TPU-native: each is a `jax.custom_vjp` over `lax` collectives, valid inside
shard_map over the "mp" axis. Under pure-GSPMD execution these are identity
at trace time (XLA inserts the collectives from shardings) — both modes share
one API, mirroring how the reference's static/dygraph paths share op names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .collective import axis_or_none

__all__ = ["c_identity", "mp_allreduce", "c_split", "c_concat",
           "c_softmax_with_cross_entropy"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _identity_fwd_allreduce_bwd(x, axis):
    return x


def _ifab_fwd(x, axis):
    return x, None


def _ifab_bwd(axis, _, g):
    return (jax.lax.psum(g, axis),)


_identity_fwd_allreduce_bwd.defvjp(_ifab_fwd, _ifab_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _allreduce_fwd_identity_bwd(x, axis):
    return jax.lax.psum(x, axis)


def _afib_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _afib_bwd(axis, _, g):
    return (g,)


_allreduce_fwd_identity_bwd.defvjp(_afib_fwd, _afib_bwd)


def c_identity(x, group=None):
    """Forward: identity; backward: allreduce grad over mp (mp_ops.py:46)."""
    axis = axis_or_none(group or "mp")
    if axis is None:
        return x
    return _identity_fwd_allreduce_bwd(x, axis)


def mp_allreduce(x, group=None):
    """Forward: allreduce over mp; backward: identity (mp_ops.py:236)."""
    axis = axis_or_none(group or "mp")
    if axis is None:
        return x
    return _allreduce_fwd_identity_bwd(x, axis)


def c_split(x, group=None, axis=-1):
    """Keep the local rank's slice of the last dim (mp_ops._c_split)."""
    ax = axis_or_none(group or "mp")
    if ax is None:
        return x
    n = jax.lax.axis_size(ax)
    idx = jax.lax.axis_index(ax)
    size = x.shape[axis] // n
    return jax.lax.dynamic_slice_in_dim(x, idx * size, size, axis=axis)


def c_concat(x, group=None, axis=-1):
    """All-gather along the mp axis, concatenated on `axis`."""
    ax = axis_or_none(group or "mp")
    if ax is None:
        return x
    return jax.lax.all_gather(x, ax, axis=axis, tiled=True)


def c_softmax_with_cross_entropy(logits, label, group=None,
                                 ignore_index=-100):
    """Vocab-sharded softmax CE (reference CUDA op
    c_softmax_with_cross_entropy_op.cu; python mpu/mp_layers.py:498).

    logits: [..., V/mp] local shard; label: [...] global vocab ids.
    Stable algorithm: global max & sum via psum/pmax over mp; the true-label
    logit is picked locally (masked) and psum'd.
    """
    axis = axis_or_none(group or "mp")
    lg = logits.astype(jnp.float32)
    if axis is None:
        logp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(logp, label[..., None], axis=-1)[..., 0]
        return nll

    vocab_local = lg.shape[-1]
    idx = jax.lax.axis_index(axis)
    start = idx * vocab_local
    gmax = jax.lax.pmax(jnp.max(lg, axis=-1, keepdims=True), axis)
    shifted = lg - gmax
    sumexp = jax.lax.psum(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True),
                          axis)
    local_label = label - start
    in_range = (local_label >= 0) & (local_label < vocab_local)
    safe_label = jnp.clip(local_label, 0, vocab_local - 1)
    picked = jnp.take_along_axis(shifted, safe_label[..., None], axis=-1)[..., 0]
    picked = jnp.where(in_range, picked, 0.0)
    picked = jax.lax.psum(picked, axis)
    nll = jnp.log(sumexp[..., 0]) - picked
    return nll
