"""Depthwise causal short convolution over time (the ``conv`` layers of
the ``lfm2`` family): K shifted multiply-adds, which XLA fuses — no
kernel, nothing to fall back from."""
import jax.numpy as jnp

__all__ = ["short_conv"]


def short_conv(u, taps, prev=None):
    """``c_t = sum_j taps[j] * u_{t - (K-1) + j}`` for ``u`` [B, T, H]
    and ``taps`` [K, H] (tap K-1 weighs the current row). ``prev`` [B,
    K-1, H] are the rows before ``u`` (None: zeros, the start of a
    sequence). Returns ``(c [B, T, H], full [B, K-1+T, H])``: the
    multiply-adds accumulate in float32; ``full`` is ``prev`` and ``u``
    in one run, whose rows ``[n, n + K-1)`` are the state after ``n``
    rows of ``u``."""
    k = taps.shape[0]
    if prev is None:
        prev = jnp.zeros((u.shape[0], k - 1, u.shape[2]), u.dtype)
    full = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
    t = u.shape[1]
    c = sum(taps[j].astype(jnp.float32)
            * full[:, j:j + t].astype(jnp.float32) for j in range(k))
    return c.astype(u.dtype), full
