"""The Mamba-2 state-space recurrence (the ``M`` layers of the
``nemotron_h`` family), in the two forms the serving tick needs. For
head ``h`` of group ``g = h // (heads / groups)``, with a step size
``d_t >= 0`` and ``A_h < 0``:

    S_t = exp(d_t A_h) S_{t-1} + d_t outer(X_t, B_{g,t})     S in R^{P x N}
    y_t = S_t C_{g,t}

``ssm_step`` is that line for one row a sequence (a decode tick).
``ssm_scan`` is the SAME recurrence over a run of rows in chunks of
``chunk`` rows (a prefill launch): inside a chunk the rows meet each
other through matmuls (``(C B^T) * decay`` against ``d X``), and ONE
state a sequence is passed from chunk to chunk, so nothing is as long
as the run but the rows themselves. Both start from the state they are
given and return the state after their last row; a row whose ``d`` is 0
is no step at all (decay 1, nothing added), which is how a chunk's
padding rows leave the state of its last real row. Everything here is
float32 whatever the model's type: the state is a sum over a whole
sequence. Plain XLA (cumulative sums, exps, matmuls): no kernel,
nothing to fall back from.
"""
import jax
import jax.numpy as jnp

__all__ = ["ssm_scan", "ssm_step"]


def ssm_step(x, d, a, bm, cm, state):
    """One row a sequence: ``x`` [B, H, P], ``d`` [B, H] (float32 step
    sizes), ``a`` [H] (negative), ``bm``/``cm`` [B, G, N], ``state``
    [B, H, P, N] float32. Returns ``(y [B, H, P] float32, state)``."""
    f32 = jnp.float32
    heads, groups = x.shape[1], bm.shape[1]
    rep = heads // groups
    bh = jnp.repeat(bm.astype(f32), rep, axis=1)             # [B, H, N]
    ch = jnp.repeat(cm.astype(f32), rep, axis=1)
    decay = jnp.exp(d * a)[..., None, None]
    state = decay * state + (d[..., None] * x.astype(f32))[..., None] \
        * bh[:, :, None, :]
    return jnp.sum(state * ch[:, :, None, :], axis=-1), state


def ssm_scan(x, d, a, bm, cm, state, chunk):
    """A run of rows a sequence, chunked: ``x`` [B, T, H, P], ``d``
    [B, T, H] (float32; 0 on a row that is no step), ``a`` [H],
    ``bm``/``cm`` [B, T, G, N], ``state`` [B, H, P, N] float32 (the
    state before row 0). Returns ``(y [B, T, H, P] float32, state after
    row T - 1)``. ``T`` need not be a multiple of ``chunk``: the run is
    padded with rows of ``d = 0``."""
    f32 = jnp.float32
    b, t, heads, p = x.shape
    groups, n = bm.shape[2:]
    rep = heads // groups
    q = min(int(chunk), t)
    pad = (-t) % q
    if pad:
        x, d, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                        for v in (x, d, bm, cm))
    c = (t + pad) // q
    # heads as (group, head of the group): B and C are a group's
    dx = (x.astype(f32) * d[..., None]).reshape(b, c, q, groups, rep, p)
    bm = bm.astype(f32).reshape(b, c, q, groups, n)
    cm = cm.astype(f32).reshape(b, c, q, groups, n)
    cum = jnp.cumsum((d * a).reshape(b, c, q, groups, rep), axis=2)
    # inside a chunk: row l reads row s <= l through exp(cum_l - cum_s)
    seg = cum[:, :, :, None] - cum[:, :, None]               # [b,c,l,s,g,r]
    below = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(below, seg, -jnp.inf))
    cb = jnp.einsum("bclgn,bcsgn->bclsg", cm, bm)
    y = jnp.einsum("bclsgr,bcsgrp->bclgrp", cb[..., None] * decay, dx)
    # what a chunk's own rows leave at its end, and its whole decay
    to_end = jnp.exp(cum[:, :, -1:] - cum)                   # [b,c,q,g,r]
    own = jnp.einsum("bcsgrp,bcsgn->bcgrpn", dx * to_end[..., None], bm)
    whole = jnp.exp(cum[:, :, -1])                           # [b,c,g,r]
    state = state.reshape(b, groups, rep, p, n)
    if c == 1:
        starts = state[:, None]
        state = whole[:, 0, ..., None, None] * state + own[:, 0]
    else:
        # the state passed between chunks: each chunk's START state out
        def carry(s, inp):
            own_c, whole_c = inp
            return whole_c[..., None, None] * s + own_c, s

        state, starts = jax.lax.scan(
            carry, state, (jnp.moveaxis(own, 1, 0),
                           jnp.moveaxis(whole, 1, 0)))
        starts = jnp.moveaxis(starts, 0, 1)                  # [b,c,g,r,p,n]
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", cm, starts) \
        * jnp.exp(cum)[..., None]
    return (y.reshape(b, t + pad, heads, p)[:, :t],
            state.reshape(b, heads, p, n))
