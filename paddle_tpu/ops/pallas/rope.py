"""Rotary position embedding.

Reference: composed in Python in the snapshot (SURVEY §2.4 — the dedicated
`fused_rotary_position_embedding` CUDA kernel landed later upstream). On TPU
the rotate+mul fuses into neighbouring matmuls under XLA, so the jnp
composition below *is* the fused kernel; a Pallas version only pays off fused
into flash-attention's Q/K load, which is an M4+ item.
"""
import jax.numpy as jnp


def available() -> bool:
    return True


def precompute_freqs(head_dim, max_seq_len, theta=10000.0, dtype=jnp.float32):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)                       # [S, D/2]
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rotary(x, cos, sin, position_ids=None):
    """x: [B, S, H, D]; cos/sin: [S_max, D/2] (neox / llama interleave-half).

    PT_ROPE_PALLAS=1 routes through the Pallas kernel on TPU (opt-in
    pending an on-chip A/B; the XLA-fused jnp path is the measured
    default)."""
    import os
    if (position_ids is None and os.environ.get("PT_ROPE_PALLAS") == "1"
            and x.ndim == 4):
        from .flash_attention import on_tpu
        if on_tpu():
            return apply_rotary_pallas(x, cos, sin)
    return _apply_rotary_jnp(x, cos, sin, position_ids)


def _apply_rotary_jnp(x, cos, sin, position_ids=None):
    seq = x.shape[1]
    if position_ids is not None:
        c = jnp.take(cos, position_ids, axis=0)     # [B, S, D/2]
        s = jnp.take(sin, position_ids, axis=0)
        c = c[:, :, None, :]
        s = s[:, :, None, :]
    else:
        c = cos[None, :seq, None, :]
        s = sin[None, :seq, None, :]
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def fused_rotary_position_embedding(q, k, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True):
    """paddle.incubate.nn.functional.fused_rotary_position_embedding parity."""
    outs = [apply_rotary(q, cos, sin, position_ids),
            apply_rotary(k, cos, sin, position_ids)]
    outs.append(v if v is None else v)
    return tuple(outs)


# ------------------------------------------------- Pallas kernel variant
# (SURVEY §2.4 "rotary embedding -> Pallas rope"). The jnp composition
# above stays the default path — XLA fuses it into the surrounding
# matmuls, and the measured bench numbers are against it; the kernel is
# opted in via PT_ROPE_PALLAS=1 (or apply_rotary_pallas directly) pending
# an on-chip A/B.


def apply_rotary_pallas(x, cos, sin, block_s=512, interpret=False):
    """Pallas rope: x [B, S, H, D] processed as [(B*H), S, D] row blocks,
    cos/sin staged per sequence block in VMEM."""
    b, seq, h, d = x.shape
    d2 = d // 2
    bs = min(block_s, seq)
    if seq % bs or seq > cos.shape[0]:
        # ragged length, or seq beyond the precomputed table (the jnp
        # path fails loudly on the latter; Pallas would silently clamp)
        return _apply_rotary_jnp(x, cos, sin)
    xt = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, seq, d)
    grid = (b * h, seq // bs)
    out = _rope_call(xt, cos[:seq], sin[:seq], bs, d, d2, grid, interpret)
    return jnp.transpose(out.reshape(b, h, seq, d), (0, 2, 1, 3))


def _rope_call(xt, c, s, bs, d, d2, grid, interpret):
    import jax
    from jax.experimental import pallas as pl

    def kernel(x_ref, cos_ref, sin_ref, o_ref):
        x = x_ref[0]
        cc = cos_ref[...]
        ss = sin_ref[...]
        x1 = x[:, :d2]
        x2 = x[:, d2:]
        o_ref[0, :, :d2] = (x1 * cc - x2 * ss).astype(o_ref.dtype)
        o_ref[0, :, d2:] = (x2 * cc + x1 * ss).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        name="rope",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bs, d2), lambda i, j: (j, 0)),
            pl.BlockSpec((bs, d2), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(xt.shape, xt.dtype),
        interpret=interpret,
    )(xt, c, s)
