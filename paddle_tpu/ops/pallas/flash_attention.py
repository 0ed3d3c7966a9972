"""Pallas flash attention (TPU).

Reference equivalent: paddle/phi/kernels/gpu/flash_attn_kernel.cu (dynloaded
libflashattn; python surface python/paddle/nn/functional/flash_attention.py:20).
TPU-native design: blockwise online-softmax forward entirely in VMEM with a
(B·H, Q-blocks, KV-blocks) grid — the KV axis is the innermost ("arbitrary")
grid dimension accumulating into VMEM scratch, so each Q block streams K/V
tiles through VMEM exactly once. Layout is paddle's [batch, seq, heads, dim];
internally [B,H,S,D].

Backward is a dedicated two-kernel Pallas pass (dq; dk+dv) from the saved
output + logsumexp, FlashAttention-2 style: delta = rowsum(do*o) is
precomputed, each kernel recomputes p = exp(s - lse) blockwise and
accumulates into VMEM scratch. Both kernels work in the transposed
[block_k, block_q] frame so lse/delta stay (1, block_q) row vectors
(no in-kernel transposes; contractions go through dot_general on the MXU)
and causal block skip prunes fully-masked tiles. Reference capability:
paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu.
"""
import functools
import math

import jax
import jax.numpy as jnp

from . import on_tpu

# v5e-swept defaults (benchmarks/flash_block_sweep.py): 1024/1024 is
# 3.7x faster fwd and 4.5x fwd+bwd than 128/128; >1024 fails to compile
# (VMEM). Kernels clamp to the sequence length when shorter.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30


def available() -> bool:
    return on_tpu()


# --------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal, block_q, block_k,
                num_kv_blocks):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [block_q, d]
        k = k_ref[0].astype(jnp.float32)          # [block_k, d]
        v = v_ref[0].astype(jnp.float32)          # [block_k, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                           # [block_q, block_k]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[:]                          # [block_q, 128]
        l_prev = l_scr[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])   # [block_q,1]
        p = jnp.exp(s - m_new[:, :1])              # [block_q, block_k]
        l_new = corr * l_prev[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # skip fully-masked KV blocks above the diagonal
        pl.when(ki * block_k <= (qi + 1) * block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        m_fin = m_scr[:]
        l_fin = l_scr[:]
        l = jnp.where(l_fin[:, :1] == 0.0, 1.0, l_fin[:, :1])
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_fin + jnp.log(jnp.maximum(l_fin, 1e-30))
                      ).astype(lse_ref.dtype)


def _flash_fwd_pallas(q, k, v, sm_scale, causal,
                      block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                      interpret=False):
    """q,k,v: [BH, S, D] (batch*heads flattened). Returns (o, lse[BH,S,128])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    nq = sq // block_q
    nk = sk // block_k
    grid = (bh, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, num_kv_blocks=nk)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse[:, :, 0]


# -------------------------------------------------------------- backward


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, sm_scale, causal, block_q, block_k,
                   num_kv_blocks):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [block_q, d]
        k = k_ref[0].astype(jnp.float32)            # [block_k, d]
        v = v_ref[0].astype(jnp.float32)            # [block_k, d]
        do = do_ref[0].astype(jnp.float32)          # [block_q, d]
        lse = lse_ref[0]                            # [1, block_q]
        delta = delta_ref[0]                        # [1, block_q]
        # transposed frame: st[kk, qq] = k·q * scale
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        st = st * sm_scale                          # [block_k, block_q]
        pt = jnp.exp(st - lse)                      # exp(s - lse)^T
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            pt = jnp.where(q_pos >= k_pos, pt, 0.0)
        dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta)                    # [block_k, block_q]
        # dq[qq, d] += ds[qq, kk] @ k[kk, d]  == dst^T @ k via dim-0 contract
        dq_scr[:] = dq_scr[:] + sm_scale * jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki * block_k <= (qi + 1) * block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    block_q, block_k, num_q_blocks):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [block_q, d]
        k = k_ref[0].astype(jnp.float32)            # [block_k, d]
        v = v_ref[0].astype(jnp.float32)            # [block_k, d]
        do = do_ref[0].astype(jnp.float32)          # [block_q, d]
        lse = lse_ref[0]                            # [1, block_q]
        delta = delta_ref[0]                        # [1, block_q]
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        st = st * sm_scale
        pt = jnp.exp(st - lse)                      # [block_k, block_q]
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            pt = jnp.where(q_pos >= k_pos, pt, 0.0)
        # dv[kk, d] += p^T[kk, qq] @ do[qq, d]
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta)                    # [block_k, block_q]
        # dk[kk, d] += ds^T[kk, qq] @ q[qq, d]
        dk_scr[:] = dk_scr[:] + sm_scale * jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when((qi + 1) * block_q - 1 >= ki * block_k)(_compute)
    else:
        _compute()

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, sm_scale, causal,
                      block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                      interpret=False, dlse=None):
    """q,k,v,o,do: [BH, S, D]; lse: [BH, S]. Returns (dq, dk, dv).

    ``dlse``: optional cotangent of lse (ring-attention merge path). It
    folds into the row term: ds = p*(dp - delta + dlse), so we just pass
    delta' = delta - dlse to the kernels."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    nq = sq // block_q
    nk = sk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    # rows as [BH*nq, 1, block_q]: block == array dims on the last two
    # axes, which satisfies Mosaic's (8, 128) block-tiling constraint
    lse = lse.astype(jnp.float32).reshape(bh * nq, 1, block_q)
    delta = delta.reshape(bh * nq, 1, block_q)

    qkv_spec_q = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    row_spec_q = pl.BlockSpec((1, 1, block_q),
                              lambda b, i, j: (b * nq + i, 0, 0))
    kv_spec_q = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_kv_blocks=nk),
        name="flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=[qkv_spec_q, kv_spec_q, kv_spec_q, qkv_spec_q,
                  row_spec_q, row_spec_q],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    qkv_spec_k = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    row_spec_k = pl.BlockSpec((1, 1, block_q),
                              lambda b, j, i: (b * nq + i, 0, 0))
    kv_spec_k = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq),
        name="flash_bwd_dkv",
        grid=(bh, nk, nq),
        in_specs=[qkv_spec_k, kv_spec_k, kv_spec_k, qkv_spec_k,
                  row_spec_k, row_spec_k],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------- XLA reference path


def _ref_attention(q, k, v, sm_scale, causal):
    """[B,H,S,D] reference; used for CPU tests and as backward recompute."""
    return _ref_with_lse(q, k, v, sm_scale, causal)[0]


# --------------------------------------------------------------- public api


def _fit_block(pref, seq):
    """Largest power-of-two block <= pref that divides seq (>=128)."""
    b = min(pref, seq)
    while b > 128 and seq % b != 0:
        b //= 2
    return b


def _pallas_ok(q, k):
    """Pallas path requires whole blocks: seq lengths must be divisible
    by SOME supported block size (>=128) — the kernels then pick the
    largest fitting one, so e.g. seq 2560 runs with 512-blocks instead of
    falling back to the O(S^2)-memory XLA composition."""
    sq, sk = q.shape[2], k.shape[2]
    return (available() and sq % _fit_block(DEFAULT_BLOCK_Q, sq) == 0
            and sk % _fit_block(DEFAULT_BLOCK_K, sk) == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, sm_scale, causal):
    # q,k,v: [B,H,S,D]
    if _pallas_ok(q, k):
        b, h, s, d = q.shape
        o, _ = _flash_fwd_pallas(q.reshape(b * h, s, d),
                                 k.reshape(b * h, k.shape[2], d),
                                 v.reshape(b * h, v.shape[2], d),
                                 sm_scale, causal)
        return o.reshape(b, h, s, d)
    return _ref_attention(q, k, v, sm_scale, causal)


def _flash_fwd(q, k, v, sm_scale, causal):
    if _pallas_ok(q, k):
        b, h, s, d = q.shape
        o, lse = _flash_fwd_pallas(q.reshape(b * h, s, d),
                                   k.reshape(b * h, k.shape[2], d),
                                   v.reshape(b * h, v.shape[2], d),
                                   sm_scale, causal)
        return o.reshape(b, h, s, d), (q, k, v, o, lse)
    return _ref_attention(q, k, v, sm_scale, causal), (q, k, v, None, None)


def _flash_bwd(sm_scale, causal, res, g):
    q, k, v, o, lse = res
    if o is not None:
        b, h, s, d = q.shape
        sk = k.shape[2]
        dq, dk, dv = _flash_bwd_pallas(
            q.reshape(b * h, s, d), k.reshape(b * h, sk, d),
            v.reshape(b * h, sk, d), o, lse,
            g.reshape(b * h, s, d), sm_scale, causal)
        return (dq.reshape(b, h, s, d), dk.reshape(b, h, sk, d),
                dv.reshape(b, h, sk, d))
    _, vjp = jax.vjp(lambda q_, k_, v_: _ref_attention(q_, k_, v_, sm_scale,
                                                       causal), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------- (o, lse) variant for ring

def _ref_with_lse(q, k, v, sm_scale, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_with_lse(q, k, v, sm_scale, causal):
    """[B,H,S,D] attention returning (o, lse[B,H,S]). The lse output is
    differentiable, which is what lets ring attention merge per-ring-step
    partial results (weights depend on lse) with exact gradients."""
    if _pallas_ok(q, k):
        b, h, s, d = q.shape
        sk = k.shape[2]
        o, lse = _flash_fwd_pallas(q.reshape(b * h, s, d),
                                   k.reshape(b * h, sk, d),
                                   v.reshape(b * h, sk, d),
                                   sm_scale, causal)
        return o.reshape(b, h, s, d), lse.reshape(b, h, s)
    return _ref_with_lse(q, k, v, sm_scale, causal)


def _fwl_fwd(q, k, v, sm_scale, causal):
    if _pallas_ok(q, k):
        b, h, s, d = q.shape
        sk = k.shape[2]
        o, lse = _flash_fwd_pallas(q.reshape(b * h, s, d),
                                   k.reshape(b * h, sk, d),
                                   v.reshape(b * h, sk, d),
                                   sm_scale, causal)
        return ((o.reshape(b, h, s, d), lse.reshape(b, h, s)),
                (q, k, v, o, lse))
    out = _ref_with_lse(q, k, v, sm_scale, causal)
    return out, (q, k, v, None, None)


def _fwl_bwd(sm_scale, causal, res, ct):
    q, k, v, o, lse = res
    do, dlse = ct
    if o is not None:
        b, h, s, d = q.shape
        sk = k.shape[2]
        dq, dk, dv = _flash_bwd_pallas(
            q.reshape(b * h, s, d), k.reshape(b * h, sk, d),
            v.reshape(b * h, sk, d), o, lse,
            do.reshape(b * h, s, d), sm_scale, causal,
            dlse=dlse.reshape(b * h, s))
        return (dq.reshape(b, h, s, d), dk.reshape(b, h, sk, d),
                dv.reshape(b, h, sk, d))
    _, vjp = jax.vjp(lambda a, b_, c: _ref_with_lse(a, b_, c, sm_scale,
                                                    causal), q, k, v)
    return vjp((do, dlse))


flash_attention_with_lse.defvjp(_fwl_fwd, _fwl_bwd)


def _mesh_partition(q):
    """``(mesh, spec)`` for a [B, H, S, D] launch traced under a mesh
    (``with mesh:``), else None. GSPMD refuses a Mosaic call ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call
    in a shard_map"), so under a mesh the Pallas path shard_maps
    itself: batch over the data axes (``dp`` x ``sharding``) that
    divide it, heads over ``mp``, everything else replicated. Inside a
    shard_map already (pipeline stages, ring attention) the axes are
    bound and the launch is per-shard as it stands."""
    from jax.interpreters import pxla
    from jax.sharding import PartitionSpec as P
    mesh = pxla.thread_resources.env.physical_mesh
    if mesh.empty or mesh.size == 1 or jax.core.nonempty_axis_env_DO_NOT_USE():
        return None
    sizes = dict(mesh.shape)
    data = tuple(a for a in ("dp", "sharding") if sizes.get(a, 1) > 1)
    while data and q.shape[0] % math.prod(sizes[a] for a in data):
        data = data[:-1]
    heads = "mp" if (sizes.get("mp", 1) > 1
                     and q.shape[1] % sizes["mp"] == 0) else None
    return mesh, P(data or None, heads, None, None)


def _attend(q, k, v, sm_scale, causal):
    """[B, H, S, D] attention: ``_flash``, split over the active mesh
    when the Pallas path runs under one."""
    part = _mesh_partition(q) if _pallas_ok(q, k) else None
    if part is None:
        return _flash(q, k, v, sm_scale, causal)
    mesh, spec = part
    return jax.shard_map(
        lambda q_, k_, v_: _flash(q_, k_, v_, sm_scale, causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


def flash_attention(q, k, v, causal=False, sm_scale=None):
    """q,k,v: paddle layout [batch, seq, num_heads, head_dim]."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    o = _attend(qt, kt, vt, sm_scale, causal)
    return jnp.swapaxes(o, 1, 2)


def flash_attention_bhsd(q, k, v, causal=False, sm_scale=None):
    """Same kernel, [batch, heads, seq, dim] layout (no transposes)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    return _attend(q, k, v, sm_scale, causal)
