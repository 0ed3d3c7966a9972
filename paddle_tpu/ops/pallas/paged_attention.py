"""Pallas ragged paged-attention decode kernel (TPU).

Serving-side analogue of "Ragged Paged Attention" (PAPERS.md): instead of
one dense per-slot KV buffer ``[slots, max_cache_len, heads, dim]`` —
whose HBM footprint and decode read bandwidth scale with the CONFIGURED
cache length — K/V live in a global page pool and each decode slot owns
an ordered list of page ids (its block table). The pool holds every
layer, LANE-DENSE: ``[layers, num_pages, page_size, kv_heads *
head_dim]`` (``models/generation.paged_pool_shape``), and the kernel
reads it through a LAYER INDEX that rides the scalar prefetch beside the
block table — so the layer loop hands over the whole pool as it stands
and nothing is sliced out or relaid out around the call. Decode
attention gathers pages through the block table, masks by the slot's
ACTUAL length, and early-exits pages wholly beyond it, so both memory
and bandwidth scale with real tokens.

Kernel shape: one query token per slot (decode step). Grid is
``(slots, pages_per_slot)`` with the page axis innermost ("arbitrary"),
accumulating an online softmax in VMEM scratch exactly like
``flash_attention._fwd_kernel``; the block table and per-slot lengths
ride ``PrefetchScalarGridSpec`` scalar prefetch so the page DMA for grid
step ``(s, p)`` is issued from ``block_tables[s, p]`` before the body
runs. GQA is handled in-kernel (query-head groups attend to their kv
head) so the pool stores kv heads unrepeated.

The XLA fallback (`_ref_paged_attention`) gathers pages into the
contiguous ``[slot, pages*page_size, ...]`` frame and then mirrors
``models/generation._cached_attend`` operation-for-operation, which makes
the paged decode path BIT-IDENTICAL to the dense one whenever
``pages_per_slot * page_size == max_cache_len`` (positions beyond a
slot's length hit -1e30 in both, contributing exactly 0.0f to softmax
and output). CPU tests run the Pallas kernel via ``interpret=True``.
"""
import functools
import math

import jax
import jax.numpy as jnp

from . import on_tpu

NEG_INF = -1e30

__all__ = ["paged_attention", "available"]


def available() -> bool:
    return on_tpu()


# ----------------------------------------------------------------- kernel


def _paged_attn_kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
                       o_ref, m_scr, l_scr, acc_scr, *, page_size,
                       pages_per_slot, kv_heads, rep, sm_scale):
    """Grid (slots, pages_per_slot); one query row per slot.

    q_ref  [1, nh, hd]       this slot's query token
    k_ref  [1, 1, page_size, kvh*hd]  the page block_tables[s, p] points
                             at, in layer layer_ref[0]; kv head g is
                             lanes [g*hd, (g+1)*hd)
    len_ref[s]               valid KV tokens for slot s (ragged lengths)
    Scratch m/l/acc carry the online softmax across the page axis.
    """
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[s]

    # early-exit: a page whose first position is past the slot's length
    # holds no valid tokens — skip all compute for it
    @pl.when(p * page_size < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [nh, hd]
        k = k_ref[0, 0].astype(jnp.float32)         # [pg, kvh*hd]
        v = v_ref[0, 0].astype(jnp.float32)
        nh, hd = q.shape
        m_prev = m_scr[:]                           # [nh, 128]
        l_prev = l_scr[:]

        # ragged masking: position p*pg + j is valid iff < length
        col = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (nh, page_size), 1)
        valid = col < length

        # per-kv-head-group contractions keep the MXU ops unbatched
        logits = []
        for g in range(kv_heads):
            qg = q[g * rep:(g + 1) * rep]           # [rep, hd]
            kg = k[:, g * hd:(g + 1) * hd]          # [pg, hd]
            logits.append(jax.lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
        s_log = jnp.concatenate(logits, axis=0) * sm_scale   # [nh, pg]
        s_log = jnp.where(valid, s_log, NEG_INF)

        m_cur = jnp.max(s_log, axis=-1, keepdims=True)       # [nh, 1]
        m_new = jnp.maximum(m_prev[:, :1], m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new)                # [nh, 1]
        pexp = jnp.exp(s_log - m_new)                        # [nh, pg]
        pexp = jnp.where(valid, pexp, 0.0)
        l_scr[:] = jnp.broadcast_to(
            corr * l_prev[:, :1] + jnp.sum(pexp, -1, keepdims=True),
            l_scr.shape)
        pv = []
        for g in range(kv_heads):
            pv.append(jax.lax.dot_general(
                pexp[g * rep:(g + 1) * rep], v[:, g * hd:(g + 1) * hd],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))         # [rep, hd]
        acc_scr[:] = acc_scr[:] * corr + jnp.concatenate(pv, axis=0)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(p == pages_per_slot - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)              # empty slot guard
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


def as_layered(k_pages, v_pages, layer):
    """The pools as the kernels take them — every layer, lane-dense,
    ``[L, P, pg, kvh*hd]`` — and the layer index as an int32 [1]. With
    ``layer`` None the operands are ONE layer's pools per head,
    ``[P, pg, kvh, hd]``: the one-layer case of the same code (a
    reshape of the minor axes, layer 0)."""
    if layer is None:
        P, pg, kvh, hd = k_pages.shape
        k_pages = k_pages.reshape(1, P, pg, kvh * hd)
        v_pages = v_pages.reshape(1, P, pg, kvh * hd)
        layer = 0
    return k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1)


def _paged_attention_pallas(q, k_pages, v_pages, block_tables, lengths,
                            sm_scale, interpret=False, layer=None):
    """q [S, nh, hd]; pages [L, P, pg, kvh*hd] read at ``layer``, or one
    layer's [P, pg, kvh, hd] (``as_layered``); block_tables [S, maxp]
    int32 (unused tail entries must hold any VALID page id, e.g. 0);
    lengths [S] int32. Returns [S, nh, hd]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k_pages, v_pages, layer = as_layered(k_pages, v_pages, layer)
    S, nh, hd = q.shape
    _, P, pg, width = k_pages.shape
    kvh = width // hd
    maxp = block_tables.shape[1]
    rep = nh // kvh
    if nh % kvh:
        raise ValueError(f"query heads ({nh}) must be a multiple of kv "
                         f"heads ({kvh})")

    flat_bt = block_tables.reshape(-1).astype(jnp.int32)
    kernel = functools.partial(
        _paged_attn_kernel, page_size=pg, pages_per_slot=maxp,
        kv_heads=kvh, rep=rep, sm_scale=sm_scale)

    def page(s, p, bt, ln, l):
        return (l[0], bt[s * maxp + p], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, maxp),
        in_specs=[
            pl.BlockSpec((1, nh, hd), lambda s, p, bt, ln, l: (s, 0, 0)),
            pl.BlockSpec((1, 1, pg, width), page),
            pl.BlockSpec((1, 1, pg, width), page),
        ],
        out_specs=pl.BlockSpec((1, nh, hd),
                               lambda s, p, bt, ln, l: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(flat_bt, lengths.astype(jnp.int32), layer, q, k_pages, v_pages)


# ------------------------------------------------- mesh-sharded kernel path


def kv_head_shards(mesh, num_kv_heads, num_heads=None, axis="mp"):
    """Ways an attention launch splits over ``mesh``'s ``axis`` on the
    kv-head dimension: the axis size when it divides the kv heads (and
    the query heads, which follows for any integral GQA ratio), else 1.
    1 means "launch replicated" — the caller's divisibility fallback,
    matching the pool placement rule in ``models/generation``."""
    if mesh is None:
        return 1
    size = int(dict(mesh.shape).get(axis, 1))
    if size <= 1 or num_kv_heads % size:
        return 1
    if num_heads is not None and num_heads % size:
        return 1
    return size


def _paged_attention_sharded(q, k_pages, v_pages, block_tables, lengths,
                             layer, sm_scale, mesh, axis, interpret):
    """Per-shard Pallas launches over the mesh's ``axis``: the page
    pools arrive sharded on their merged kv-head axis (contiguous
    blocks of whole heads), q splits into the matching query-head
    groups (a GQA group never straddles a shard — consecutive head
    blocks keep each kv head with its own rep query heads), the block
    table, lengths and layer index ride replicated, and the out_spec's
    head-axis concatenation IS the attention all-gather GSPMD would
    insert on the fallback path. XLA cannot partition a custom call,
    so the kernel path must shard_map itself; returns None when the
    head counts don't divide the axis — the caller then runs one
    replicated launch."""
    from jax.sharding import PartitionSpec as P

    kvh = k_pages.shape[-1] // q.shape[-1]
    if kv_head_shards(mesh, kvh, q.shape[1], axis) <= 1:
        return None
    def fn(q, k_pages, v_pages, block_tables, lengths, layer):
        return _paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                       lengths, sm_scale, interpret, layer)

    pool = P(None, None, None, axis)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, axis, None), pool, pool, P(None, None), P(None),
                  P(None)),
        out_specs=P(None, axis, None), check_vma=False,
    )(q, k_pages, v_pages, block_tables, lengths, layer)


# ------------------------------------------------------ XLA reference path


def _ref_paged_attention(q, k_pages, v_pages, block_tables, lengths,
                         sm_scale, layer=None):
    """Gather-through-block-table reference. Mirrors the dense decode
    attention (`generation._cached_attend` at s=1) op-for-op so the paged
    server emits bit-identical tokens to the dense backend on every
    platform: valid positions carry the exact cached values, positions at
    or beyond ``lengths`` are masked to -1e30 before the same f32 softmax
    (contributing exactly 0.0), and the einsum specs match."""
    k_pages, v_pages, layer = as_layered(k_pages, v_pages, layer)
    S, nh, hd = q.shape
    _, P, pg, width = k_pages.shape
    kvh = width // hd
    maxp = block_tables.shape[1]
    T = maxp * pg
    k = k_pages[layer[0], block_tables].reshape(S, T, kvh, hd)
    v = v_pages[layer[0], block_tables].reshape(S, T, kvh, hd)
    rep = nh // kvh
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qb = q[:, None]                                        # [S, 1, nh, hd]
    logits = jnp.einsum("bsnd,btnd->bnst", qb, k) * sm_scale
    pos = jnp.arange(T)
    ok = pos[None, None] < lengths[:, None, None]          # [S, 1, T]
    logits = jnp.where(ok[:, None], logits.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", p, v)[:, 0]


# --------------------------------------------------------------- public


def paged_attention(q, k_pages, v_pages, block_tables, lengths,
                    sm_scale=None, interpret=False, mesh=None, layer=None):
    """Ragged paged-attention decode step.

    q            [slots, num_heads, head_dim]   one query token per slot
    k_pages      the global pool: with ``layer`` given, every layer
                 lane-dense ``[layers, num_pages, page_size, kv_heads *
                 head_dim]``; without, one layer per head
                 ``[num_pages, page_size, kv_heads, head_dim]``
    v_pages      same shape as ``k_pages``
    layer        int32 scalar (may be traced): the layer of the pool
                 this call reads — the serving layer loop passes its
                 loop index and the WHOLE carried pool
    block_tables [slots, pages_per_slot] int32  page ids, in position
                 order; entries past a slot's allocation must hold a
                 valid id (the manager fills them with 0)
    lengths      [slots] int32  valid KV tokens per slot (ragged)
    mesh         optional ``jax.sharding.Mesh`` whose ``mp`` axis the
                 page pools are sharded over on their kv-head axis
                 (sharded paged serving): the Pallas path then runs one
                 launch PER SHARD via shard_map — each shard reads only
                 its resident pool slice, block tables replicated —
                 and the head-axis restitch is the attention
                 all-gather. Ignored on the XLA fallback, where GSPMD
                 partitions the gather/einsum composition from the
                 pool's input sharding directly.

    Returns [slots, num_heads, head_dim]. Runs the Pallas kernel on TPU
    (or under ``interpret=True`` anywhere); elsewhere the gather-based
    XLA composition, which is bit-identical to the dense decode path.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    k_pages, v_pages, layer = as_layered(k_pages, v_pages, layer)
    if available() or interpret:
        if mesh is not None:
            out = _paged_attention_sharded(
                q, k_pages, v_pages, block_tables, lengths, layer,
                sm_scale, mesh, "mp", interpret)
            if out is not None:
                return out
        return _paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                       lengths, sm_scale,
                                       interpret=interpret, layer=layer)
    return _ref_paged_attention(q, k_pages, v_pages, block_tables,
                                lengths, sm_scale, layer=layer)
