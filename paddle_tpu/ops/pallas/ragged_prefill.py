"""Pallas ragged prefill attention over the paged KV pool (TPU).

Prefill-side counterpart of ``paged_attention.py`` (PAPERS.md "Ragged
Paged Attention"): several variable-length prompt CHUNKS — one per
serving slot — are packed into a single ``[slots, chunk]`` launch and
attend causally over the global page pool through their slots' block
tables, each at its own prefix offset ``t0`` (an auto-prefix-cache hit
resumes at the first uncached token and attends over the already-cached
pages exactly like a decode step does). This is what lets the serving
scheduler run the prefill work of SEVERAL admissions as one device
dispatch, interleaved with decode ticks, with K/V written straight into
pool pages — no dense batch-1 cache detour.

Kernel shape: grid ``(slots, pages_per_slot)`` with the page axis
innermost ("arbitrary"), ``chunk`` query rows per slot, accumulating an
online softmax in VMEM scratch over the page axis like the decode
kernel — the scratch simply carries ``chunk * num_heads`` rows instead
of ``num_heads``. The block table and the per-slot ``t0``/last-valid
position ride ``PrefetchScalarGridSpec`` scalar prefetch, so a slot
whose chunk is empty this launch (``last < 0``, the scheduler's idle
sentinel) skips every page's compute, and trailing pages beyond a
slot's frontier early-exit.

The XLA fallback (``_ref_ragged_prefill``) gathers the pool through the
block table into the contiguous per-slot frame and then mirrors
``models/generation._cached_attend`` operation-for-operation (same
einsum specs, same -1e30 mask, same f32 softmax), which keeps ragged
prefill BIT-IDENTICAL to the dense batch-1 prefill path: a masked
position contributes exactly 0.0f in both, and XLA's row-wise matmul
results are stable across the batch/sequence shapes involved (asserted
by the parity suite, tests/test_ragged_prefill.py). CPU tests run the
Pallas kernel via ``interpret=True``.
"""
import functools
import math

import jax
import jax.numpy as jnp

from . import on_tpu
from .paged_attention import NEG_INF, as_layered, kv_head_shards

__all__ = ["ragged_prefill_attention", "available"]

# query rows per kernel launch: scratch is (rows * num_heads)-tall in
# VMEM, so the public entry tiles wider chunks down to this
_QUERY_TILE = 8


def available() -> bool:
    return on_tpu()


# ----------------------------------------------------------------- kernel


def _ragged_prefill_kernel(bt_ref, t0_ref, last_ref, layer_ref, q_ref,
                           k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                           page_size, pages_per_slot, chunk, kv_heads, rep,
                           sm_scale):
    """Grid (slots, pages_per_slot); ``chunk`` query rows per slot.

    q_ref  [1, chunk, nh, hd]       this slot's packed prompt chunk
    k_ref  [1, 1, page_size, kvh*hd]  the page block_tables[s, p] points
                at, in layer layer_ref[0]; kv head g is lanes
                [g*hd, (g+1)*hd)
    t0_ref[s]   absolute position of the chunk's first row (prefix offset)
    last_ref[s] last position the chunk writes (t0 + take - 1); -1 for a
                slot with no prefill work this launch (all compute skipped)
    Scratch m/l/acc carry the online softmax across the page axis, one
    row per (chunk row, query head) pair.
    """
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    t0 = t0_ref[s]
    last = last_ref[s]
    nh = kv_heads * rep

    # early-exit: a page wholly past the chunk's frontier (or an idle
    # slot, last == -1) holds nothing any row may attend to
    @pl.when(p * page_size <= last)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [chunk, nh, hd]
        k = k_ref[0, 0].astype(jnp.float32)         # [pg, kvh*hd]
        v = v_ref[0, 0].astype(jnp.float32)
        hd = q.shape[-1]
        m_prev = m_scr[:]                           # [chunk*nh, 128]
        l_prev = l_scr[:]

        # per-kv-head-group contractions keep the MXU ops unbatched
        logits = []
        for g in range(kv_heads):
            qg = q[:, g * rep:(g + 1) * rep].reshape(chunk * rep, -1)
            kg = k[:, g * hd:(g + 1) * hd]          # [pg, hd]
            logits.append(jax.lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
                .reshape(chunk, rep, page_size))
        s_log = jnp.concatenate(logits, axis=1)     # [chunk, nh, pg]
        s_log = s_log.reshape(chunk * nh, page_size) * sm_scale

        # causal ragged masking: key position p*pg + j is visible to
        # chunk row c iff it is <= t0 + c (the row's absolute position)
        col = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (chunk * nh, page_size), 1)
        row = jax.lax.broadcasted_iota(
            jnp.int32, (chunk * nh, page_size), 0) // nh
        valid = col <= t0 + row
        s_log = jnp.where(valid, s_log, NEG_INF)

        m_cur = jnp.max(s_log, axis=-1, keepdims=True)   # [chunk*nh, 1]
        m_new = jnp.maximum(m_prev[:, :1], m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new)
        pexp = jnp.exp(s_log - m_new)
        pexp = jnp.where(valid, pexp, 0.0)
        l_scr[:] = jnp.broadcast_to(
            corr * l_prev[:, :1] + jnp.sum(pexp, -1, keepdims=True),
            l_scr.shape)
        pe = pexp.reshape(chunk, nh, page_size)
        pv = []
        for g in range(kv_heads):
            pv.append(jax.lax.dot_general(
                pe[:, g * rep:(g + 1) * rep].reshape(chunk * rep, -1),
                v[:, g * hd:(g + 1) * hd], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                .reshape(chunk, rep, -1))
        pv = jnp.concatenate(pv, axis=1).reshape(chunk * nh, -1)
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(p == pages_per_slot - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)              # idle-slot guard
        o_ref[0] = (acc_scr[:] / l).reshape(
            chunk, kv_heads * rep, -1).astype(o_ref.dtype)


def _ragged_prefill_pallas(q, k_pages, v_pages, block_tables, t0, last,
                           sm_scale, interpret=False, layer=None):
    """q [S, C, nh, hd]; pages [L, P, pg, kvh*hd] read at ``layer``, or
    one layer's [P, pg, kvh, hd] (``as_layered``); block_tables
    [S, maxp] int32 (unused tail entries must hold any VALID page id,
    e.g. 0); t0/last [S] int32 (last = t0 + take - 1, or -1 to skip the
    slot). Returns [S, C, nh, hd]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k_pages, v_pages, layer = as_layered(k_pages, v_pages, layer)
    S, C, nh, hd = q.shape
    _, P, pg, width = k_pages.shape
    kvh = width // hd
    maxp = block_tables.shape[1]
    rep = nh // kvh
    if nh % kvh:
        raise ValueError(f"query heads ({nh}) must be a multiple of kv "
                         f"heads ({kvh})")

    flat_bt = block_tables.reshape(-1).astype(jnp.int32)
    kernel = functools.partial(
        _ragged_prefill_kernel, page_size=pg, pages_per_slot=maxp,
        chunk=C, kv_heads=kvh, rep=rep, sm_scale=sm_scale)

    def rows(s, p, bt, t0_, ls, l):
        return (s, 0, 0, 0)

    def page(s, p, bt, t0_, ls, l):
        return (l[0], bt[s * maxp + p], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, maxp),
        in_specs=[
            pl.BlockSpec((1, C, nh, hd), rows),
            pl.BlockSpec((1, 1, pg, width), page),
            pl.BlockSpec((1, 1, pg, width), page),
        ],
        out_specs=pl.BlockSpec((1, C, nh, hd), rows),
        scratch_shapes=[
            pltpu.VMEM((C * nh, 128), jnp.float32),
            pltpu.VMEM((C * nh, 128), jnp.float32),
            pltpu.VMEM((C * nh, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="ragged_prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, C, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(flat_bt, t0.astype(jnp.int32), last.astype(jnp.int32), layer,
      q, k_pages, v_pages)


# ------------------------------------------------- mesh-sharded kernel path


def _ragged_prefill_sharded(q, k_pages, v_pages, block_tables, t0, last,
                            layer, sm_scale, mesh, axis, interpret):
    """Per-shard Pallas launches over the mesh's ``axis`` (sharded
    paged serving): pools sharded on their merged kv-head axis, q split
    into the matching query-head groups (head axis 2 of
    [S, C, nh, hd]), block table / t0 / last / layer replicated, output
    restitched on the head axis — the same split
    ``paged_attention._paged_attention_sharded`` makes for decode.
    Returns None when the head counts don't divide the axis; the caller
    then runs one replicated launch."""
    from jax.sharding import PartitionSpec as P

    kvh = k_pages.shape[-1] // q.shape[-1]
    if kv_head_shards(mesh, kvh, q.shape[2], axis) <= 1:
        return None
    def fn(q, k_pages, v_pages, block_tables, t0, last, layer):
        return _ragged_prefill_pallas(q, k_pages, v_pages, block_tables,
                                      t0, last, sm_scale, interpret, layer)

    pool = P(None, None, None, axis)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, None, axis, None), pool, pool, P(None, None),
                  P(None), P(None), P(None)),
        out_specs=P(None, None, axis, None), check_vma=False,
    )(q, k_pages, v_pages, block_tables, t0, last, layer)


# ------------------------------------------------------ XLA reference path


def _ref_ragged_prefill(q, k_pages, v_pages, block_tables, t0, sm_scale,
                        layer=None):
    """Gather-through-block-table reference. Mirrors the dense prefill
    attention (``generation._cached_attend``) op-for-op so the ragged
    prefill path emits BIT-IDENTICAL cache rows and logits to the dense
    batch-1 prefill on every platform: valid positions carry the exact
    cached values, positions beyond a row's causal frontier are masked
    to -1e30 before the same f32 softmax (contributing exactly 0.0),
    and the einsum specs match."""
    k_pages, v_pages, layer = as_layered(k_pages, v_pages, layer)
    S, C, nh, hd = q.shape
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
    logits = jnp.einsum("bsnd,btnd->bnst", q, k) * sm_scale
    pos = jnp.arange(T)
    row = t0[:, None] + jnp.arange(C, dtype=jnp.int32)[None]   # [S, C]
    ok = pos[None, None] <= row[:, :, None]                    # [S, C, T]
    logits = jnp.where(ok[:, None], logits.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", p, v)


# --------------------------------------------------------------- public


def ragged_prefill_attention(q, k_pages, v_pages, block_tables, t0,
                             last=None, sm_scale=None, interpret=False,
                             mesh=None, layer=None):
    """Ragged packed-prefill attention over paged KV.

    q            [slots, chunk, num_heads, head_dim]  packed prompt
                 chunks, one variable-length segment per slot (shorter
                 segments are padded on the right; their garbage rows
                 are causally self-contained and discarded by the
                 caller)
    k_pages      the global pool: with ``layer`` given, every layer
                 lane-dense ``[layers, num_pages, page_size, kv_heads *
                 head_dim]``; without, one layer per head
                 ``[num_pages, page_size, kv_heads, head_dim]``
    v_pages      same shape as ``k_pages``
    layer        int32 scalar (may be traced): the layer of the pool
                 this call reads (see ``paged_attention``)
    block_tables [slots, pages_per_slot] int32  page ids in position
                 order; entries past a slot's allocation must hold a
                 valid id (the manager fills them with 0)
    t0           [slots] int32  absolute position of each slot's first
                 chunk row — the prefix offset (cached pages before it
                 are attended through the block table)
    last         [slots] int32  last position each slot's chunk writes
                 (t0 + take - 1); -1 skips the slot entirely. Defaults
                 to ``t0 + chunk - 1`` (every row live).

    Row c of slot s attends to key positions <= t0[s] + c. Returns
    [slots, chunk, num_heads, head_dim]. Runs the Pallas kernel on TPU
    (or under ``interpret=True`` anywhere); elsewhere the gather-based
    XLA composition, which is bit-identical to the dense prefill path.
    ``mesh`` (sharded paged serving) splits the kernel launch per
    kv-head shard exactly like ``paged_attention`` — ignored on the
    XLA fallback, where GSPMD partitions from the pool's sharding.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if last is None:
        last = t0 + q.shape[1] - 1
    k_pages, v_pages, layer = as_layered(k_pages, v_pages, layer)

    def _launch(qt, t0t, lastt):
        if mesh is not None:
            out = _ragged_prefill_sharded(qt, k_pages, v_pages,
                                          block_tables, t0t, lastt, layer,
                                          sm_scale, mesh, "mp", interpret)
            if out is not None:
                return out
        return _ragged_prefill_pallas(qt, k_pages, v_pages, block_tables,
                                      t0t, lastt, sm_scale,
                                      interpret=interpret, layer=layer)

    if available() or interpret:
        # the kernel's VMEM scratch is (rows * nh)-tall: tile the query
        # rows so scratch stays bounded whatever chunk width the
        # scheduler packs (prefill_tokens_per_tick defaults to
        # max_cache_len — untiled, a long first chunk would blow VMEM
        # at serve time). Row r of tile starting at r0 sits at absolute
        # position t0 + r0 + r, so each tile is just a ragged launch
        # with a shifted prefix offset; the idle sentinel (last = -1)
        # survives the min(). The tiles are a LOOP over one launch, not
        # C/8 launches spelled out: the kernel is traced and lowered
        # once a program whatever its width (unrolled, a C=512 program
        # traced 64 kernels: 24 s of a 100 s server start on the chip's
        # host, PERF.md section 6, PR 26).
        C, tile = q.shape[1], _QUERY_TILE
        if C <= tile:
            return _launch(q, t0, last)
        pad = -C % tile                 # never on the server's pow2 ladder
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q

        def one_tile(i, out):
            r0 = i * tile
            qt = jax.lax.dynamic_slice_in_dim(qp, r0, tile, axis=1)
            lastt = jnp.minimum(last, t0 + r0 + tile - 1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, _launch(qt, t0 + r0, lastt), r0, axis=1)

        out = jax.lax.fori_loop(0, (C + pad) // tile, one_tile,
                                jnp.zeros_like(qp))
        return out[:, :C] if pad else out
    out = _ref_ragged_prefill(q, k_pages, v_pages, block_tables, t0,
                              sm_scale, layer=layer)
    # platform-consistent skip semantics: the kernel's idle slots
    # (last < 0) finalize to zeros through the empty-accumulator guard;
    # zero the same rows here so fallback output matches bit-for-bit
    return jnp.where((last < 0)[:, None, None, None],
                     jnp.zeros_like(out), out)
