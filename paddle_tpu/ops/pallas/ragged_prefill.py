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

Kernel shape: ONE call a layer whose grid is FLAT and as long as the
launch has live work. The chunk's rows are cut into QUERY TILES of
``QUERY_TILE`` rows (the VMEM scratch is ``tile * num_heads`` rows
tall whatever chunk width the scheduler packs); a (row, tile) PAIR is
live when the row is no idle one and the tile holds a real row, and it
attends the pages from 0 to the one its last row's position falls in.
A grid step is one page of one live pair, row-major, tile-major, a
pair's pages in position order — ``prefill_grid`` counts them,
``prefill_schedule`` lists them, and the count is a DYNAMIC grid bound,
so a launch that carries one prompt of 100 tokens takes some 50 steps a
layer and not ``rows x tiles x pages_per_slot``. A pair's steps are one
consecutive run: its online softmax (VMEM scratch, as the decode
kernel's) is initialised at its first page and written out at its last,
and its output block is visited once. The schedule, the block table and
``t0`` ride ``PrefetchScalarGridSpec`` scalar prefetch, so a step's
page DMA is issued from the block-table entry the schedule names before
the body runs. Rows of a pair no step visits (an idle row, a padding
tile wholly past the chunk's real rows) read 0.

The schedule is TWO-LEVEL so that scalar memory holds it at any context
length: the live pairs with the running sum of their pages (at most
``rows * tiles`` entries: 512 for a 4,096-row launch), and a coarse
index — the pair that holds every ``block``-th step — from which a step
finds its pair in under ``block`` comparisons. ``block`` is 1 (the index
names every step's pair outright) until ``pairs * pages_per_slot``
passes ``paged_attention._INDEX_ENTRIES``. The grid's count and schedule
live beside the decode kernel's (``paged_attention.prefill_grid``,
``prefill_schedule``): one owner for both kernels' live-page grids.

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
import numpy as np

from . import on_tpu
from .paged_attention import (NEG_INF, as_layered, kv_head_shards,
                              prefill_grid, prefill_index_block,
                              prefill_schedule, prefill_step_pair)

__all__ = ["ragged_prefill_attention", "QUERY_TILE", "available"]

# query rows a grid step attends: scratch is (rows * num_heads)-tall in
# VMEM, so a chunk of any width is cut into tiles of this many rows
QUERY_TILE = 8


def available() -> bool:
    return on_tpu()


# ----------------------------------------------------------------- kernel


def _ragged_prefill_kernel(pair_ref, bounds_ref, index_ref, bt_ref, t0_ref,
                           layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                           l_scr, acc_scr, *, page_size, tile, tiles, pairs,
                           block, kv_heads, rep, sm_scale):
    """Grid (steps,); step g attends one page of one live (row, query
    tile) pair, found through the schedule (``prefill_schedule``).

    q_ref  [1, tile, nh, hd]        the pair's query rows
    k_ref  [1, 1, page_size, kvh*hd]  the page the row's block table
                names at the step's position, in layer layer_ref[0]; kv
                head g is lanes [g*hd, (g+1)*hd)
    t0_ref[r]   absolute position of row r's first chunk row (prefix
                offset)
    Scratch m/l/acc carry the online softmax across a pair's run of
    steps, one row per (query row, query head).
    """
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    n = prefill_step_pair(g, bounds_ref, index_ref, pairs, block)
    start, end = bounds_ref[n], bounds_ref[n + 1]
    p = g - start
    nh = kv_heads * rep

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # position of the tile's first row
    at = pair_ref[n]
    t0 = (t0_ref[jax.lax.div(at, np.int32(tiles))]
          + jax.lax.rem(at, np.int32(tiles)) * tile)

    # a scheduled step is a page some row of its tile attends; only the
    # lone step of a launch with nothing live (an empty run) computes
    # nothing
    @pl.when(g < end)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [tile, nh, hd]
        k = k_ref[0, 0].astype(jnp.float32)         # [pg, kvh*hd]
        v = v_ref[0, 0].astype(jnp.float32)
        hd = q.shape[-1]
        m_prev = m_scr[:]                           # [tile*nh, 128]
        l_prev = l_scr[:]

        # per-kv-head-group contractions keep the MXU ops unbatched
        logits = []
        for h in range(kv_heads):
            qg = q[:, h * rep:(h + 1) * rep].reshape(tile * rep, -1)
            kg = k[:, h * hd:(h + 1) * hd]          # [pg, hd]
            logits.append(jax.lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
                .reshape(tile, rep, page_size))
        s_log = jnp.concatenate(logits, axis=1)     # [tile, nh, pg]
        s_log = s_log.reshape(tile * nh, page_size) * sm_scale

        # causal ragged masking: key position p*pg + j is visible to
        # tile row c iff it is <= t0 + c (the row's absolute position)
        col = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (tile * nh, page_size), 1)
        row = jax.lax.broadcasted_iota(
            jnp.int32, (tile * nh, page_size), 0) // nh
        valid = col <= t0 + row
        s_log = jnp.where(valid, s_log, NEG_INF)

        m_cur = jnp.max(s_log, axis=-1, keepdims=True)   # [tile*nh, 1]
        m_new = jnp.maximum(m_prev[:, :1], m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new)
        pexp = jnp.exp(s_log - m_new)
        pexp = jnp.where(valid, pexp, 0.0)
        l_scr[:] = jnp.broadcast_to(
            corr * l_prev[:, :1] + jnp.sum(pexp, -1, keepdims=True),
            l_scr.shape)
        pe = pexp.reshape(tile, nh, page_size)
        pv = []
        for h in range(kv_heads):
            pv.append(jax.lax.dot_general(
                pe[:, h * rep:(h + 1) * rep].reshape(tile * rep, -1),
                v[:, h * hd:(h + 1) * hd], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                .reshape(tile, rep, -1))
        pv = jnp.concatenate(pv, axis=1).reshape(tile * nh, -1)
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    # the pair's last page: the next step, if any, is another pair's
    @pl.when(g + 1 == end)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)              # empty-row guard
        o_ref[0] = (acc_scr[:] / l).reshape(
            tile, kv_heads * rep, -1).astype(o_ref.dtype)


def _ragged_prefill_pallas(q, k_pages, v_pages, block_tables, t0, take,
                           sm_scale, interpret=False, layer=None,
                           schedule=None):
    """q [S, C, nh, hd]; pages [L, P, pg, kvh*hd] read at ``layer``, or
    one layer's [P, pg, kvh, hd] (``as_layered``); block_tables
    [S, maxp] int32 (unused tail entries must hold any VALID page id,
    e.g. 0); t0/take [S] int32; ``schedule`` what ``prefill_schedule``
    makes of them (made here when the caller has none to share between
    layers). Returns [S, C, nh, hd]."""
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

    tile = QUERY_TILE
    t0, take = t0.astype(jnp.int32), take.astype(jnp.int32)
    if schedule is None:
        schedule = prefill_schedule(t0, take, C, tile, pg, maxp)
    pair, bounds, index, steps = schedule
    tiles = -(-C // tile)
    pairs = S * tiles
    block = prefill_index_block(pairs, maxp)
    pad = tiles * tile - C              # never on the server's pow2 ladder
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    flat_bt = block_tables.reshape(-1).astype(jnp.int32)
    kernel = functools.partial(
        _ragged_prefill_kernel, page_size=pg, tile=tile, tiles=tiles,
        pairs=pairs, block=block, kv_heads=kvh, rep=rep, sm_scale=sm_scale)

    # truncating lax.div / lax.rem (the operands are non-negative): ``//``
    # and ``%`` are a dozen scalar operations each, in every index map
    ntiles = np.int32(tiles)

    def rows(g, pr, bd, ix, bt, t0_, l):
        at = pr[prefill_step_pair(g, bd, ix, pairs, block)]
        return (jax.lax.div(at, ntiles), jax.lax.rem(at, ntiles), 0, 0)

    def page(g, pr, bd, ix, bt, t0_, l):
        n = prefill_step_pair(g, bd, ix, pairs, block)
        p = jax.lax.min(g - bd[n], np.int32(maxp - 1))
        return (l[0], bt[jax.lax.div(pr[n], ntiles) * maxp + p], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, tile, nh, hd), rows),
            pl.BlockSpec((1, 1, pg, width), page),
            pl.BlockSpec((1, 1, pg, width), page),
        ],
        out_specs=pl.BlockSpec((1, tile, nh, hd), rows),
        scratch_shapes=[
            pltpu.VMEM((tile * nh, 128), jnp.float32),
            pltpu.VMEM((tile * nh, 128), jnp.float32),
            pltpu.VMEM((tile * nh, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="ragged_prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pair, bounds, index, flat_bt, t0, layer, q, k_pages, v_pages)
    return out[:, :C] if pad else out


def _visited(out, t0, take, page_size, pages_per_slot):
    """``out`` [S, C, nh, hd] with the rows of every (row, tile) pair
    that has no grid step (``prefill_grid``) read as 0: nothing wrote
    the kernel's output block there."""
    C = out.shape[1]
    pages, _ = prefill_grid(t0, take, C, QUERY_TILE, page_size,
                            pages_per_slot)
    live = jnp.repeat(pages > 0, QUERY_TILE, axis=1)[:, :C]
    return jnp.where(live[:, :, None, None], out, 0)


# ------------------------------------------------- mesh-sharded kernel path


def _ragged_prefill_sharded(q, k_pages, v_pages, block_tables, t0, take,
                            layer, schedule, sm_scale, mesh, axis,
                            interpret):
    """Per-shard Pallas launches over the mesh's ``axis`` (sharded
    paged serving): pools sharded on their merged kv-head axis, q split
    into the matching query-head groups (head axis 2 of
    [S, C, nh, hd]), block table / t0 / take / layer / grid schedule
    replicated (every shard takes the same steps), output restitched on
    the head axis — the same split
    ``paged_attention._paged_attention_sharded`` makes for decode.
    Returns None when the head counts don't divide the axis; the caller
    then runs one replicated launch."""
    from jax.sharding import PartitionSpec as P

    kvh = k_pages.shape[-1] // q.shape[-1]
    if kv_head_shards(mesh, kvh, q.shape[2], axis) <= 1:
        return None
    def fn(q, k_pages, v_pages, block_tables, t0, take, layer, schedule):
        return _ragged_prefill_pallas(q, k_pages, v_pages, block_tables,
                                      t0, take, sm_scale, interpret, layer,
                                      schedule)

    pool = P(None, None, None, axis)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, None, axis, None), pool, pool, P(None, None),
                  P(None), P(None), P(None),
                  (P(None), P(None), P(None), P())),
        out_specs=P(None, None, axis, None), check_vma=False,
    )(q, k_pages, v_pages, block_tables, t0, take, layer, schedule)


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
                             take=None, sm_scale=None, interpret=False,
                             mesh=None, layer=None, schedule=None):
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
                 are attended through the block table); at or past the
                 table's span (the scheduler's idle sentinel) the slot
                 is skipped entirely
    take         [slots] int32  the REAL rows of each slot's chunk; 0
                 skips the slot. Defaults to ``chunk`` (every row live).
    schedule     ``prefill_schedule(t0, take, chunk, QUERY_TILE, page_size,
                 pages_per_slot)`` where the caller already has it — a
                 launch makes it once for all its layers; made here
                 otherwise

    Row c of slot s attends to key positions <= t0[s] + c; the rows of
    a query tile that holds no real row (``prefill_grid``) are 0.
    Returns [slots, chunk, num_heads, head_dim]. Runs the Pallas kernel
    on TPU (or under ``interpret=True`` anywhere); elsewhere the
    gather-based XLA composition, which is bit-identical to the dense
    prefill path. ``mesh`` (sharded paged serving) splits the kernel
    launch per kv-head shard exactly like ``paged_attention`` — ignored
    on the XLA fallback, where GSPMD partitions from the pool's
    sharding.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if take is None:
        take = jnp.full(t0.shape, q.shape[1], jnp.int32)
    k_pages, v_pages, layer = as_layered(k_pages, v_pages, layer)
    pg, maxp = k_pages.shape[2], block_tables.shape[1]
    if available() or interpret:
        if schedule is None:
            schedule = prefill_schedule(t0, take, q.shape[1], QUERY_TILE,
                                        pg, maxp)
        out = None
        if mesh is not None:
            out = _ragged_prefill_sharded(
                q, k_pages, v_pages, block_tables, t0, take, layer,
                schedule, sm_scale, mesh, "mp", interpret)
        if out is None:
            out = _ragged_prefill_pallas(
                q, k_pages, v_pages, block_tables, t0, take, sm_scale,
                interpret=interpret, layer=layer, schedule=schedule)
    else:
        out = _ref_ragged_prefill(q, k_pages, v_pages, block_tables, t0,
                                  sm_scale, layer=layer)
    # platform-consistent skip semantics: the kernel never writes the
    # rows of a pair it has no step for; zero the same rows of the
    # fallback so the two match there too
    return _visited(out, t0, take, pg, maxp)
