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

Kernel shape: one query token per slot (decode step). The grid is FLAT
and as long as the call has live pages: one step for every page a slot's
valid tokens span, slot after slot, a slot's pages in position order —
``decode_grid`` counts them from the lengths, ``decode_schedule`` lists
them, and the count is a DYNAMIC grid bound, so a tick with three
decoding slots of a dozen pages takes three dozen steps and not ``slots
x pages_per_slot``. A step accumulates an online softmax in VMEM scratch
exactly like ``flash_attention._fwd_kernel``, initialised at a slot's
first page and written out at its last; the schedule, the block table
and the per-slot lengths ride ``PrefetchScalarGridSpec`` scalar prefetch,
so the page DMA for step ``g`` is issued from the block-table entry
``schedule[g]`` names before the body runs. A slot of length 0 (idle, or
mid-prefill) has no step and its output rows are zeros. GQA is handled
in-kernel (query-head groups attend to their kv head) so the pool stores
kv heads unrepeated. The ragged prefill kernel (``ragged_prefill.py``)
follows its live work the same way and its grid is owned here too:
``prefill_grid`` counts the pages of every live (row, query tile) pair
of a launch, ``prefill_schedule`` lists them.

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
import numpy as np

from . import on_tpu

NEG_INF = -1e30

__all__ = ["paged_attention", "decode_grid", "decode_schedule",
           "prefill_grid", "prefill_schedule", "window_start", "available"]


def available() -> bool:
    return on_tpu()


# ----------------------------------------------------------------- kernel


def window_start(lengths, window):
    """The first key position a query sees in a WINDOW layer, for a
    query whose row holds ``lengths`` valid tokens (its position is
    ``lengths - 1``): key ``j`` is visible to query ``p`` iff ``p - j <
    window``, so the first is ``max(0, lengths - window)``. THE owner of
    the window's edge: both kernels' grids, index maps, first pages and
    masks, and the host's count of the keys a window layer attends, read
    it here. Plain operators, for NumPy values, traced arrays and a
    kernel's scalars alike."""
    over = lengths - window
    return over * (over > 0)


def decode_grid(lengths, page_size, window=None):
    """``(pages, steps)`` of one decode call: the pages each slot's
    ``lengths`` valid tokens span ([slots]; 0 for a slot of length 0)
    and the steps the kernel's grid takes — their sum, the live pages,
    and one step where nothing is live (it finds length 0 and computes
    nothing). With a ``window`` the span starts at the page of the
    first key the window shows (``window_start``). Plain operators, so
    it counts NumPy lengths on the host (the server's
    ``decode_grid_steps``) as it sizes the grid from traced ones on the
    device: one owner of the count."""
    pages = (lengths + (page_size - 1)) // page_size
    if window is not None:
        pages = pages - window_start(lengths, window) // page_size
    live = pages.sum()
    return pages, live + (live == 0)


def decode_schedule(lengths, page_size, pages_per_slot, window=None):
    """``(entries, steps)``: what each step of the decode grid reads.
    ``entries[g]`` is the FLAT block-table index ``slot * pages_per_slot
    + page`` of step ``g < steps``, slot-major with a slot's pages in
    position order, so a slot's steps are one consecutive run (its
    online softmax never interleaves with another's and its output
    block is visited once). ``entries`` has the static length ``slots *
    pages_per_slot``; past ``steps`` it holds valid indices nobody
    visits. A function of the lengths alone: every layer of a tick
    attends the same lengths, so the tick computes it once (once a
    layer KIND: a ``window`` layer's run starts at its window's first
    page, ``decode_grid``)."""
    slots = lengths.shape[0]
    lengths = lengths.astype(jnp.int32)
    pages, steps = decode_grid(lengths, page_size, window)
    ends = jnp.cumsum(pages)
    g = jnp.arange(slots * pages_per_slot, dtype=jnp.int32)
    # the slot whose run [end - pages, end) holds g
    slot = jnp.minimum(jnp.sum(g[:, None] >= ends[None, :], axis=1),
                       slots - 1)
    page = g - (ends - pages)[slot]
    if window is not None:
        page = page + (window_start(lengths, window) // page_size)[slot]
    page = jnp.clip(page, 0, pages_per_slot - 1)
    return ((slot * pages_per_slot + page).astype(jnp.int32),
            steps.astype(jnp.int32))


# entries the prefill schedule's coarse index may take of scalar memory
# (1 MiB on a v5e, which the block table shares): past it the index names
# the pair of every ``block``-th step only
_INDEX_ENTRIES = 32768


def prefill_grid(t0, take, width, tile, page_size, pages_per_slot,
                 window=None):
    """``(pages, steps)`` of one prefill launch: the pages each (row,
    query tile) pair attends ([rows, tiles]; 0 for a pair that is not
    live) and the steps the kernel's grid takes — their sum, and one
    step where nothing is live (it finds no page and computes nothing).
    ``t0`` [rows] is each row's first position (the idle sentinel, at
    or past the table's span, for a row with no work), ``take`` [rows]
    its REAL rows of the ``width`` the launch carries. Tile ``i`` is
    live while ``i * tile < take`` and attends through its last row's
    page: the padding rows INSIDE a row's last live tile are computed
    with it, a tile wholly past ``take`` is not. With a ``window`` a
    pair attends from the page of the first key its FIRST row sees
    (``window_start``). Plain operators, so it counts NumPy values on
    the host (the server's ``prefill_grid_steps``) as it sizes the grid
    from traced ones on the device: one owner of the count."""
    first = np.arange(0, width, tile, dtype=np.int32)          # [tiles]
    ends = np.minimum(first + tile, width)
    live = (t0 < page_size * pages_per_slot)[:, None] \
        & (first[None, :] < take[:, None])
    reach = (t0[:, None] + (ends[None, :] - 1)) // page_size + 1
    over = reach - pages_per_slot       # padding rows past the table
    pages = live * (reach - over * (over > 0))
    if window is not None:
        # the tile's first row holds t0 + first + 1 tokens
        lo = window_start(t0[:, None] + first[None, :] + 1, window)
        pages = pages - live * (lo // page_size)
    total = pages.sum()
    return pages, total + (total == 0)


def prefill_index_block(pairs, pages_per_slot):
    """Steps a coarse-index entry stands for: 1 while an index of every
    step a launch of this shape can take fits ``_INDEX_ENTRIES``."""
    return -(-pairs * pages_per_slot // _INDEX_ENTRIES)


def prefill_schedule(t0, take, width, tile, page_size, pages_per_slot,
                     window=None):
    """``(pair, bounds, index, steps)``: what each step of the prefill
    grid reads. The LIVE pairs of ``prefill_grid`` in row-major,
    tile-major order: ``pair[n]`` is the n-th's ``row * tiles + tile``
    and its steps are ``bounds[n] <= g < bounds[n + 1]``, one a page in
    position order — one consecutive run (its online softmax never
    interleaves with another's and its output block is visited once).
    ``index[g // block]`` (``prefill_index_block``) is the n whose run
    holds step ``g - g % block``; every live pair has a step, so step
    ``g``'s is at most ``g % block`` further on. Static lengths ``rows *
    tiles``, that plus one, and the launch's most steps over ``block``;
    past the live pairs they hold valid values nobody visits. A
    function of the launch's ``t0`` and ``take`` alone: every layer
    attends the same chunks, so the launch computes it once (once a
    layer KIND: a ``window`` layer's pairs start at their window's
    first page, which the kernel finds from ``t0`` as the grid does)."""
    pages, steps = prefill_grid(t0.astype(jnp.int32),
                                take.astype(jnp.int32), width, tile,
                                page_size, pages_per_slot, window)
    pages = pages.reshape(-1).astype(jnp.int32)
    pairs = pages.shape[0]
    n = jnp.arange(pairs, dtype=jnp.int32)
    # the n-th live pair: the first with n + 1 live ones through it
    seen = jnp.cumsum(pages > 0)
    pair = jnp.minimum(jnp.sum(seen[None, :] <= n[:, None], axis=1),
                       pairs - 1).astype(jnp.int32)
    ends = jnp.cumsum(pages)[pair]           # the total past the live ones
    block = prefill_index_block(pairs, pages_per_slot)
    at = block * jnp.arange(-(-pairs * pages_per_slot // block),
                            dtype=jnp.int32)
    index = jnp.minimum(jnp.sum(ends[None, :] <= at[:, None], axis=1),
                        pairs - 1).astype(jnp.int32)
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              ends.astype(jnp.int32)])
    return pair, bounds, index, steps.astype(jnp.int32)


def prefill_step_pair(g, bounds_ref, index_ref, pairs, block):
    """The n of the live pair whose run holds grid step ``g`` (see
    ``prefill_schedule``): the coarse index's, moved on past every pair
    that ends at or before ``g``."""
    # lax's truncating division: every operand is non-negative, and an
    # index map is traced and lowered once a call (plain ``//`` is a
    # dozen scalar operations)
    n = index_ref[g if block == 1 else jax.lax.div(g, np.int32(block))]
    for _ in range(block - 1):
        n = jax.lax.min(n + (bounds_ref[n + 1] <= g).astype(jnp.int32),
                        np.int32(pairs - 1))
    return n


def _paged_attn_kernel(sched_ref, bt_ref, len_ref, layer_ref, q_ref, k_ref,
                       v_ref, o_ref, m_scr, l_scr, acc_scr, *, page_size,
                       pages_per_slot, kv_heads, rep, sm_scale, window=None):
    """Grid (steps,); step g attends page p of slot s, ``sched_ref[g] ==
    s * pages_per_slot + p`` (``decode_schedule``). With a ``window``
    a slot's run starts at the page of its window's first key, and keys
    before it are masked (``window_start``).

    q_ref  [1, nh, hd]       slot s's query token
    k_ref  [1, 1, page_size, kvh*hd]  the page block_tables[s, p] points
                             at, in layer layer_ref[0]; kv head g is
                             lanes [g*hd, (g+1)*hd)
    len_ref[s]               valid KV tokens for slot s (ragged lengths)
    Scratch m/l/acc carry the online softmax across a slot's run of
    steps, from its page 0 to the page that holds its last token.
    """
    from jax.experimental import pallas as pl

    entry = sched_ref[pl.program_id(0)]
    s = entry // pages_per_slot
    p = entry % pages_per_slot
    # the page a slot's run starts at
    first = 0 if window is None else jax.lax.div(
        window_start(len_ref[s], np.int32(window)), np.int32(page_size))

    @pl.when(p == first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[s]

    # a scheduled page holds valid tokens; only the lone step of a call
    # with nothing live (length 0) finds none and computes nothing
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
        if window is not None:      # the query at length - 1 sees
            valid = valid & (col >= window_start(length, np.int32(window)))

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

    # the slot's last page: the next step, if any, is another slot's
    @pl.when((p + 1) * page_size >= length)
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
                            sm_scale, interpret=False, layer=None,
                            schedule=None, window=None):
    """q [S, nh, hd]; pages [L, P, pg, kvh*hd] read at ``layer``, or one
    layer's [P, pg, kvh, hd] (``as_layered``); block_tables [S, maxp]
    int32 (unused tail entries must hold any VALID page id, e.g. 0);
    lengths [S] int32; ``schedule`` what ``decode_schedule`` makes of
    them (made here when the caller has none to share between layers);
    ``window`` (static) the window of a window layer, None for a full
    one. Returns [S, nh, hd]."""
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

    lengths = lengths.astype(jnp.int32)
    if schedule is None:
        schedule = decode_schedule(lengths, pg, maxp, window)
    entries, steps = schedule
    flat_bt = block_tables.reshape(-1).astype(jnp.int32)
    kernel = functools.partial(
        _paged_attn_kernel, page_size=pg, pages_per_slot=maxp,
        kv_heads=kvh, rep=rep, sm_scale=sm_scale,
        window=window)

    def row(g, sched, bt, ln, l):
        return (sched[g] // maxp, 0, 0)

    def page(g, sched, bt, ln, l):
        return (l[0], bt[sched[g]], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, nh, hd), row),
            pl.BlockSpec((1, 1, pg, width), page),
            pl.BlockSpec((1, 1, pg, width), page),
        ],
        out_specs=pl.BlockSpec((1, nh, hd), row),
        scratch_shapes=[
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(entries, flat_bt, lengths, layer, q, k_pages, v_pages)
    # a slot of length 0 has no step: nothing wrote its output block
    return jnp.where((lengths > 0)[:, None, None], out, 0)


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
                             layer, schedule, sm_scale, mesh, axis,
                             interpret, window=None):
    """Per-shard Pallas launches over the mesh's ``axis``: the page
    pools arrive sharded on their merged kv-head axis (contiguous
    blocks of whole heads), q splits into the matching query-head
    groups (a GQA group never straddles a shard — consecutive head
    blocks keep each kv head with its own rep query heads), the block
    table, lengths, layer index and grid schedule ride replicated
    (every shard takes the same steps), and the out_spec's
    head-axis concatenation IS the attention all-gather GSPMD would
    insert on the fallback path. XLA cannot partition a custom call,
    so the kernel path must shard_map itself; returns None when the
    head counts don't divide the axis — the caller then runs one
    replicated launch."""
    from jax.sharding import PartitionSpec as P

    kvh = k_pages.shape[-1] // q.shape[-1]
    if kv_head_shards(mesh, kvh, q.shape[1], axis) <= 1:
        return None
    def fn(q, k_pages, v_pages, block_tables, lengths, layer, schedule):
        return _paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                       lengths, sm_scale, interpret, layer,
                                       schedule, window)

    pool = P(None, None, None, axis)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, axis, None), pool, pool, P(None, None), P(None),
                  P(None), (P(None), P())),
        out_specs=P(None, axis, None), check_vma=False,
    )(q, k_pages, v_pages, block_tables, lengths, layer, schedule)


# ------------------------------------------------------ XLA reference path


def _ref_paged_attention(q, k_pages, v_pages, block_tables, lengths,
                         sm_scale, layer=None, window=None):
    """Gather-through-block-table reference. Mirrors the dense decode
    attention (`generation._cached_attend` at s=1) op-for-op so the paged
    server emits bit-identical tokens to the dense backend on every
    platform: valid positions carry the exact cached values, positions at
    or beyond ``lengths`` (and, with a ``window``, before its first key)
    are masked to -1e30 before the same f32 softmax (contributing
    exactly 0.0), and the einsum specs match."""
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
    if window is not None:
        ok = ok & (pos[None, None]
                   >= window_start(lengths, window)[:, None, None])
    logits = jnp.where(ok[:, None], logits.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", p, v)[:, 0]


# --------------------------------------------------------------- public


def paged_attention(q, k_pages, v_pages, block_tables, lengths,
                    sm_scale=None, interpret=False, mesh=None, layer=None,
                    schedule=None, window=None):
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
    schedule     ``decode_schedule(lengths, page_size, pages_per_slot,
                 window)`` where the caller already has it — the serving
                 tick makes it once for all its layers of a kind; made
                 here otherwise
    window       None (a full layer), or a WINDOW layer's window: the
                 query at position ``lengths - 1`` sees key ``j`` iff
                 ``lengths - 1 - j < window``, and the grid starts at
                 the page of the first such key (``window_start``); the
                 table may then be a ring's (entries repeat)
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
        if schedule is None:
            schedule = decode_schedule(lengths, k_pages.shape[2],
                                       block_tables.shape[1], window)
        if mesh is not None:
            out = _paged_attention_sharded(
                q, k_pages, v_pages, block_tables, lengths, layer,
                schedule, sm_scale, mesh, "mp", interpret, window)
            if out is not None:
                return out
        return _paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                       lengths, sm_scale,
                                       interpret=interpret, layer=layer,
                                       schedule=schedule, window=window)
    return _ref_paged_attention(q, k_pages, v_pages, block_tables,
                                lengths, sm_scale, layer=layer,
                                window=window)
