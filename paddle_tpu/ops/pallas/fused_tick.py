"""Pallas fused mixed prefill/decode tick attention (TPU) — ISSUE 14.

One serving tick used to be several device programs: a ragged-prefill
launch for the admission wave, the s=1 decode program for live slots,
plus the state pushes between them — and both paged kernels issued page
DMAs across the FULL block-table width per slot, masking (but paying
for) every page beyond a slot's live length (the PR-6 cut the goodput
ledger priced at a 0.001 paged goodput ratio). This module is the
attention core of the fused tick (FlashFuser / "Tile-Level Activation
Overlap", PAPERS.md): every slot's work this tick — a prefill CHUNK at
its own prefix offset, a single s=1 DECODE row, or nothing — runs as
one kernel whose DMA schedule covers ONLY live pages.

Two ideas over ``ragged_prefill.py``:

- **Unified per-row phase.** A decode step at position ``t`` is exactly
  a one-row prefill chunk with ``t0 = t``: write K/V at ``t``, attend
  causally to positions ``<= t``. So one kernel covers both phases —
  each query row ``r`` of slot ``s`` attends to positions
  ``<= t0[s] + r``, with its own online softmax lane. (The XLA
  fallback still routes decode rows through an s=1-shaped einsum —
  XLA CPU's single-row matmul takes a fused-reduce path ~1 ulp off the
  multi-row one, the PR-6 measurement — so fused serving stays
  BIT-IDENTICAL to the unfused decode program on every platform.)
- **True page skipping.** The grid is not ``(slots, table_width)`` but
  a flat DMA SCHEDULE: scalar-prefetched ``(sched_slot, sched_page)``
  pairs listing, slot-major, exactly the live pages
  (``ceil((last+1)/page_size)`` per live slot). A page wholly beyond a
  slot's frontier is never DMAed — HBM traffic scales with live
  tokens, not the configured cache length. The schedule is padded up a
  quarter-octave ladder (pad entries carry ``slot == n_slots`` and are
  fully skipped) so compiles stay O(log total_pages) with pad bounded
  at ~25% of live entries, and the caller passes
  block tables SLICED to the live width for the same reason on the
  gather fallback: the compiled program's cost-analysis bytes are flat
  in the configured block-table width (test-asserted in
  tests/test_costs.py).

The XLA fallback (``_ref_fused_tick``) gathers the live-width table
slice and mirrors ``models/generation._cached_attend`` op-for-op —
prefill rows through the same s=C einsum as ``_ref_ragged_prefill``,
decode rows through the same s=1 einsum as ``_ref_paged_attention`` —
which keeps fused tokens bit-identical to both unfused paths (the
masked-softmax output is bitwise invariant to the gathered frame's
extent on this XLA version; pinned by tests/test_fused_tick.py).
CPU tests run the Pallas kernel via ``interpret=True``.
"""
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from . import on_tpu
from .paged_attention import NEG_INF
from .ragged_prefill import _QUERY_TILE

__all__ = ["fused_tick_attention", "build_schedule", "available",
           "refuse_on_tpu"]


def available() -> bool:
    return on_tpu()


def refuse_on_tpu():
    """The Mosaic build of this kernel compiles for a v5e and then
    HALTS THE CORE when it runs (PR 21, ``chip_smoke.py``): standalone,
    at GPT-2 345M geometry (8 slots, 16 heads x 64, page 16, 8-row query
    tiles, f32 and bf16), with a ladder-padded and with an exact
    schedule alike, and the process that launched it loses the chip. So
    on a real TPU the kernel — and ``serving_mode="fused"`` on top of it
    — refuses instead of either crashing the chip or quietly handing
    over to ``_ref_fused_tick``. The interpreter (``interpret=True``)
    and the XLA composition off-TPU are unaffected."""
    if jax.default_backend() == "tpu":
        raise NotImplementedError(
            "the fused-tick Pallas kernel halts a TPU v5e core at run "
            "time (libtpu 0.0.34: 'Core halted unexpectedly ... "
            "perhaps due to an on-device check-failure', "
            "TensorCoreSequencer) although Mosaic compiles it; until "
            "its Mosaic bring-up lands (ROADMAP A1) it runs only under "
            "interpret=True or as the XLA composition off-TPU — use "
            "serving_mode='split' on the chip")


# ------------------------------------------------------------- schedule


def _ladder(n, min_entries):
    """Quarter-octave schedule-length ladder: round ``n`` up to the
    next multiple of ``2**floor(log2 n) / 4``. Pad stays <= ~25% of
    the live entries (a plain pow2 ladder wastes up to ~100% right
    past each power — the dominant fused-goodput waste at long
    contexts) while the number of distinct compile signatures stays
    O(4 log total_pages)."""
    n = max(int(n), int(min_entries))
    step = max(1, (1 << (n.bit_length() - 1)) // 4)
    return -(-n // step) * step


def build_schedule(last, page_size, n_slots=None, min_entries=8):
    """Host-side DMA schedule for one fused launch.

    ``last`` ([S] ints): each slot's last written position this launch
    (prefill: ``t0 + take - 1``; decode: ``t``; idle: ``-1``). A live
    slot contributes entries ``(s, 0) .. (s, last // page_size)`` —
    exactly the pages any of its live rows may attend to — in slot-
    major page order (the kernel's online softmax accumulates one
    slot's run contiguously). The schedule is padded up a
    quarter-octave ladder (floor ``min_entries``; see ``_ladder``)
    with ``(n_slots, 0)`` sentinels the kernel skips, so the launch
    signature stays on an O(log) compile ladder while live page
    counts drift tick to tick, and the pad — the fused path's ONLY
    remaining masked DMA — stays <= ~25% of the live entries.

    Returns ``(sched_slot, sched_page, n_live)`` — two int32 arrays of
    equal ladder length and the number of real (unpadded) entries;
    ``(len - n_live) * page_size`` is the ledger's masked-DMA model
    for the launch.
    """
    last = np.asarray(last, np.int64)
    if n_slots is None:
        n_slots = last.shape[0]
    # vectorized: this runs on the host EVERY tick — no per-page
    # Python loop on the packing hot path
    npages = np.where(last >= 0, last // int(page_size) + 1, 0)
    n_live = int(npages.sum())
    total = _ladder(n_live, min_entries)
    ss = np.full(total, int(n_slots), np.int32)
    sp = np.zeros(total, np.int32)
    ss[:n_live] = np.repeat(np.arange(last.shape[0]), npages)
    sp[:n_live] = np.arange(n_live) - np.repeat(
        np.cumsum(npages) - npages, npages)
    return ss, sp, n_live


# ----------------------------------------------------------------- kernel


def _fused_tick_kernel(bt_ref, t0_ref, ss_ref, sp_ref, q_ref, k_ref,
                       v_ref, o_ref, m_scr, l_scr, acc_scr, *, page_size,
                       n_slots, table_width, chunk, kv_heads, rep,
                       sm_scale, n_steps):
    """Grid ``(n_steps,)`` — one scheduled (slot, page) per step.

    q_ref  [1, chunk, nh, hd]       the scheduled slot's packed rows
    k_ref  [1, page_size, kvh, hd]  the page bt[slot, sched_page[g]]
                                    points at
    t0_ref[s]  absolute position of slot s's first row (decode rows
               are one-row chunks at their write position)
    ss/sp      the DMA schedule (slot-major; pad entries carry
               ``slot == n_slots`` and skip everything)
    Scratch m/l/acc carry one slot's online softmax across its
    contiguous schedule run; the run finalizes when the next entry
    belongs to a different slot.
    """
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    s = ss_ref[g]
    live = s < n_slots
    s_idx = jnp.minimum(s, n_slots - 1)           # clamp sentinel reads
    prev_s = ss_ref[jnp.maximum(g - 1, 0)]
    next_s = ss_ref[jnp.minimum(g + 1, n_steps - 1)]
    first = jnp.logical_or(g == 0, prev_s != s)
    closes = jnp.logical_or(g == n_steps - 1, next_s != s)
    nh = kv_heads * rep

    @pl.when(jnp.logical_and(live, first))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _compute():
        p = sp_ref[g]
        t0 = t0_ref[s_idx]
        q = q_ref[0].astype(jnp.float32)            # [chunk, nh, hd]
        k = k_ref[0].astype(jnp.float32)            # [pg, kvh, hd]
        v = v_ref[0].astype(jnp.float32)
        m_prev = m_scr[:]                           # [chunk*nh, 128]
        l_prev = l_scr[:]

        # per-kv-head-group contractions keep the MXU ops unbatched
        logits = []
        for grp in range(kv_heads):
            qg = q[:, grp * rep:(grp + 1) * rep].reshape(chunk * rep, -1)
            kg = k[:, grp]                          # [pg, hd]
            logits.append(jax.lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
                .reshape(chunk, rep, page_size))
        s_log = jnp.concatenate(logits, axis=1)     # [chunk, nh, pg]
        s_log = s_log.reshape(chunk * nh, page_size) * sm_scale

        # causal ragged masking: key position p*pg + j is visible to
        # row c iff it is <= t0 + c (decode rows: c = 0, t0 = t)
        col = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (chunk * nh, page_size), 1)
        row = jax.lax.broadcasted_iota(
            jnp.int32, (chunk * nh, page_size), 0) // nh
        valid = col <= t0 + row
        s_log = jnp.where(valid, s_log, NEG_INF)

        m_cur = jnp.max(s_log, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev[:, :1], m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new)
        pexp = jnp.exp(s_log - m_new)
        pexp = jnp.where(valid, pexp, 0.0)
        l_scr[:] = jnp.broadcast_to(
            corr * l_prev[:, :1] + jnp.sum(pexp, -1, keepdims=True),
            l_scr.shape)
        pe = pexp.reshape(chunk, nh, page_size)
        pv = []
        for grp in range(kv_heads):
            pv.append(jax.lax.dot_general(
                pe[:, grp * rep:(grp + 1) * rep].reshape(chunk * rep, -1),
                v[:, grp], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                .reshape(chunk, rep, -1))
        pv = jnp.concatenate(pv, axis=1).reshape(chunk * nh, -1)
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(jnp.logical_and(live, closes))
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)              # safety guard
        o_ref[0] = (acc_scr[:] / l).reshape(
            chunk, kv_heads * rep, -1).astype(o_ref.dtype)


def _fused_tick_pallas(q, k_pages, v_pages, block_tables, t0, sched_slot,
                       sched_page, sm_scale, interpret=False):
    """q [S, C, nh, hd]; pages [P, pg, kvh, hd]; block_tables [S, W]
    int32 sliced to the live width (unused tail entries must hold any
    VALID page id, e.g. 0); t0 [S] int32; sched_* [G] int32 (pad
    entries carry slot == S). Returns [S, C, nh, hd]; rows of slots
    absent from the schedule are left unwritten (the caller zeroes
    idle slots)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, C, nh, hd = q.shape
    P, pg, kvh, _ = k_pages.shape
    W = block_tables.shape[1]
    G = sched_slot.shape[0]
    rep = nh // kvh
    if nh % kvh:
        raise ValueError(f"query heads ({nh}) must be a multiple of kv "
                         f"heads ({kvh})")

    flat_bt = block_tables.reshape(-1).astype(jnp.int32)
    kernel = functools.partial(
        _fused_tick_kernel, page_size=pg, n_slots=S, table_width=W,
        chunk=C, kv_heads=kvh, rep=rep, sm_scale=sm_scale, n_steps=G)

    def _slot(g, bt, t0_, ss, sp):
        return jnp.minimum(ss[g], S - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, C, nh, hd),
                         lambda g, bt, t0_, ss, sp:
                         (_slot(g, bt, t0_, ss, sp), 0, 0, 0)),
            pl.BlockSpec((1, pg, kvh, hd),
                         lambda g, bt, t0_, ss, sp:
                         (bt[_slot(g, bt, t0_, ss, sp) * W + sp[g]],
                          0, 0, 0)),
            pl.BlockSpec((1, pg, kvh, hd),
                         lambda g, bt, t0_, ss, sp:
                         (bt[_slot(g, bt, t0_, ss, sp) * W + sp[g]],
                          0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, nh, hd),
                               lambda g, bt, t0_, ss, sp:
                               (_slot(g, bt, t0_, ss, sp), 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * nh, 128), jnp.float32),
            pltpu.VMEM((C * nh, 128), jnp.float32),
            pltpu.VMEM((C * nh, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="fused_tick",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, C, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(flat_bt, t0.astype(jnp.int32), sched_slot.astype(jnp.int32),
      sched_page.astype(jnp.int32), q, k_pages, v_pages)


# ------------------------------------------------------ XLA reference path


def _ref_fused_tick(q, k_pages, v_pages, block_tables, t0, dec,
                    sm_scale):
    """Gather-through-the-live-slice reference. Prefill rows mirror
    ``_ref_ragged_prefill`` (s=C causal einsum), decode rows mirror
    ``_ref_paged_attention`` (s=1 einsum at lengths ``t0 + 1``) — the
    split keeps fused tokens BIT-IDENTICAL to both unfused programs on
    every platform (XLA CPU's single-row matmul differs ~1 ulp from
    the multi-row path, the PR-6 measurement). The gather spans only
    ``block_tables``' width — the caller slices it to the live page
    frontier, so compiled bytes are flat in the CONFIGURED table
    width (the skipped-page-DMA story, priced by the cost catalog)."""
    S, C, nh, hd = q.shape
    P, pg, kvh, _ = k_pages.shape
    W = block_tables.shape[1]
    T = W * pg
    k = k_pages[block_tables].reshape(S, T, kvh, hd)
    v = v_pages[block_tables].reshape(S, T, kvh, hd)
    rep = nh // kvh
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    pos = jnp.arange(T)
    # prefill-shaped causal attention over all C rows
    logits = jnp.einsum("bsnd,btnd->bnst", q, k) * sm_scale
    row = t0[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    ok = pos[None, None] <= row[:, :, None]                # [S, C, T]
    p_pre = jax.nn.softmax(
        jnp.where(ok[:, None], logits.astype(jnp.float32), -1e30),
        axis=-1).astype(q.dtype)
    pre = jnp.einsum("bnst,btnd->bsnd", p_pre, v)
    # decode-shaped s=1 attention on row 0 at lengths t0 + 1
    qd = q[:, :1]
    logits_d = jnp.einsum("bsnd,btnd->bnst", qd, k) * sm_scale
    ok_d = pos[None, None] < (t0 + 1)[:, None, None]       # [S, 1, T]
    p_dec = jax.nn.softmax(
        jnp.where(ok_d[:, None], logits_d.astype(jnp.float32), -1e30),
        axis=-1).astype(q.dtype)
    dec_row = jnp.einsum("bnst,btnd->bsnd", p_dec, v)      # [S, 1, ...]
    dec_full = jnp.concatenate(
        [dec_row, jnp.zeros_like(q[:, 1:])], axis=1)
    return jnp.where((dec > 0)[:, None, None, None], dec_full, pre)


# --------------------------------------------------------------- public


def fused_tick_attention(q, k_pages, v_pages, block_tables, t0, last,
                         dec, sched_slot, sched_page, sm_scale=None,
                         interpret=False):
    """Fused mixed prefill/decode tick attention over paged KV.

    q            [slots, chunk, num_heads, head_dim]  one packed row
                 group per slot: a prompt chunk (right-padded), a
                 single decode row in row 0, or garbage for idle slots
    k_pages      [num_pages, page_size, kv_heads, head_dim]  global pool
    v_pages      same shape as ``k_pages``
    block_tables [slots, live_width] int32  the LIVE slice of the block
                 tables (width >= every slot's live page count; tail
                 entries hold a valid id, the manager fills 0)
    t0           [slots] int32  absolute position of each slot's first
                 row (decode: the write position ``t``)
    last         [slots] int32  last position each slot's rows write
                 (``t0 + take - 1``; decode: ``t0``); ``-1`` marks an
                 idle slot — skipped by the kernel, zeroed on output
    dec          [slots] int32  1 for decode slots (fallback routes
                 them through the s=1 einsum for bit-parity with the
                 unfused decode program; the kernel is phase-agnostic)
    sched_slot / sched_page
                 [entries] int32 DMA schedule from ``build_schedule``:
                 slot-major live pages, ladder-padded with
                 ``slot == slots`` sentinels

    Row c of slot s attends to key positions <= t0[s] + c. Returns
    [slots, chunk, num_heads, head_dim]; idle slots' rows are zeros,
    live slots' rows past their take are garbage the caller discards.
    Runs the Pallas kernel on TPU (or under ``interpret=True``
    anywhere); elsewhere the gather-based XLA composition, bit-exact
    with the unfused ragged-prefill and s=1 decode programs.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if not interpret:
        refuse_on_tpu()
    if available() or interpret:
        # tile wide chunks down to the ragged kernel's VMEM-bounded
        # row count; each tile is a shifted-offset launch against the
        # SAME schedule (live rows of tile r0 still attend <= last,
        # all covered pages scheduled) — still one host dispatch, the
        # tiles live inside one jitted program
        C = q.shape[1]
        if C <= _QUERY_TILE:
            out = _fused_tick_pallas(q, k_pages, v_pages, block_tables,
                                     t0, sched_slot, sched_page,
                                     sm_scale, interpret=interpret)
        else:
            outs = []
            for r0 in range(0, C, _QUERY_TILE):
                qt = q[:, r0:r0 + _QUERY_TILE]
                outs.append(_fused_tick_pallas(
                    qt, k_pages, v_pages, block_tables, t0 + r0,
                    sched_slot, sched_page, sm_scale,
                    interpret=interpret))
            out = jnp.concatenate(outs, axis=1)
    else:
        out = _ref_fused_tick(q, k_pages, v_pages, block_tables, t0,
                              dec, sm_scale)
    # platform-consistent idle semantics: slots with no work this
    # launch (absent from the schedule / garbage on the fallback)
    # read as zeros everywhere
    return jnp.where((last < 0)[:, None, None, None],
                     jnp.zeros_like(out), out)
