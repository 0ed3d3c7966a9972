"""The prefill kernel's grid follows the live query tiles (ISSUE 32).

``ragged_prefill_attention`` takes one grid step a page of a LIVE (row,
query tile) pair: ``prefill_grid`` counts them, ``prefill_schedule``
(both beside the decode kernel's in ``paged_attention.py``) lists them two-level (the live pairs with their running page sums, and a
coarse index from step to pair), and the kernel finds each step's pair,
page and block-table entry through the scalar prefetch. What these tests
hold, with the kernel in the Pallas interpreter:

- the kernel against the gather reference on every live row (idle rows,
  carried chunks, ragged takes, a row at the table's last page, MHA and
  GQA), and rows of tiles nobody visits read 0;
- a live row's output EQUAL, bit for bit, to the parent's: a loop over
  the tiles, each one launch over the static grid ``(rows,
  pages_per_slot)`` — kept here as this file's own helper;
- the schedule: as many steps as ``prefill_grid`` counts in NumPy, every
  pair's steps one consecutive run in page order, one step where nothing
  is live, and the same steps whatever the coarse index's block.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import ragged_prefill as rp
from paddle_tpu.ops.pallas.paged_attention import NEG_INF

TILE = rp.QUERY_TILE


def _rand(*shape, seed=0, scale=0.5):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale)


# ------------------------------------------------- the parent's tiled loop


def _parent_kernel(bt_ref, t0_ref, last_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, page_size, pages_per_slot,
                   chunk, kv_heads, rep, sm_scale):
    """PR 31's kernel: grid (slots, pages_per_slot), every step taken,
    a dead one (``p * page_size > last``) computing nothing."""
    from jax.experimental import pallas as pl
    s, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    t0, last, nh = t0_ref[s], last_ref[s], kv_heads * rep

    @pl.when(p * page_size <= last)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        hd = q.shape[-1]
        m_prev, l_prev = m_scr[:], l_scr[:]
        logits = []
        for g in range(kv_heads):
            qg = q[:, g * rep:(g + 1) * rep].reshape(chunk * rep, -1)
            logits.append(jax.lax.dot_general(
                qg, k[:, g * hd:(g + 1) * hd], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
                .reshape(chunk, rep, page_size))
        s_log = jnp.concatenate(logits, axis=1)
        s_log = s_log.reshape(chunk * nh, page_size) * sm_scale
        col = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (chunk * nh, page_size), 1)
        row = jax.lax.broadcasted_iota(
            jnp.int32, (chunk * nh, page_size), 0) // nh
        valid = col <= t0 + row
        s_log = jnp.where(valid, s_log, NEG_INF)
        m_cur = jnp.max(s_log, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev[:, :1], m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new)
        pexp = jnp.where(valid, jnp.exp(s_log - m_new), 0.0)
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
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l).reshape(chunk, nh, -1).astype(o_ref.dtype)


def _parent_launch(q, k_pages, v_pages, block_tables, t0, last, sm_scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, C, nh, hd = q.shape
    P, pg, kvh, _ = k_pages.shape
    maxp = block_tables.shape[1]
    k_pages = k_pages.reshape(P, pg, kvh * hd)
    v_pages = v_pages.reshape(P, pg, kvh * hd)
    rows = lambda s, p, bt, t0_, ls: (s, 0, 0, 0)
    page = lambda s, p, bt, t0_, ls: (bt[s * maxp + p], 0, 0)
    return pl.pallas_call(
        functools.partial(_parent_kernel, page_size=pg, pages_per_slot=maxp,
                          chunk=C, kv_heads=kvh, rep=nh // kvh,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S, maxp),
            in_specs=[pl.BlockSpec((1, C, nh, hd), rows),
                      pl.BlockSpec((1, pg, kvh * hd), page),
                      pl.BlockSpec((1, pg, kvh * hd), page)],
            out_specs=pl.BlockSpec((1, C, nh, hd), rows),
            scratch_shapes=[pltpu.VMEM((C * nh, 128), jnp.float32),
                            pltpu.VMEM((C * nh, 128), jnp.float32),
                            pltpu.VMEM((C * nh, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype), interpret=True,
    )(block_tables.reshape(-1), t0, last, q, k_pages, v_pages)


def _parent_tiled(q, k_pages, v_pages, block_tables, t0, last, sm_scale):
    """The parent's public entry: a ``fori_loop`` of C / 8 launches,
    tile i a ragged launch at the offset ``t0 + 8 i`` (C a multiple of
    the tile, as on the server's ladder)."""
    C = q.shape[1]

    def one_tile(i, out):
        r0 = i * TILE
        qt = jax.lax.dynamic_slice_in_dim(q, r0, TILE, axis=1)
        lastt = jnp.minimum(last, t0 + r0 + TILE - 1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _parent_launch(qt, k_pages, v_pages, block_tables,
                                t0 + r0, lastt, sm_scale), r0, axis=1)

    return jax.lax.fori_loop(0, C // TILE, one_tile, jnp.zeros_like(q))


# ------------------------------------------------------------ the launches

# rows of one launch over a table of 12 pages of 4 (a span of 48):
# (t0, take) a row, chunk width 32 = four query tiles
PG, MAXP, POOL, C = 4, 12, 40, 32
SPAN = PG * MAXP
LAUNCHES = {
    # cold full chunk, a carried ragged chunk, an idle row (sentinel),
    # a row whose chunk ends at the table's last page, a row of take 0
    "mixed": ([0, 5, SPAN, SPAN - C, 3], [C, 13, 7, C, 0]),
    # takes that are no multiple of the tile, mid-page offsets
    "ragged": ([1, 7, 10, 15], [1, 9, 17, 31]),
    # a carried chunk whose PADDING rows run past the table's span
    "tail": ([SPAN - 6, SPAN - 20], [6, 3]),
    # one prompt among idle rows: what a chat launch looks like
    "lonely": ([SPAN, SPAN, 0, SPAN, SPAN, SPAN], [0, 0, 21, 0, 0, 0]),
    "idle": ([SPAN, SPAN, SPAN], [0, 0, 0]),
}


def _launch(name, kvh, nh, hd=16):
    t0, take = (np.array(a, np.int32) for a in LAUNCHES[name])
    S = len(t0)
    q = _rand(S, C, nh, hd, seed=1)
    kp, vp = _rand(POOL, PG, kvh, hd, seed=2), _rand(POOL, PG, kvh, hd,
                                                    seed=3)
    rng = np.random.RandomState(4)
    bt = jnp.asarray(np.stack([
        rng.choice(np.arange(1, POOL), MAXP, replace=False)
        for _ in range(S)]).astype(np.int32))
    return q, kp, vp, bt, t0, take


def _visited_rows(t0, take):
    """Rows (of C) some grid step visits, a row of the launch: those of
    its live tiles, the padding rows inside the last one included."""
    up = -(-take // TILE) * TILE
    return np.where(t0 < SPAN, np.minimum(up, C), 0)


HEADS = [(2, 2), (2, 8)]                   # MHA; GQA with rep 4


@pytest.mark.parametrize("kvh,nh", HEADS, ids=["mha", "gqa4"])
@pytest.mark.parametrize("name", sorted(LAUNCHES))
def test_kernel_matches_gather_reference(name, kvh, nh):
    q, kp, vp, bt, t0, take = _launch(name, kvh, nh)
    out = np.asarray(rp.ragged_prefill_attention(
        q, kp, vp, bt, jnp.asarray(t0), jnp.asarray(take), 0.2,
        interpret=True))
    ref = np.asarray(rp._ref_ragged_prefill(q, kp, vp, bt, jnp.asarray(t0),
                                            0.2))
    for s, rows in enumerate(_visited_rows(t0, take)):
        real = min(int(take[s]), int(rows))
        np.testing.assert_allclose(out[s, :real], ref[s, :real],
                                   rtol=2e-5, atol=2e-5)
        # rows of tiles nobody visits read 0
        assert (out[s, rows:] == 0).all()
    assert np.isfinite(out).all()


@pytest.mark.parametrize("kvh,nh", HEADS, ids=["mha", "gqa4"])
@pytest.mark.parametrize("name", sorted(LAUNCHES))
def test_live_rows_equal_the_parents_tiled_loop(name, kvh, nh):
    """Same tile, same masking, same page order a row: every row of a
    visited tile is the parent's bit for bit (the parent, told nothing
    of ``take``, ran every tile of a live row to the chunk's end)."""
    q, kp, vp, bt, t0, take = _launch(name, kvh, nh)
    got = np.asarray(rp.ragged_prefill_attention(
        q, kp, vp, bt, jnp.asarray(t0), jnp.asarray(take), 0.2,
        interpret=True))
    last = np.where(t0 >= SPAN, -1, t0 + C - 1).astype(np.int32)
    want = np.asarray(_parent_tiled(q, kp, vp, bt, jnp.asarray(t0),
                                    jnp.asarray(last), 0.2))
    for s, rows in enumerate(_visited_rows(t0, take)):
        np.testing.assert_array_equal(got[s, :rows], want[s, :rows])


def _decode(schedule, tiles, block):
    """Walk the two-level schedule as the kernel's index maps do:
    ``[(row, tile, page)]`` a step."""
    pair, bounds, index, steps = (np.asarray(a) for a in schedule)
    out = []
    for g in range(int(steps)):
        n = index[g // block]
        for _ in range(block - 1):
            n = min(n + int(bounds[n + 1] <= g), len(pair) - 1)
        assert bounds[n] <= g
        out.append((pair[n] // tiles, pair[n] % tiles, g - bounds[n]))
    return out


def _expected_steps(pages):
    return [(r, i, p) for r in range(pages.shape[0])
            for i in range(pages.shape[1]) for p in range(pages[r, i])]


@pytest.mark.parametrize("entries", [pa._INDEX_ENTRIES, 64, 7],
                         ids=["direct", "block", "odd-block"])
@pytest.mark.parametrize("name", sorted(LAUNCHES))
def test_schedule_lists_what_the_grid_counts(name, entries, monkeypatch):
    """The device's schedule has the steps NumPy counts on the host,
    every (row, tile) pair's one consecutive run in page order,
    row-major and tile-major — whatever the coarse index's block."""
    monkeypatch.setattr(pa, "_INDEX_ENTRIES", entries)
    t0, take = (np.array(a, np.int32) for a in LAUNCHES[name])
    pages, steps = pa.prefill_grid(t0, take, C, TILE, PG, MAXP)
    assert isinstance(pages, np.ndarray) and pages.shape == (len(t0),
                                                             C // TILE)
    schedule = pa.prefill_schedule(jnp.asarray(t0), jnp.asarray(take), C,
                                   TILE, PG, MAXP)
    assert int(schedule[3]) == int(steps) == max(int(pages.sum()), 1)
    block = pa.prefill_index_block(pages.size, MAXP)
    assert (block > 1) == (entries < pages.size * MAXP)
    got = _decode(schedule, C // TILE, block)
    if pages.sum():
        assert got == _expected_steps(pages)
    else:
        assert len(got) == 1               # the lone step: no page
        assert schedule[1][got[0][0] + 1] == 0
    # static lengths: one entry a pair, that plus one, a step a block
    assert schedule[0].shape == (pages.size,)
    assert schedule[1].shape == (pages.size + 1,)
    assert schedule[2].shape == (-(-pages.size * MAXP // block),)


def test_grid_counts_pages_through_each_tiles_last_row():
    """A tile attends the pages from 0 to its LAST row's (padding rows
    inside a live tile included), clipped to the table; a tile wholly
    past ``take`` and an idle row attend nothing."""
    t0 = np.array([0, 5, SPAN, SPAN - 6], np.int32)
    take = np.array([C, 9, 7, 6], np.int32)
    pages, steps = pa.prefill_grid(t0, take, C, TILE, PG, MAXP)
    np.testing.assert_array_equal(pages, [
        [2, 4, 6, 8],          # rows 0-7 end at position 7: pages 0, 1
        [4, 6, 0, 0],          # 9 rows from 5: tiles 0-1, through 12, 20
        [0, 0, 0, 0],          # the sentinel, whatever its take
        [MAXP, 0, 0, 0]])      # padding rows past the span: the table
    assert steps == pages.sum()
    # a width that is no multiple of the tile: its last tile is short
    pages, _ = pa.prefill_grid(np.array([0], np.int32),
                               np.array([11], np.int32), 11, TILE, PG, MAXP)
    np.testing.assert_array_equal(pages, [[2, 3]])


def test_a_launch_with_nothing_live_takes_one_step():
    q, kp, vp, bt, t0, take = _launch("idle", 2, 2)
    pages, steps = pa.prefill_grid(t0, take, C, TILE, PG, MAXP)
    assert pages.sum() == 0 and steps == 1
    out = rp.ragged_prefill_attention(q, kp, vp, bt, jnp.asarray(t0),
                                      jnp.asarray(take), 0.2,
                                      interpret=True)
    assert (np.asarray(out) == 0).all()


@pytest.mark.parametrize("name", ["mixed", "ragged", "lonely"])
def test_kernel_reads_the_same_through_a_coarse_index(name, monkeypatch):
    """Past ``_INDEX_ENTRIES`` the index names every ``block``-th step's
    pair and the kernel walks on from it: the same output, bit for bit."""
    q, kp, vp, bt, t0, take = _launch(name, 2, 8)
    call = lambda: np.asarray(rp.ragged_prefill_attention(
        q, kp, vp, bt, jnp.asarray(t0), jnp.asarray(take), 0.2,
        interpret=True))
    direct = call()
    monkeypatch.setattr(pa, "_INDEX_ENTRIES", 50)
    assert pa.prefill_index_block(len(t0) * C // TILE, MAXP) > 1
    np.testing.assert_array_equal(call(), direct)


def test_one_kernel_call_with_a_dynamic_grid_bound():
    """ONE ``pallas_call`` a launch, no loop around it, its grid's
    bound a traced scalar (the schedule's step count)."""
    q, kp, vp, bt, t0, take = _launch("mixed", 2, 8)
    jaxpr = str(jax.make_jaxpr(lambda t0, take: rp.ragged_prefill_attention(
        q, kp, vp, bt, t0, take, 0.2, interpret=True))(
        jnp.asarray(t0), jnp.asarray(take)))
    assert jaxpr.count("name=ragged_prefill_attention") == 1
    assert "while" not in jaxpr and "dynamic_slice" not in jaxpr
    # a dynamic bound shows as the grid's placeholder
    assert jaxpr.count("grid=(DynamicGridDim,)") == 1


def test_a_shared_schedule_is_the_launches_own():
    """A caller with a plan (the serving launch: one for all layers)
    hands the schedule in; the kernel makes the same one without."""
    q, kp, vp, bt, t0, take = _launch("ragged", 2, 8)
    t0, take = jnp.asarray(t0), jnp.asarray(take)
    own = rp.ragged_prefill_attention(q, kp, vp, bt, t0, take, 0.2,
                                      interpret=True)
    shared = rp.ragged_prefill_attention(
        q, kp, vp, bt, t0, take, 0.2, interpret=True,
        schedule=pa.prefill_schedule(t0, take, C, TILE, PG, MAXP))
    np.testing.assert_array_equal(np.asarray(own), np.asarray(shared))
