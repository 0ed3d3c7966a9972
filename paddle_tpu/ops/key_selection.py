"""Learned key selection (a lightning indexer in DeepSeek-V3.2's form)
and attention over the selected keys, as an XLA composition.

For a query row at position ``p`` the indexer scores every cached
position ``s <= p``::

    I(p, s) = sum_j w[p, j] * relu(q_idx[p, j] . k_idx[s])

and attention runs over the ``topk`` positions of largest score (all of
them while ``p + 1 <= topk``). The selection is EXACT and never sorts:
the k-th largest score of a row is found by a bit-wise search over the
float's ordered integer image (32 compare-and-count passes over the
row), equal scores go to the LOWER position (15 more passes over the
positions), positions past the row's own rank last. What comes out is a
mask over the context, applied to dense attention scores; the rows a
slot's block table spans are gathered once a slot and layer. A slot
with nothing to do is skipped at run time by a ``cond`` on its offset:
the server parks EVERY slot that holds no request for this launch past
the table (never used, finished, cancelled, preempted, mid-prefill in a
decode tick, decoding in a prefill launch), so the gathers and the
search are paid for the slots that have rows.

Nothing here materialises ``[rows, indexer heads, context]``: rows go
in tiles of ``ROW_TILE`` and the sum over indexer heads is a loop that
carries one ``[tile, context]`` accumulator.
"""
import jax
import jax.numpy as jnp

__all__ = ["indexer_scores", "topk_mask", "select_and_attend",
           "sparse_paged_attention", "sparse_dense_attention", "ROW_TILE"]

ROW_TILE = 128
_NEG = -1e30
# indexer scores of all heads at once while [rows, heads, context] stays
# under 64 MB of float32 (8 decode rows x 16 heads x 16,384 keys is 8 MB)
_ALL_HEADS_AT_ONCE = 1 << 24


def indexer_scores(qi, wi, ki):
    """``qi`` [R, J, D], ``wi`` [R, J] float32, ``ki`` [T, D] ->
    scores [R, T] float32. A few rows (a decode step) take every head
    in one matrix product; a row tile of a prefill chunk goes one head
    at a time, so that ``[J, R, T]`` never exists."""
    r, j, _ = qi.shape
    if r * j * ki.shape[0] <= _ALL_HEADS_AT_ONCE:
        s = jnp.einsum("rjd,td->rjt", qi, ki,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("rjt,rj->rt", jnp.maximum(s, 0.0), wi)

    def head(acc, xs):
        q, w = xs                                        # [R, D], [R]
        s = jnp.dot(q, ki.T, preferred_element_type=jnp.float32)
        return acc + w[:, None] * jnp.maximum(s, 0.0), None

    acc = jnp.zeros((r, ki.shape[0]), jnp.float32)
    acc, _ = jax.lax.scan(head, acc, (jnp.swapaxes(qi, 0, 1),
                                      jnp.swapaxes(wi, 0, 1)))
    return acc


def _ordered(x):
    """float32 -> uint32 with the same order (-inf lowest, +inf
    highest, -0.0 with 0.0; a NaN lands at one end and is some idle
    row's business)."""
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x),
                                        jnp.int32)
    flipped = jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31))
    return jax.lax.bitcast_convert_type(flipped, jnp.uint32)


def topk_mask(scores, k, valid):
    """Boolean mask [R, T] of the ``min(k, valid.sum(-1))`` largest
    ``scores`` of each row among ``valid`` positions; ties to the lower
    position. Exact: equal to ``jax.lax.top_k`` over the valid scores."""
    r, t = scores.shape
    key = jnp.where(valid, jnp.maximum(_ordered(scores), jnp.uint32(1)),
                    jnp.uint32(0))

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        enough = jnp.sum(key >= cand[:, None], axis=-1) >= k
        return jnp.where(enough, cand, prefix)

    # the k-th largest key of each row (0 where the row has fewer)
    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros((r,), jnp.uint32))
    above = key > thr[:, None]
    equal = key == thr[:, None]
    room = k - jnp.sum(above, axis=-1)                  # ties to keep
    pos = jnp.arange(t, dtype=jnp.int32)
    nbits = max(1, int(t).bit_length())

    def pbit(i, bound):
        cand = bound | (jnp.int32(1) << (nbits - 1 - i))
        fits = jnp.sum(equal & (pos[None] < cand[:, None]), axis=-1) <= room
        return jnp.where(fits, cand, bound)

    # the largest bound with at most `room` equal keys below it
    bound = jax.lax.fori_loop(0, nbits, pbit, jnp.zeros((r,), jnp.int32))
    return valid & (above | (equal & (pos[None] < bound[:, None])))


def _attend(q, k, v, mask, scale):
    """q [R, G, M, D] (G kv heads x M query heads each), k/v [T, G * D]
    as the pool stores them, mask [R, T] -> [R, G, M, D]; float32
    softmax, masked scores at -1e30 (a row with no key at all gives a
    finite mean nobody reads). A kv head is a lane slice of the stored
    rows, so K and V are read where the gather left them."""
    d = q.shape[-1]
    out = []
    for g in range(q.shape[1]):
        kg, vg = k[:, g * d:(g + 1) * d], v[:, g * d:(g + 1) * d]
        s = jnp.einsum("rmd,td->mrt", q[:, g], kg,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        out.append(jnp.einsum("mrt,td->rmd", p, vg))
    return jnp.stack(out, axis=1)


def select_and_attend(q, qi, wi, k, v, ki, t0, topk, scale):
    """One slot: query rows ``q`` [s, nh, hd] at positions ``t0 + row``
    over its cached ``k``/``v`` [T, kvh * hd] (a token's kv heads merged,
    as the pool stores them) and indexer keys ``ki`` [T, D] (all already
    written through the rows). Returns ``(out [s, nh, hd], kept [s])``:
    ``kept`` is the number of keys each row's mask let through, counted
    where the mask is made."""
    s, nh, hd = q.shape
    t, kvh = k.shape[0], k.shape[1] // hd
    tile = min(ROW_TILE, s)
    pad = (-s) % tile                    # rows up to whole tiles
    if pad:
        q, qi, wi = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                     for a in (q, qi, wi))
    n = (s + pad) // tile
    qg = q.reshape(n, tile, kvh, nh // kvh, hd)
    qit = qi.reshape((n, tile) + qi.shape[1:])
    wit = wi.astype(jnp.float32).reshape(n, tile, wi.shape[-1])
    first = t0 + jnp.arange(0, s + pad, tile, dtype=jnp.int32)
    pos = jnp.arange(t, dtype=jnp.int32)

    def rows(xs):
        qq, qqi, wwi, p0 = xs
        valid = pos[None] <= (p0 + jnp.arange(tile, dtype=jnp.int32)
                              )[:, None]
        with jax.named_scope("indexer"):
            scores = indexer_scores(qqi, wwi, ki)
        with jax.named_scope("select"):
            keep = topk_mask(scores, topk, valid)
        with jax.named_scope("sparse_attend"):
            return (_attend(qq, k, v, keep, scale),
                    jnp.sum(keep, axis=-1, dtype=jnp.int32))

    out, kept = jax.lax.map(rows, (qg, qit, wit, first))
    return out.reshape(s + pad, nh, hd)[:s], kept.reshape(s + pad)[:s]


def sparse_paged_attention(q, qi, wi, pool, layer, bt, t, topk, scale):
    """Selection and attention through the block table: ``q`` [B, s,
    nh, hd], ``qi`` [B, s, J, D], ``wi`` [B, s, J], ``pool`` the page
    pools ``{"k", "v", "ki"}`` [L, P, pg, lanes] read at ``layer``,
    ``bt`` [B, pages], ``t`` [B] the rows' first positions. A slot
    whose ``t`` lies past its table is idle (the scheduler's sentinel,
    which every slot without a request for this launch carries): zeros
    and ``kept`` 0, and neither its pages gathered nor its keys
    searched. Returns ``(out [B, s, nh, hd], kept [B, s])``."""
    b, s, nh, hd = q.shape
    pg = pool["k"].shape[2]
    span = bt.shape[1] * pg

    def slot(xs):
        qq, qqi, wwi, pages, t0 = xs

        def live(_):
            k = pool["k"][layer, pages].reshape(span, -1)
            v = pool["v"][layer, pages].reshape(span, -1)
            ki = pool["ki"][layer, pages].reshape(span, -1)
            return select_and_attend(qq, qqi, wwi, k, v, ki, t0, topk,
                                     scale)

        return jax.lax.cond(
            t0 < span, live,
            lambda _: (jnp.zeros_like(qq), jnp.zeros((s,), jnp.int32)),
            None)

    return jax.lax.map(slot, (q, qi, wi, bt, t))


def sparse_dense_attention(q, qi, wi, k, v, ki, t, topk, scale):
    """The same over dense per-row caches ``k``/``v`` [B, T, kvh, hd]
    and ``ki`` [B, T, 1, D]; ``t`` scalar or [B]. Returns ``(out,
    kept)`` as ``sparse_paged_attention`` does."""
    b = q.shape[0]
    if jnp.ndim(t) == 0:
        t = jnp.full((b,), t, jnp.int32)

    def row(xs):
        qq, qqi, wwi, kk, vv, kki, t0 = xs
        rows = kk.shape[0]
        return select_and_attend(qq, qqi, wwi, kk.reshape(rows, -1),
                                 vv.reshape(rows, -1), kki[:, 0], t0,
                                 topk, scale)

    return jax.lax.map(row, (q, qi, wi, k, v, ki, t))
