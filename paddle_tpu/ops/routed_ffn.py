"""Routed expert FFN for the serving path: any ``top_k``, no capacity,
no dropped token, and only the experts a row chose are computed.

The (row, expert) pairs are sorted by expert and laid out so that every
expert's group starts on a tile boundary; a loop with a DYNAMIC trip
count then runs one SwiGLU tile an iteration against that expert's
weights, sliced out of the stacked ``[layers, experts, ...]`` arrays by
``(layer, expert)``. What a launch reads of the expert weights is
therefore what its rows touched: a decode tick of 8 rows x top-8 reads
at most 64 experts a layer, a prefill launch of thousands of rows reads
each expert once a tile. Plain XLA (sort, gather, while, dot): no
kernel, nothing to fall back from.
"""
import jax
import jax.numpy as jnp

__all__ = ["route_topk", "routed_ffn"]


def route_topk(h, router_w, top_k, normalize=True, score="softmax",
               bias=None, scale=1.0, eps=1e-6):
    """``h`` [N, H] -> (experts [N, k] int32, gates [N, k] float32).
    Router logits accumulate in float32 and the score is float32; the
    top-k is EXACT (``jax.lax.top_k``: ties to the lower expert id).

    ``score="softmax"`` (Mixtral, Qwen-MoE): the gates are the kept
    probabilities, divided by their sum under ``normalize``
    (``norm_topk_prob``). ``score="sigmoid"`` (the DeepSeek-V3 form the
    ``lfm2_moe`` decoder uses): each expert scores ``sigmoid(logit)`` on
    its own; ``bias`` [E] (the load-balancing ``expert_bias``) is added
    to the scores that CHOOSE the k experts and to nothing else, so the
    gates are the chosen experts' unbiased scores, divided by ``sum +
    eps`` under ``normalize`` (``lfm2_moe``'s 1e-6; the ``nemotron_h``
    decoder's is 1e-20), then times ``scale``
    (``routed_scaling_factor``)."""
    logits = jnp.dot(h, router_w, preferred_element_type=jnp.float32)
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, top_k)
        if normalize:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), gate
    if score != "sigmoid":
        raise ValueError(f"unknown router score {score!r}")
    scores = jax.nn.sigmoid(logits)
    choose = scores if bias is None else scores + bias.astype(jnp.float32)
    idx = jax.lax.top_k(choose, top_k)[1]
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), gate * scale


def _tile_rows(pairs, experts):
    """Rows a tile: the power of two nearest the mean group size, within
    [8, 256]. Small launches (decode) keep the padding rows few, wide
    ones (prefill) amortise an expert's 9 MB of weights over 256 rows."""
    mean = max(1, pairs // max(1, experts))
    return int(min(256, max(8, 1 << (mean - 1).bit_length())))


def _expert(w, layer, e):
    """Expert ``e`` of layer ``layer`` out of a stacked weight
    ``[L, E, a, b]`` (or ``[E, a, b]`` with ``layer`` None); an
    ``(int8, scale)`` pair yields the pair of its slices."""
    if isinstance(w, tuple):
        return tuple(_expert(a, layer, e) for a in w)
    if layer is None:
        return jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)
    return jax.lax.dynamic_slice(
        w, (layer, e, 0, 0), (1, 1) + w.shape[2:])[0, 0]


def _mm(x, w):
    if isinstance(w, tuple):
        return (x @ w[0].astype(x.dtype)) * w[1].astype(x.dtype)
    return x @ w


def _layout(flat, experts, t, n_tiles):
    """Tile-aligned layout of the (row, expert) pairs ``flat`` [m]
    (expert ids; ``experts`` marks a dead pair): every expert's group
    starts on a multiple of ``t``. Returns ``(order, dst, tile_expert,
    used)``: the pair ids sorted by expert, where sorted pair j sits in
    the layout, the expert of every tile, and the number of tiles that
    hold a live pair. The dead pairs sort last and make no group: they
    sit one after another behind the last group, on rows no tile that
    runs reaches (the groups' padding is under ``experts * t`` rows and
    the layout has ``experts * t`` to spare), and read back the zero
    the output was made of."""
    m = flat.shape[0]
    order = jnp.argsort(flat, stable=True)   # pair ids, by expert
    e_sorted = flat[order]
    counts = jnp.zeros((experts + 1,), jnp.int32).at[flat].add(1)
    counts = counts.at[experts].set(0)       # the dead: counted nowhere
    padded = -(-counts // t) * t
    p_end = jnp.cumsum(padded)
    p_start = p_end - padded
    start = jnp.cumsum(counts) - counts
    dst = p_start[e_sorted] + (jnp.arange(m, dtype=jnp.int32)
                               - start[e_sorted])
    tile_expert = jnp.minimum(
        jnp.searchsorted(p_end, jnp.arange(n_tiles, dtype=jnp.int32) * t,
                         side="right"), experts - 1).astype(jnp.int32)
    return order, dst, tile_expert, p_end[-1] // t


def held_tile(pairs, held, experts):
    """Rows a tile for a launch of ``pairs`` (row, expert) choices over
    ``experts`` of which this layer holds ``held``: the tile of the
    pairs it can expect to fall on a held expert."""
    return _tile_rows(pairs * held // experts, held)


def routed_ffn(h, idx, gate, wg, wu, wd, layer=None, tile=None, live=None,
               held=None):
    """``sum_j gate[n, j] * SwiGLU_{idx[n, j]}(h[n])`` for rows ``h``
    [N, H]. ``wg``/``wu`` are ``[L, E, H, F]`` and ``wd`` ``[L, E, F,
    H]`` indexed at ``layer`` (or ``[E, ...]`` with ``layer`` None);
    int8 pairs work too. Rows are independent: a NaN row stays in its
    own output row. ``wg`` None: the experts are NOT gated, ``W_d
    relu(W_u x)^2`` (the ``nemotron_h`` decoder's ``relu2``), two
    matrices an expert.

    ``held`` (``(first, count)``): this layer holds a SHARE of the
    experts the router chose among, ids ``[first, first + count)``, and
    the stacks have ``count`` experts. A pair whose expert is not held
    is a dead pair, by the mechanism a dead row has below: it joins no
    group, reads no weight and adds zero, so the result is the held
    experts' part of the sum and the shares of a layer add up to it.

    ``live`` ([N] bool, default every row): a dead row (an idle slot's
    garbage) joins no expert's group. Its pairs sort behind every live
    pair and add to no count, so the loop's trip count covers the live
    rows' tiles only and no expert is read for a dead row's sake; its
    output row is zero whatever it held. A live row meets the same
    weights in the same arithmetic as without the mask. Shapes stay
    static: the tile and the layout's length follow ``N * k``."""
    n, hidden = h.shape
    k = idx.shape[1]
    main = wu[0] if isinstance(wu, tuple) else wu
    experts = main.shape[-3]
    m = n * k
    t = int(tile or _tile_rows(m, experts))
    n_tiles = -(-m // t) + experts           # every group padded to t
    flat = idx.reshape(m)
    # dead pairs: expert id ``experts``, which sorts last
    if held is not None:
        flat = flat - int(held[0])
        flat = jnp.where((flat >= 0) & (flat < experts), flat, experts)
        gate = jnp.where(flat.reshape(n, k) < experts, gate, 0.0)
    if live is not None:
        flat = jnp.where(jnp.repeat(live, k), flat, experts)
        gate = jnp.where(live[:, None], gate, 0.0)
    order, dst, tile_expert, used = _layout(flat, experts, t, n_tiles)
    # the layout's rows, gathered: slot -> source row (n = a zero row)
    src = jnp.full((n_tiles * t,), n, jnp.int32).at[dst].set(
        (order // k).astype(jnp.int32))
    rows = jnp.concatenate([h, jnp.zeros((1, hidden), h.dtype)])[src]

    def body(i, out):
        e = tile_expert[i]
        x = jax.lax.dynamic_slice(rows, (i * t, 0), (t, hidden))
        if wg is None:
            act = jnp.square(jax.nn.relu(_mm(x, _expert(wu, layer, e))))
        else:
            act = (jax.nn.silu(_mm(x, _expert(wg, layer, e)))
                   * _mm(x, _expert(wu, layer, e)))
        y = _mm(act, _expert(wd, layer, e))
        return jax.lax.dynamic_update_slice(out, y.astype(out.dtype),
                                            (i * t, 0))

    out = jax.lax.fori_loop(0, used, body, jnp.zeros_like(rows))
    # back to (row, choice) order, then the gated sum in float32
    where = jnp.zeros((m,), jnp.int32).at[order].set(dst)
    y = out[where].reshape(n, k, hidden)
    return jnp.sum(y.astype(jnp.float32) * gate[..., None], axis=1
                   ).astype(h.dtype)
