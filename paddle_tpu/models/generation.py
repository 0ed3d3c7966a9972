"""Model-level text generation on the on-device decode loop.

The reference serves autoregressive models through per-token host loops
around fused ops (fused_multi_transformer_op.cu time_step path); the
generation filters (top-k/top-p/temperature) live in its incubate
generation utils. Here the whole pipeline — prefill, KV-cache decode,
logits filtering, sampling — compiles to two XLA programs (one prefill,
one `lax.scan` decode; inference/decode_loop.py), so host dispatch is
paid once per sequence.

Design: instead of threading mutable cache state through every
``nn.Layer.forward`` (the torch/reference pattern), each CausalLM model
decomposes into PURE step functions over its raw parameter tree — the
same approach its ``pipeline_decompose`` uses for pipeline parallelism.
``GenerationMixin.generate`` is the user API on GPTForCausalLM and
LlamaForCausalLM.
"""
import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import unwrap, wrap
from ..jit.hoist import hoisted_jit

__all__ = ["GenerationMixin"]


def _stacked(blocks, name):
    return jnp.stack([unwrap(b[name]) for b in blocks])


_QUANT_WEIGHTS = frozenset({
    "wq", "wk", "wv", "wo", "wg", "wu", "wd",            # llama/mixtral
    "attn.qkv.weight", "attn.proj.weight",               # gpt
    "mlp.fc1.weight", "mlp.fc2.weight",
})


def _quantize_tree(p):
    """Weight-only int8: every matmul weight (explicit allowlist) becomes
    an (int8, fp32 scale) pair with per-output-channel scales — decode
    streams HALF the weight bytes from HBM (the decode roofline; cf.
    bench.py decode HBM-util accounting). Norms/embeddings/router/biases
    stay full precision; the lm head does too (logit fidelity)."""
    def q(name, w):
        if name not in _QUANT_WEIGHTS:
            return w
        # reduce over the contraction dim (axis -2): per-(layer, expert,
        # out-channel) scales — NOT shared across the stacked layer dim
        amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
        s = amax.astype(jnp.float32) / 127.0 + 1e-12
        qw = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
        return (qw, s)

    return {k: q(k, v) for k, v in p.items()}


def _apply_mesh(p, mesh, shard_dims, axis="mp"):
    """Tensor-parallel weight placement for decode: ``shard_dims`` maps
    weight name -> dimension index to shard over the mesh's ``axis``
    (column-parallel out-dims, row-parallel contraction dims, or the
    expert dim). Everything else — and any dim not divisible by the axis
    size — is placed replicated, so the whole tree lives on the mesh and
    one jit compiles an SPMD decode (GSPMD inserts the collectives,
    exactly as the training-side TP layers rely on)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    size = mesh.shape[axis]
    rep = NamedSharding(mesh, P())

    def place(name, w):
        main = w[0] if isinstance(w, tuple) else w
        dim = shard_dims.get(name)
        if dim is not None and main.shape[dim] % size == 0:
            spec = P(*[axis if i == dim else None
                       for i in range(main.ndim)])
            sh = NamedSharding(mesh, spec)
        else:
            sh = rep
        if isinstance(w, tuple):          # int8 (weights, scales)
            return (jax.device_put(w[0], sh), jax.device_put(w[1], rep))
        return jax.device_put(w, sh)

    return {k: place(k, v) for k, v in p.items()}


def _stacked_weights(model, weight_dtype, mesh, build, shard_dims):
    """The model's stacked decode weight tree: built once per
    ``(weight_dtype, mesh)`` and SHARED by every decode bundle of the
    model — bundles differ in cache layout, never in weights, and the
    server always holds a dense and a paged one. Holds one tree: asking
    for another dtype or mesh replaces it (live bundles keep theirs)."""
    cache = getattr(model, "_pt_stacked_weights", None)
    if cache is None:
        cache = model._pt_stacked_weights = {}
    key = (weight_dtype, None if mesh is None else id(mesh))
    if key not in cache:
        p = build()
        if weight_dtype == "int8":
            p = _quantize_tree(p)
        if mesh is not None:
            p = _apply_mesh(p, mesh, shard_dims)
        cache.clear()
        cache[key] = p
    return cache[key]


def _mesh_caches(init_caches, mesh):
    """Replicate fresh KV caches over the mesh so every array in the
    decode jit shares one device set."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def init(batch):
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P())),
            init_caches(batch))

    return init


def paged_pool_shards(mesh, num_kv_heads, axis="mp"):
    """How many ways the paged K/V pool is sharded on ``mesh``: the
    ``axis`` size when it divides the kv-head count, else 1 (the
    replicated fallback, mirroring ``_apply_mesh``'s weight rule).
    Host-side bookkeeping (allocator, prefix cache, postmortems) uses
    this to report per-shard balance without touching device state."""
    if mesh is None:
        return 1
    size = int(dict(mesh.shape).get(axis, 1))
    return size if size > 1 and num_kv_heads % size == 0 else 1


def _mesh_paged_caches(init_caches, mesh, kv_heads, axis="mp"):
    """Mesh placement for a fresh PAGED cache tree: the global K/V page
    pools shard their merged ``kv_heads * head_dim`` axis (the minor
    one, see ``paged_pool_shape``) over the mesh's ``axis`` — contiguous
    blocks of whole kv heads, so per-device pool bytes shrink by 1/mp
    at fixed page capacity (the mesh column, ROADMAP A8) —
    while the block table stays REPLICATED: page ids are global, so the
    host-side allocator, grow/preempt/donate, and the prefix radix tree
    never learn the mesh exists. A ``kv_heads`` count (the model
    config's) the axis size doesn't divide falls back to a replicated
    pool (``paged_pool_shards`` reports 1), exactly like ``_apply_mesh``
    does for weights."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    rep = NamedSharding(mesh, P())
    sh = (NamedSharding(mesh, P(None, None, None, axis))
          if paged_pool_shards(mesh, kv_heads, axis) > 1 else rep)

    def init(batch):
        tree = init_caches(batch)
        return dict({n: jax.device_put(a, rep) for n, a in tree.items()
                     if n != "pool"},
                    pool={n: jax.device_put(a, sh)
                          for n, a in tree["pool"].items()})

    return init


def _mm(x, w):
    """x @ w where w is a raw array or an (int8, scale) pair. The int8
    path casts tile-wise inside the fused matmul (XLA folds the convert
    into the HBM read) and applies the per-channel scale on the out."""
    if isinstance(w, tuple):
        qw, s = w
        return (x @ qw.astype(x.dtype)) * s.astype(x.dtype)
    return x @ w


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            ).astype(x.dtype) * w


def _ln(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def _positions(t, b, s):
    """Absolute positions [B, s] for a step at offset ``t`` — scalar
    (all rows aligned) or [B] (per-row offsets, continuous batching)."""
    row = jnp.arange(s, dtype=jnp.int32)
    if jnp.ndim(t) == 0:
        return (t + row)[None, :].repeat(b, 0)
    return t[:, None] + row[None, :]


def _cached_attend(q, k_cache, v_cache, t, s, scale):
    """q [B,s,nh,hd] at positions [t, t+s); caches [B,T,nh,hd] already
    updated through t+s. Masks unwritten/future slots: key position p is
    visible to query row r iff p <= t+r. ``t`` scalar or [B]."""
    T = k_cache.shape[1]
    logits = jnp.einsum("bsnd,btnd->bnst", q, k_cache) * scale
    pos = jnp.arange(T)
    row = _positions(t, q.shape[0], s)                   # [B, s]
    ok = pos[None, None] <= row[:, :, None]              # [B, s, T]
    logits = jnp.where(ok[:, None], logits.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", p, v_cache)


def _write_cache(cache, kv, t):
    """cache [B,T,h,hd] <- kv [B,s,h,hd] at positions [t, t+s); ``t``
    scalar or [B] (per-row write offsets)."""
    kv = kv.astype(cache.dtype)
    if jnp.ndim(t) == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, kv, t, axis=1)
    b, s = kv.shape[0], kv.shape[1]
    rows = jnp.arange(b)[:, None].repeat(s, 1)           # [B, s]
    cols = _positions(t, b, s)
    return cache.at[rows, cols].set(kv)


def _kv_write(lc, name, kv, t):
    """Write new k/v rows into this layer's cache dict. With an int8
    cache (a ``<name>s`` scale entry present) the rows are quantized
    per (batch, position, head): amax/127 scale, int8 payload — half
    the cache bytes decode streams every step (its roofline)."""
    if name + "s" in lc:
        amax = jnp.max(jnp.abs(kv.astype(jnp.float32)), -1) + 1e-8
        sc = (amax / 127.0).astype(jnp.float32)
        q = jnp.clip(jnp.round(kv.astype(jnp.float32) / sc[..., None]),
                     -127, 127).astype(jnp.int8)
        return dict(lc, **{name: _write_cache(lc[name], q, t),
                           name + "s": _write_cache(lc[name + "s"], sc,
                                                    t)})
    return dict(lc, **{name: _write_cache(lc[name], kv, t)})


def _kv_read(lc, name, dtype):
    """Full cache view [B,T,h,hd] in compute dtype (dequantized if the
    cache is int8 — the cast+scale fuses into the attention einsum)."""
    c = lc[name]
    if name + "s" in lc:
        return c.astype(dtype) * lc[name + "s"].astype(dtype)[..., None]
    return c


def _init_kv(shape, dtype, cache_dtype, index_dim=None):
    """Dense per-layer caches ``[L, B, T, heads, head_dim]``; with
    ``index_dim`` a third leaf ``ki`` ``[L, B, T, 1, index_dim]`` for
    the key-selection indexer's keys (never quantised)."""
    lc = {}
    if index_dim:
        lc["ki"] = jnp.zeros(shape[:3] + (1, int(index_dim)), dtype)
    if cache_dtype == "int8":
        lc["k"] = jnp.zeros(shape, jnp.int8)
        lc["v"] = jnp.zeros(shape, jnp.int8)
        lc["ks"] = jnp.zeros(shape[:-1], jnp.float32)
        lc["vs"] = jnp.zeros(shape[:-1], jnp.float32)
    else:
        lc["k"] = jnp.zeros(shape, dtype)
        lc["v"] = jnp.zeros(shape, dtype)
    return lc


# ------------------------------------------------------ paged KV backend

def _check_paged_config(max_cache_len, page_size, num_pages, cache_dtype,
                        mesh):
    """Validate a paged-cache decode bundle request. ``page_size`` must
    divide ``max_cache_len`` so the block-table width times page size
    equals the dense cache length — that equality is what makes the
    paged decode path bit-identical to the dense one. A ``mesh`` is
    accepted as-is: the pool shards on the kv-head dim (or falls back
    to replicated) via ``_mesh_paged_caches`` — nothing to refuse."""
    if cache_dtype == "int8":
        raise NotImplementedError(
            "cache_dtype='int8' is not wired for the paged backend yet "
            "(ROADMAP A7: quantized paged KV pool); use "
            "cache_backend='dense' with int8 caches")
    del mesh
    if not page_size or int(page_size) < 1:
        raise ValueError("paged backend needs page_size >= 1")
    if not num_pages or int(num_pages) < 2:
        raise ValueError("paged backend needs num_pages >= 2 (page 0 is "
                         "the reserved null page)")
    if max_cache_len % int(page_size):
        raise ValueError(
            f"page_size ({page_size}) must divide max_cache_len "
            f"({max_cache_len}) for dense/paged token parity")


def paged_pool_shape(layers, num_pages, page_size, kv_heads, head_dim):
    """THE storage shape of a paged K or V pool — spelled here and
    nowhere else: ``[layers, num_pages, page_size, kv_heads *
    head_dim]``, a token's kv heads merged into one LANE-DENSE minor
    axis. A ``head_dim`` of 64 is half a TPU lane tile: stored as its
    own minor axis the compiler's default layout puts another
    dimension (the pages) on the lanes instead of padding every row to
    128, and since a Mosaic call takes its operands row-major, every
    layer's pool was relaid out before each kernel call and back after
    it. Merged, the minor axis is ``kv_heads * head_dim`` wide, the
    default layout IS row-major, and kernel and XLA read the same
    bytes. ``pool_heads`` / ``pool_lanes`` are the views to and from
    per-head rows (reshapes of the minor axis: head ``g`` is lanes
    ``[g * head_dim, (g + 1) * head_dim)``, so a mesh shard of the
    merged axis is a contiguous block of whole heads)."""
    return (int(layers), int(num_pages), int(page_size),
            int(kv_heads) * int(head_dim))


def pool_heads(a, kv_heads):
    """View pool-stored rows ``[..., kv_heads * head_dim]`` as
    ``[..., kv_heads, head_dim]`` (what payloads, dense caches and the
    attention math see)."""
    return a.reshape(a.shape[:-1] + (kv_heads, a.shape[-1] // kv_heads))


def pool_lanes(a):
    """Inverse of ``pool_heads``: rows ``[..., kv_heads, head_dim]`` as
    the pool stores them, ``[..., kv_heads * head_dim]``."""
    return a.reshape(a.shape[:-2] + (a.shape[-2] * a.shape[-1],))


def paged_kv_heads(cfg):
    """kv heads a model config's paged pool stores per token (GQA
    models cache them unrepeated; GPT has no separate count)."""
    return int(getattr(cfg, "num_kv_heads", None) or cfg.num_heads)


def _init_paged_kv(batch, layers, num_pages, page_size, pages_per_slot,
                   kvh, hd, dtype, extra=None, route_k=None, kept=False,
                   route_layers=None, state=None):
    """Paged decode cache tree: one global page pool over the model's
    ATTENTION layers (``paged_pool_shape``; ``layers`` is their count,
    which is every layer unless the model has a layer spec) plus the
    per-slot block table (a RUNTIME argument of the decode program —
    page churn never recompiles).
    The pool's leaves are ``k`` and ``v`` and whatever ``extra`` names
    (``{leaf: (heads, head_dim)}``: the key-selection indexer's keys
    ``ki`` ride here). EVERY leaf is addressed by the SAME block table
    and page ids: a page holds all of a token run's state, so
    allocation, prefix sharing, preemption, spill and migration move
    the leaves together (the server walks the pool's leaves and never
    spells their names). ``route_k`` adds ``route`` ``[route_layers,
    batch, route_k]``: the experts each slot's LAST decode row chose in
    each expert layer, which the decode tick packs into the read-back it
    already makes; ``kept`` adds ``kept`` ``[L, batch]``, the keys the
    selection kept for that row, which rides the same read-back.

    ``state`` (``_slot_state``'s argument) adds ``state``: PER-SLOT
    recurrent state beside the pool, a TREE of leaves ``[layers of a
    kind, batch, ...]``, each of its own type (a short convolution's
    last inputs, a layer a conv layer; a state-space layer's
    convolution window and its float32 recurrent state). It is
    addressed by the slot and NOT by the block table: no page holds it,
    so nothing that moves pages (prefix sharing, spill, migration)
    moves it, and the server refuses those for a model that has it."""
    def leaf(heads, dim):
        return jnp.zeros(paged_pool_shape(layers, num_pages, page_size,
                                          heads, dim), dtype)

    pool = {"k": leaf(kvh, hd), "v": leaf(kvh, hd)}
    for name, (heads, dim) in (extra or {}).items():
        pool[name] = leaf(heads, dim)
    tree = {"pool": pool,
            "bt": jnp.zeros((batch, pages_per_slot), jnp.int32)}
    if route_k:
        tree["route"] = jnp.zeros((route_layers or layers, batch,
                                   int(route_k)), jnp.int32)
    if kept:
        tree["kept"] = jnp.zeros((layers, batch), jnp.int32)
    if state is not None:
        tree["state"] = _slot_state(state, batch)
    return tree


def _slot_state(state, batch):
    """Fresh per-slot state: ``state`` is a tree (one leaf, or a dict of
    them) of ``jax.ShapeDtypeStruct((layers, ...), dtype)``, what ONE
    slot holds in the layers of a kind; the batch goes in at axis 1."""
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros((s.shape[0], batch) + s.shape[1:], s.dtype),
        state)


def _paged_decode_plan(bt, t, page_size):
    """What every layer of one paged decode step attends, made ONCE a
    step outside the layer loop: ``(lengths, schedule)``. ``lengths``
    [B] are the valid tokens t+1 (cache written through t); a slot
    carrying the scheduler's idle sentinel (``t`` past the block-table
    extent: it holds no decoding request) gets length 0, as
    ``_paged_prefill_attend`` hands it ``last = -1``. ``schedule`` is
    the decode kernel's grid made of those lengths (``decode_schedule``:
    one step a live page), which the kernel reads by scalar prefetch
    beside the block table."""
    from ..ops.pallas.paged_attention import decode_schedule
    if jnp.ndim(t) == 0:
        t = jnp.full((bt.shape[0],), t, jnp.int32)
    limit = bt.shape[1] * page_size                # tokens a table spans
    lengths = jnp.where(t < limit, t + 1, jnp.int32(0))
    return lengths, decode_schedule(lengths, page_size, bt.shape[1])


def _paged_prefill_plan(bt, t, take, width, page_size):
    """What every layer of one ragged prefill launch attends, made ONCE
    a launch outside the layer loop: ``(take, schedule)``. ``take`` [B]
    are the REAL rows of each slot's ``width``-row chunk (None: every
    row), ``t`` [B] the chunks' offsets; a row carrying the scheduler's
    idle sentinel (``t`` past the block-table extent) has no live query
    tile whatever its ``take``. ``schedule`` is the prefill kernel's
    grid made of them (``prefill_schedule``: one step a page of a live
    query tile), which the kernel reads by scalar prefetch beside the
    block table."""
    from ..ops.pallas.paged_attention import prefill_schedule
    from ..ops.pallas.ragged_prefill import QUERY_TILE
    b = bt.shape[0]
    t = jnp.broadcast_to(t, (b,)).astype(jnp.int32)
    take = (jnp.full((b,), width, jnp.int32) if take is None
            else take.astype(jnp.int32))
    return take, prefill_schedule(t, take, width, QUERY_TILE, page_size,
                                  bt.shape[1])


def _paged_attend(q, pool, layer, bt, t, scale, mesh=None, plan=None):
    """Decode-step attention through the block table: q [B, 1, nh, hd],
    ``pool`` the whole K/V pools ``{"k", "v"}`` [L, P, pg, kvh*hd] read
    at ``layer``, over ``plan`` (``_paged_decode_plan(bt, t, pg)``, which
    the layer loop's caller makes once for all its layers). A slot of
    length 0 has no step in the kernel's grid and gets zeros; the
    fallback masks its every position (a finite mean of the null page
    nobody reads). Handed ``t + 1`` an idle slot would be the kernel's
    dearest row: a sweep over its whole table of null pages.
    Pallas ragged kernel on TPU (per-kv-head-shard launches under
    ``mesh`` — XLA cannot partition a custom call, so the kernel path
    shard_maps itself), bit-exact dense-mirroring gather composition
    elsewhere (GSPMD partitions it from the pool's input sharding).
    Returns [B, 1, nh, hd]."""
    from ..ops.pallas.paged_attention import paged_attention
    if plan is None:
        plan = _paged_decode_plan(bt, t, pool["k"].shape[2])
    lengths, schedule = plan
    return paged_attention(q[:, 0], pool["k"], pool["v"], bt, lengths,
                           scale, mesh=mesh, layer=layer,
                           schedule=schedule)[:, None]


def _page_write(pool, layer, kv, bt, t):
    """Page write: pool [L, P, pg, h*hd] <- kv [B, s, h, hd] into layer
    ``layer`` at per-slot position runs [t_b, t_b + s) — a decode row
    (s = 1) or a ragged-prefill chunk. The write is a scatter of
    ``B * s`` rows into the CARRIED pool, so inside the layer loop it
    updates the donated buffer in place: nothing pool-sized is sliced
    out, copied or written back. Null-page discipline: any position
    past the block-table width is redirected to page 0 with a ZEROED
    payload. That is where EVERY slot without a decoding request
    writes: the server parks its offset on the idle sentinel
    (``max_cache_len``, the table's span) whether it was never used,
    finished, was cancelled or preempted, or is mid-prefill, so its
    decode rows can never corrupt a live slot's pages (the dense
    analogue relies on out-of-bounds writes being dropped), and its
    padded chunk rows — which carry rope's / the position table's
    out-of-range NaN fill — never store a NaN that would poison every
    slot's attention through 0-weight reads (every slot's unused
    block-table entries point at the null page). The wasted block steps
    of a LIVE slot past its budget land inside the table but past its
    allocation, in its NULL_PAGE tail entries — finite garbage the
    length masks hide."""
    pg = pool.shape[2]
    b, s = kv.shape[0], kv.shape[1]
    maxp = bt.shape[1]
    if jnp.ndim(t) == 0:
        t = jnp.full((b,), t, jnp.int32)
    P = _positions(t, b, s)                              # [B, s]
    pidx = P // pg
    oob = pidx >= maxp
    page = jnp.where(
        oob, jnp.int32(0),
        jnp.take_along_axis(bt, jnp.minimum(pidx, maxp - 1), axis=1))
    vals = pool_lanes(kv.astype(pool.dtype))             # [B, s, h*hd]
    vals = jnp.where(oob[..., None], jnp.zeros_like(vals), vals)
    n = b * s
    return pool.at[layer, page.reshape(n), (P % pg).reshape(n)].set(
        vals.reshape(n, vals.shape[-1]))


def _paged_prefill_attend(q, pool, layer, bt, t, scale, mesh=None,
                          plan=None):
    """Ragged packed-prefill attention through the block table: q
    [B, s, nh, hd] chunk rows starting at per-slot offsets ``t``,
    ``pool`` the whole K/V pools read at ``layer``, over ``plan``
    (``_paged_prefill_plan(bt, t, take, s, pg)``, which the layer
    loop's caller makes once for all its layers); row j of slot b
    attends to positions <= t_b + j (cache already written through the
    chunk). Pallas kernel on TPU, bit-exact dense-mirroring gather
    composition elsewhere. A slot carrying the scheduler's idle
    sentinel (``t`` past the block-table extent) has no step in the
    kernel's grid, nor has a query tile past a chunk's real rows: both
    read zeros instead of sweeping NaN garbage. A live slot scans at
    most one query tile past its real frontier (the padding rows of its
    last live tile)."""
    from ..ops.pallas.ragged_prefill import ragged_prefill_attention
    b, s = q.shape[0], q.shape[1]
    if jnp.ndim(t) == 0:
        t = jnp.full((b,), t, jnp.int32)
    if plan is None:
        plan = _paged_prefill_plan(bt, t, None, s, pool["k"].shape[2])
    take, schedule = plan
    return ragged_prefill_attention(q, pool["k"], pool["v"], bt, t,
                                    take=take, sm_scale=scale, mesh=mesh,
                                    layer=layer, schedule=schedule)


def _paged_kv_step(pool, layer, q, k, v, bt, t, scale, mesh=None,
                   select=None, plan=None):
    """One layer's page write and attention over the CARRIED pools
    [L, P, pg, lanes]: the new rows k/v [B, s, kvh, hd] land in layer
    ``layer``'s pages through the block table, then q [B, s, nh, hd]
    attends through it. s == 1 is a decode step (ragged paged-attention
    kernel over ``plan``, the step's ``_paged_decode_plan``); s > 1 a
    RAGGED PREFILL chunk at per-slot offsets ``t`` (ragged prefill
    kernel over ``plan``, the launch's ``_paged_prefill_plan``) — which
    is what lets the server prefill several admissions as one launch
    with no dense-cache detour.

    ``select`` (``(qi, wi, ki, topk)``: indexer queries [B, s, J, D],
    head weights [B, s, J], the rows' indexer keys [B, s, 1, D]) is
    learned key selection: ``ki`` is written into the pool's third
    leaf beside k and v, and attention runs over the ``topk`` keys the
    indexer ranks highest (``ops/key_selection.py``, one composition
    for decode rows and prefill chunks). Returns ``(att [B, s, nh,
    hd], pool, kept)``: ``kept`` [B, s] is the number of keys the
    selection let each row attend (None without ``select``)."""
    rows = {"k": k, "v": v}
    if select is not None:
        rows["ki"] = select[2]
    pool = {n: _page_write(pool[n], layer, rows[n], bt, t) for n in pool}
    kept = None
    if select is not None:
        from ..ops.key_selection import sparse_paged_attention
        b = q.shape[0]
        if jnp.ndim(t) == 0:
            t = jnp.full((b,), t, jnp.int32)
        att, kept = sparse_paged_attention(q, select[0], select[1], pool,
                                           layer, bt, t, select[3], scale)
    elif q.shape[1] > 1:
        att = _paged_prefill_attend(q, pool, layer, bt, t, scale, mesh=mesh,
                                    plan=plan)
    else:
        att = _paged_attend(q, pool, layer, bt, t, scale, mesh=mesh,
                            plan=plan)
    return att, pool, kept


# which stack of a layer-spec model a bundle leaf belongs to: the norms
# are stacked over every layer, the rest over the layers of their kind
_LEAF_KIND = dict(
    {n: "layer" for n in ("ln1", "ln2")},
    **{n: "attn" for n in ("wq", "wk", "wv", "wo", "qn", "kn")},
    **{n: "conv" for n in ("ci", "cw", "co")},
    **{n: "ssm" for n in ("si", "sw", "sb", "sa", "sd", "st", "sn", "so")},
    **{n: "dense" for n in ("dg", "du", "dd")},
    **{n: "moe" for n in ("router", "rbias", "ld", "lu", "s1", "s2")})


def _layer_spec(cfg):
    """A model whose layers are NOT alike says so in its config
    (``layer_types``: each layer's mixer, ``"full_attention"`` or
    ``"conv"``; ``num_dense_layers``: how many leading layers keep a
    dense FFN in an expert model; or ``sublayers``: a layer is ONE
    sublayer behind one norm, a mixer OR an FFN, named by the stack it
    reads: ``"ssm"``, ``"attn"``, ``"moe"``). Returns None for a model
    of identical layers, else one dict a layer: the layer's index within
    each stack it reads (``_LEAF_KIND``) — ``{"layer": l, "attn" |
    "conv": i, "dense" | "moe": j}``, or ``{"layer": l, "ssm" | "attn" |
    "moe": i}``. ``attn`` is also the layer's index in the page pool,
    ``conv`` and ``ssm`` in the slot state, ``moe`` in the expert
    stacks."""
    types = getattr(cfg, "layer_types", None)
    experts = bool(getattr(cfg, "num_experts", 0))
    n_dense = int(getattr(cfg, "num_dense_layers", 0) or 0) if experts else 0
    solo = getattr(cfg, "sublayers", None)
    if types is None and not n_dense and solo is None:
        return None
    types = tuple(types or ("full_attention",) * cfg.num_layers)
    spec, count = [], {}
    for l, mixer in enumerate(types):
        kinds = (solo[l],) if solo is not None else (
            "conv" if mixer == "conv" else "attn",
            "moe" if experts and l >= n_dense else "dense")
        at = {"layer": l}
        for kind in kinds:
            at[kind] = count[kind] = count.get(kind, -1) + 1
        spec.append(at)
    return tuple(spec)


def _run_layers(layer_fn, x, blk_tree, caches, paged, spec=None):
    """THE layer loop of every decode bundle. ``layer_fn(xx, blk, lc,
    l) -> (xx, lc, aux)`` runs one layer over its slice ``blk`` of the
    stacked weights; ``aux`` (None, or a small dict such as the experts
    a row chose and the keys it kept) comes back stacked over layers.
    Returns ``(x, caches, aux)``.

    Dense: a scan with the per-layer caches as ``xs``/``ys`` (``lc`` is
    layer ``l``'s cache dict). Paged: the hidden state AND what the
    layers write (``lc``: the cache tree's ``pool``, whose leaves are
    written and read at layer index ``l``, and ``state`` where the model
    has per-slot state) are the loop's CARRY; the stacked weights are
    scanned as before. The pool must be carried, not scanned: as
    ``xs``/``ys`` a donated pool can never be its own result, so the
    compiled tick copied the pool whole, sliced each layer out and wrote
    it back into a new one — pool-sized HBM traffic five times a tick
    and a second copy of the pool in temp. Carried, with the layer's
    rows scattered in at ``[l, page, offset]`` and the kernels indexing
    the layer themselves, the donated argument's buffer IS the result's.

    ``spec`` (``_layer_spec``): the layers are not alike, so there is no
    one body to scan. The same loop runs unrolled: layer ``l`` gets the
    slices of the stacks it reads, each at the layer's index WITHIN that
    stack (static, so nothing is copied out), and ``l`` is ``spec[l]``
    itself; ``lc`` is the carried tree on both backends (dense: the
    whole cache dict, its leaves stacked over the layers of their
    kind). ``aux`` comes back stacked, a key at a time, over the layers
    that returned that key."""
    if spec is not None:
        lc = ({n: caches[n] for n in ("pool", "state") if n in caches}
              if paged else caches)
        auxes = []
        for at in spec:
            # (tree_map: an int8 weight is an (int8, scale) pair)
            blk = {n: jax.tree_util.tree_map(
                       lambda w, i=at.get(_LEAF_KIND[n]): w[i], a)
                   for n, a in blk_tree.items() if _LEAF_KIND[n] in at}
            x, lc, aux = layer_fn(x, blk, lc, at)
            auxes.append(aux or {})
        aux = {k: jnp.stack([a[k] for a in auxes if k in a])
               for k in sorted({k for a in auxes for k in a})}
        return x, dict(caches, **lc), aux

    layers = jnp.arange(jax.tree_util.tree_leaves(blk_tree)[0].shape[0],
                        dtype=jnp.int32)
    if not paged:
        def dense(xx, xs):
            xx, lc, aux = layer_fn(xx, *xs)
            return xx, (lc, aux)

        x, (caches, aux) = jax.lax.scan(dense, x, (blk_tree, caches,
                                                   layers))
        return x, caches, aux

    def body(carry, xs):
        xx, lc, aux = layer_fn(carry[0], xs[0], {"pool": carry[1]}, xs[1])
        return (xx, lc["pool"]), aux

    (x, pool), aux = jax.lax.scan(body, (x, caches["pool"]),
                                  (blk_tree, layers))
    return x, dict(caches, pool=pool), aux


def _short_conv_mixer(blk, xx, state, layer, t, take, live, eps):
    """The gated short-convolution sublayer (``lfm2``'s ``conv`` layer)
    for the decode loop: pre-RMSNorm, ``B, C, X = split(h W_in)``, a
    depthwise causal convolution of ``u = B * X`` over time, ``(C * c)
    W_out``, residual. Its only state is a sequence's last ``K - 1``
    rows of ``u``: ``state`` ``[conv layers, slots, K - 1, H]``, read and
    written at ``layer`` — per SLOT, not through the block table.

    ``xx`` [B, s, H] are chunk rows at per-slot offsets ``t`` (a decode
    row: s = 1), of which the first ``take`` [B] are real (the rest is
    chunk padding). A row run that starts a sequence (``t == 0``)
    convolves from zeros whatever the slot held; the state it leaves is
    that of its last REAL row; a slot that is not ``live`` (parked on
    the idle sentinel: it rides the launch with garbage rows) keeps its
    state untouched."""
    from ..ops.short_conv import short_conv
    with jax.named_scope("short_conv"):
        k = blk["cw"].shape[0]
        h = _rms(xx, blk["ln1"], eps)
        gb, gc, gx = jnp.split(_mm(h, blk["ci"]), 3, axis=-1)
        held = state[layer]
        prev = jnp.where((t == 0)[:, None, None], jnp.zeros_like(held), held)
        c, full = short_conv(gb * gx, blk["cw"], prev)
        rows = take[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None]
        new = jnp.take_along_axis(full, rows[:, :, None], axis=1)
        new = jnp.where(live[:, None, None], new, held)
        return xx + _mm(gc * c, blk["co"]), state.at[layer].set(new)


def _ssm_core(blk, xbc, z, dt, s0, take, dims, eps):
    """A Mamba-2 mixer between its convolution and its output
    projection: ``xbc`` [B, s, I + 2 G N] the convolved, activated
    channels (``X``, ``B``, ``C``), ``z`` [B, s, I] the gate, ``dt``
    [B, s, H] the raw step sizes, ``s0`` [B, H, P, N] float32 the state
    before the rows, ``take`` [B] the real rows (None: all): a row past
    them takes a step of size 0, so the state that comes back is the
    last real row's. One row is ``ssm_step``, a run the chunked
    ``ssm_scan``: the same recurrence. Then the skip ``D X``, the gate
    BEFORE the norm, and an RMSNorm a group. Returns ``(y [B, s, I],
    state)``."""
    from ..ops.ssm_scan import ssm_scan, ssm_step
    heads, p, groups, n, chunk = dims
    b, s = xbc.shape[:2]
    inner = heads * p
    x, bm, cm = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    x = x.reshape(b, s, heads, p)
    bm, cm = bm.reshape(b, s, groups, n), cm.reshape(b, s, groups, n)
    d = jax.nn.softplus(dt.astype(jnp.float32) + blk["st"])
    if take is not None:
        real = jnp.arange(s, dtype=jnp.int32)[None] < take[:, None]
        d = jnp.where(real[..., None], d, 0.0)
    a = -jnp.exp(blk["sa"])
    if s == 1:
        y, s1 = ssm_step(x[:, 0], d[:, 0], a, bm[:, 0], cm[:, 0], s0)
        y = y[:, None]
    else:
        y, s1 = ssm_scan(x, d, a, bm, cm, s0, chunk)
    y = y + blk["sd"][:, None] * x.astype(jnp.float32)
    y = y.reshape(b, s, inner) * jax.nn.silu(z.astype(jnp.float32))
    g = y.reshape(b, s, groups, inner // groups)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return g.reshape(b, s, inner).astype(z.dtype) * blk["sn"], s1


def _ssm_mixer(blk, xx, state, layer, t, take, live, dims, eps, slots=None):
    """The Mamba-2 sublayer (``nemotron_h``'s ``M`` layer) for the
    decode loop: pre-RMSNorm, ``z, xBC, dt = split(u W_in)``, a
    depthwise causal convolution with a bias and a SiLU over ``xBC``,
    the state-space recurrence (``_ssm_core``), ``W_out``, residual. Its
    state is a TREE, per SLOT and not through the block table, read and
    written at ``layer``: ``conv`` ``[ssm layers, slots, K - 1, I + 2 G
    N]``, a sequence's last ``K - 1`` rows of ``xBC`` in the model's
    type, and ``ssm`` ``[ssm layers, slots, H, P, N]``, the recurrent
    state, float32.

    ``xx``, ``t``, ``take`` and ``live`` as ``_short_conv_mixer`` has
    them, and the same three rules: a run that starts a sequence
    (``t == 0``) starts from zeros whatever the slot held; the state it
    leaves is that of its last REAL row; a slot that is not ``live``
    keeps its state untouched."""
    from ..ops.short_conv import short_conv
    with jax.named_scope("ssm_scan"):
        inner = dims[0] * dims[1]
        k = blk["sw"].shape[0]
        u = _rms(xx, blk["ln1"], eps)
        z, xbc, dt = jnp.split(
            _mm(u, blk["si"]), [inner, inner + blk["sw"].shape[1]], axis=-1)
        if slots is None:
            conv, ssm = state["conv"][layer], state["ssm"][layer]
        else:
            at = jnp.minimum(slots, state["ssm"].shape[1] - 1)
            conv, ssm = state["conv"][layer, at], state["ssm"][layer, at]
        fresh = t == 0
        c, full = short_conv(xbc, blk["sw"], jnp.where(
            fresh[:, None, None], jnp.zeros_like(conv), conv))
        rows = take[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None]
        new = jnp.take_along_axis(full, rows[:, :, None], axis=1)
        y, s1 = _ssm_core(
            blk, jax.nn.silu(c + blk["sb"]), z, dt, jnp.where(
                fresh[:, None, None, None], jnp.zeros_like(ssm), ssm),
            take, dims, eps)
        if slots is None:
            state = {
                "conv": state["conv"].at[layer].set(
                    jnp.where(live[:, None, None], new, conv)),
                "ssm": state["ssm"].at[layer].set(
                    jnp.where(live[:, None, None, None], s1, ssm))}
        else:
            # (a row that is not live names no slot: its write is dropped)
            to = jnp.where(live, slots, state["ssm"].shape[1])
            state = {
                "conv": state["conv"].at[layer, to].set(new, mode="drop"),
                "ssm": state["ssm"].at[layer, to].set(s1, mode="drop")}
        return xx + _mm(y, blk["so"]), state


def _rope_gqa_attn(blk, xx, lc, t, pos, dims, tables, eps, bt=None,
                   mesh=None, layer=None, qk_norm=False, indexer=None,
                   plan=None):
    """Shared llama-family attention sublayer for the decode loop:
    pre-RMSNorm, rope at absolute positions, GQA cache write + masked
    cached attention, output projection + residual. ``lc`` is this
    layer's cache dict (fp or int8 codec) — or, when ``bt`` (a per-slot
    block table) is given, the WHOLE page pools, written and attended
    at ``layer`` through the table (paged backend:
    ``_paged_kv_step``, a decode step over its ``plan``). ``qk_norm``: RMSNorm over each
    head's dims with the learned gains ``blk["qn"]``/``blk["kn"]``,
    before the rope (Qwen3's). ``indexer`` (``(heads, dim, topk, (cos,
    sin))``): learned key selection — indexer queries ``blk["iq"]``,
    one shared key ``blk["ik"]`` (cached beside k and v) and head
    weights ``blk["iw"]`` pick the ``topk`` keys attention runs over.
    ``tables`` None: no positional term (``nemotron_h``). Returns (xx,
    lc, h2, kept) with h2 = the post-attention norm for the FFN (None
    where the block has none) and kept [B, s] the keys the selection let
    each row attend (None without an indexer)."""
    b, s, nh, kvh, hd, scale = dims
    from ..ops.pallas import rope as rope_mod
    h = _rms(xx, blk["ln1"], eps)
    q = _mm(h, blk["wq"]).reshape(b, s, nh, hd)
    k = _mm(h, blk["wk"]).reshape(b, s, kvh, hd)
    v = _mm(h, blk["wv"]).reshape(b, s, kvh, hd)
    if qk_norm:
        q = _rms(q, blk["qn"], eps)
        k = _rms(k, blk["kn"], eps)
    if tables is not None:      # None: the model has no positional term
        cos, sin = tables
        q = rope_mod._apply_rotary_jnp(q, cos, sin, position_ids=pos)
        k = rope_mod._apply_rotary_jnp(k, cos, sin, position_ids=pos)
    select = kept = None
    if indexer is not None:
        heads, dim, topk, (icos, isin) = indexer
        qi = rope_mod._apply_rotary_jnp(
            _mm(h, blk["iq"]).reshape(b, s, heads, dim), icos, isin,
            position_ids=pos)
        ki = rope_mod._apply_rotary_jnp(
            _mm(h, blk["ik"]).reshape(b, s, 1, dim), icos, isin,
            position_ids=pos)
        select = (qi, _mm(h, blk["iw"]), ki, topk)
    if bt is not None:
        att, lc, kept = _paged_kv_step(lc, layer, q, k, v, bt, t, scale,
                                       mesh=mesh, select=select, plan=plan)
    else:
        lc = _kv_write(lc, "k", k, t)
        lc = _kv_write(lc, "v", v, t)
        kc = _kv_read(lc, "k", q.dtype)
        vc = _kv_read(lc, "v", q.dtype)
        if select is not None:
            from ..ops.key_selection import sparse_dense_attention
            lc = _kv_write(lc, "ki", select[2], t)
            att, kept = sparse_dense_attention(
                q, select[0], select[1], kc, vc, lc["ki"], t, select[3],
                scale)
        else:
            rep = nh // kvh
            kk = jnp.repeat(kc, rep, axis=2) if rep > 1 else kc
            vv = jnp.repeat(vc, rep, axis=2) if rep > 1 else vc
            att = _cached_attend(q, kk, vv, t, s, scale)
    xx = xx + _mm(att.reshape(b, s, nh * hd), blk["wo"])
    # (a layer that is this sublayer alone has no second norm)
    h2 = _rms(xx, blk["ln2"], eps) if "ln2" in blk else None
    return xx, lc, h2, kept


def _make_ragged_prefill_fn(step_fn, head_fn, embed_tokens):
    """Build the paged bundle's ragged-prefill entry point: several
    variable-length prompt chunks — one per serving slot — run as ONE
    program, K/V written straight into pool pages through the block
    table (no dense batch-1 cache detour) and attended causally at
    per-slot prefix offsets, so an auto-prefix-cache hit resumes over
    its already-cached pages exactly like decode does.

    Signature: ``(tokens [P, C], t0 [P], caches, out_idx [P], take [P],
    slots [P]) -> (logits [P, V], caches)``. ``tokens`` holds one
    right-padded chunk a row, ``t0`` the chunk's absolute start position
    (a row with no prefill work carries t0 = max_cache_len: every one
    of its writes null-redirects and its rows are garbage nobody
    reads), ``out_idx`` the row of each chunk's LAST prompt token —
    ``logits[j]`` is that row's next-token distribution, valid only for
    slots whose prompt completes in this launch. All chunk geometry is
    static per (P, C): the server pads C up a power-of-two ladder so
    compiles stay O(log max_cache_len), not O(distinct prompt lengths).

    A launch is PACKED: it has a row for P slots and not for all of
    them, row j being slot ``slots[j]`` (the server puts its plan's j-th
    slot there and gives the launch ``min(slots, R // C)`` rows). ``R``
    is ``continuous_batching._launch_row_limit``: the power of two over
    the server's per-tick token budget, so a launch's dense matmuls are
    sized by the tokens it may carry; 4,096 survives as the ceiling on
    ``R``, for a server whose budget is as long as a 16k cache. A
    padding row names a slot past the last, carries the idle sentinel in
    ``t0`` and writes nothing. The step runs over the launch's VIEW of
    the per-slot leaves (the block table's rows and, where the model has
    it, the slot state, gathered at ``slots``) and the state is
    scattered back; a state that is a TREE of leaves is viewed a layer
    at a time by the mixers that own it. ``take`` [P]: the REAL rows of each chunk: the
    prefill kernel's grid has no step for a query tile past them, and a
    model with per-slot state must know where a chunk that ends
    mid-prompt really ends (the K/V of padding rows are hidden by
    lengths, a recurrent state would carry them).
    """
    def prefill_tick(tokens, t0, caches, out_idx, take, slots):
        P = tokens.shape[0]
        x = embed_tokens(tokens, t0)
        at = jnp.minimum(slots, caches["bt"].shape[0] - 1)
        view = dict(caches, bt=caches["bt"][at])
        # a state TREE is viewed by its mixers, a layer at a time
        # (``_ssm_mixer``): the step is handed the whole of it and the
        # rows' slots
        own = isinstance(caches.get("state"), dict)
        if "state" in caches and not own:
            view["state"] = caches["state"][:, at]
        out, new = step_fn(x, view, t0, take, *((slots,) if own else ()))
        new = dict(new, bt=caches["bt"])
        if "state" in caches and not own:
            new["state"] = caches["state"].at[:, slots].set(
                new["state"], mode="drop")
        last = out[jnp.arange(P), out_idx][:, None]         # [P, 1, H]
        return head_fn(last)[:, -1], new

    # hoisted_jit names the program after it: ``jit_prefill_tick``
    return prefill_tick


# bundle leaf -> the per-block parameter a LlamaBlock / MixtralBlock holds
_LLAMA_ATTN = {"ln1": "input_layernorm.weight",
               "ln2": "post_attention_layernorm.weight",
               "wq": "self_attn.q_proj.weight",
               "wk": "self_attn.k_proj.weight",
               "wv": "self_attn.v_proj.weight",
               "wo": "self_attn.o_proj.weight"}
_LLAMA_FFN = {"wg": "mlp.gate_proj.weight", "wu": "mlp.up_proj.weight",
              "wd": "mlp.down_proj.weight"}
_MIXTRAL_FFN = {"router": "moe.gate.gate.weight",
                "wg": "moe.experts.w_gate", "wu": "moe.experts.w_up",
                "wd": "moe.experts.w_down"}
_EXPERT_LEAVES = ("wg", "wu", "wd")


def _llama_family_weights(model, moe):
    """The llama-family bundle's weight tree, every block leaf stacked
    over layers. A model that already keeps its blocks stacked hands
    over its OWN arrays (``decode_weights()``: no second copy of the
    weights exists); a model of per-layer blocks is stacked here."""
    own = getattr(model, "decode_weights", None)
    if own is not None:
        return own()
    blocks = [dict(blk.raw_params()) for blk in model.model.layers]
    tree = {"table": unwrap(model.model.embed_tokens.weight),
            "norm": unwrap(model.model.norm.weight),
            "head": unwrap(model.lm_head.weight)}             # [H, V]
    names = dict(_LLAMA_ATTN, **(_MIXTRAL_FFN if moe else _LLAMA_FFN))
    tree.update({leaf: _stacked(blocks, name)
                 for leaf, name in names.items()})
    return tree


def _make_llama_decode_fns(model, max_cache_len, weight_dtype=None, mesh=None,
                cache_dtype=None, cache_backend="dense", page_size=None,
                num_pages=None):
    """(init_caches, embed_fn, step_fn, head_fn[, ragged]) for the
    llama family: pre-RMSNorm blocks, rope at absolute positions,
    GQA (kv heads cached unrepeated), SwiGLU. What a block has BESIDES
    that is read from ``model.cfg`` — one builder, no copy per model:

    - ``num_experts`` / ``top_k`` (/ ``norm_topk_prob``): the FFN is a
      routed expert FFN (``ops/routed_ffn.py``: exact top-k for any k,
      no capacity, only the chosen experts computed) — Mixtral, Keye;
    - ``qk_norm``: per-head RMSNorm of q and k before the rope;
    - ``indexer`` (``(heads, dim, topk)``): learned key selection with
      an indexer-key cache beside K and V (``ops/key_selection.py``);
    - ``router_score`` (``"sigmoid"``; ``use_expert_bias``,
      ``routed_scaling_factor``): each expert scores on its own and a
      bias chooses without weighing (``route_topk``);
    - ``layer_types`` / ``num_dense_layers``: the layers are NOT alike
      (``_layer_spec``): a layer's mixer is attention or a gated short
      convolution (``_short_conv_mixer``, whose state is per slot:
      ``caches["state"]``, a layer a conv layer), its FFN dense or
      routed; the weights are stacked a kind of sublayer, the loop runs
      the spec, and the caches have a layer an ATTENTION layer;
    - ``sublayers`` (``nemotron_h``): a layer is ONE sublayer behind
      one norm: a Mamba-2 mixer (``_ssm_mixer``; ``ssm_dims``; slot
      state a tree of two leaves), attention (``rope_theta`` None: no
      positional term) or a latent expert layer (``latent_moe`` below:
      ``experts_held``, the share of the router's experts the stacks
      hold; ungated ``relu2`` experts; ``router_eps``).

    ``cache_backend="paged"`` swaps the dense per-slot cache for a
    global page pool + per-slot block tables."""
    from ..ops.pallas import rope as rope_mod
    from ..ops.routed_ffn import held_tile, route_topk, routed_ffn
    cfg = model.cfg
    nh, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.rms_eps
    moe = bool(getattr(cfg, "num_experts", 0))
    top_k = int(getattr(cfg, "top_k", 0)) if moe else 0
    norm_topk = bool(getattr(cfg, "norm_topk_prob", True))
    # how the router scores: softmax gates, or (``"sigmoid"``) each
    # expert's own sigmoid with a bias that chooses and does not weigh
    route_kw = {}
    if getattr(cfg, "router_score", "softmax") != "softmax":
        route_kw = dict(score=cfg.router_score, scale=float(
            getattr(cfg, "routed_scaling_factor", 1.0)))
        if hasattr(cfg, "router_eps"):
            route_kw["eps"] = float(cfg.router_eps)
    use_bias = bool(getattr(cfg, "use_expert_bias", True))
    qk_norm = bool(getattr(cfg, "qk_norm", False))
    indexer = getattr(cfg, "indexer", None)
    if indexer is not None and mesh is not None:
        raise NotImplementedError(
            "key selection is not wired for a mesh (ROADMAP A8, the "
            "mesh column): its indexer-key pool has one head, which no "
            "kv-head sharding divides")
    # layers that are not alike: the loop below runs the spec
    spec = _layer_spec(cfg)
    n_of = lambda kind: sum(kind in at for at in spec)
    conv_rows = int(getattr(cfg, "conv_L_cache", 1)) - 1
    ssm_dims = getattr(cfg, "ssm_dims", None)
    # the share of the router's experts the stacks hold: (first, count)
    held = getattr(cfg, "experts_held", None)

    ffn_dims = ({"wg": 1, "wu": 1, "wd": 1} if moe     # expert-parallel
                else {"wg": 2, "wu": 2, "wd": 1})
    p = _stacked_weights(
        model, weight_dtype, mesh,
        lambda: _llama_family_weights(model, moe),
        dict({"wq": 2, "wk": 2, "wv": 2,               # column-parallel
              "wo": 1, "head": 1}, **ffn_dims))        # row-parallel
    tables = (None if cfg.rope_theta is None else
              rope_mod.precompute_freqs(hd, max_cache_len, cfg.rope_theta))
    if indexer is not None:
        heads, dim, topk = indexer
        indexer = (int(heads), int(dim), int(topk),
                   rope_mod.precompute_freqs(int(dim), max_cache_len,
                                             cfg.rope_theta))
    dtype = p["table"].dtype
    # the caches have a layer an ATTENTION layer, the slot state a layer
    # a conv layer, the route read-back a layer an expert layer
    L = cfg.num_layers if spec is None else n_of("attn")
    state = None
    if spec is not None and n_of("conv"):
        state = jax.ShapeDtypeStruct(
            (n_of("conv"), conv_rows, p["table"].shape[1]), dtype)
    elif spec is not None and n_of("ssm"):
        heads, width, _, n, _ = ssm_dims
        state = {"conv": jax.ShapeDtypeStruct(
                     (n_of("ssm"), p["sw"].shape[1] - 1, p["sw"].shape[2]),
                     dtype),
                 "ssm": jax.ShapeDtypeStruct(
                     (n_of("ssm"), heads, width, n), jnp.float32)}
    scale = 1.0 / np.sqrt(hd)
    paged = cache_backend == "paged"
    if paged:
        _check_paged_config(max_cache_len, page_size, num_pages,
                            cache_dtype, mesh)

    def init_caches(batch):
        if paged:
            return _init_paged_kv(
                batch, L, num_pages, page_size, max_cache_len // page_size,
                kvh, hd, dtype,
                extra={"ki": (1, indexer[1])} if indexer else None,
                route_k=top_k, kept=indexer is not None,
                route_layers=None if spec is None else n_of("moe"),
                state=state)
        tree = _init_kv((L, batch, max_cache_len, kvh, hd), dtype,
                        cache_dtype,
                        index_dim=indexer[1] if indexer else None)
        if state is not None:
            tree["state"] = _slot_state(state, batch)
        return tree

    if mesh is not None:
        init_caches = (_mesh_paged_caches(init_caches, mesh, kvh) if paged
                       else _mesh_caches(init_caches, mesh))

    def embed_fn(tok, t):
        return p["table"][tok][:, None, :]

    # the expert stacks are NOT scanned with the block: a scan would
    # slice a layer's 128 experts out whole; the routed FFN slices one
    # expert a tile at (layer, expert)
    skip = ("table", "norm", "head") + (_EXPERT_LEAVES if moe else ())
    blk_tree = {k_: v_ for k_, v_ in p.items() if k_ not in skip}

    def _forward(x, caches, t, bt, take=None, slots=None):
        x = unwrap(x)
        b, s = x.shape[0], x.shape[1]
        # an idle slot's offset is parked past the table: its rows are
        # garbage nobody reads, rotated at the last real position
        pos = jnp.minimum(_positions(t, b, s), max_cache_len - 1)
        # ... and they are sent to no expert: every row of a slot parked
        # on the sentinel is dead to the routed FFN. The padding rows
        # INSIDE a live slot's chunk stay live
        alive = jnp.broadcast_to(t < max_cache_len, (b,))
        live = jnp.repeat(alive, s)
        # the attention kernel's grid, once for every layer: a decode
        # step's live pages, a prefill launch's live query tiles and
        # theirs (key selection attends without either kernel)
        plan = None
        if paged and indexer is None:
            plan = (_paged_decode_plan(bt, t, page_size) if s == 1
                    else _paged_prefill_plan(bt, t, take, s, page_size))
        if state is not None:
            t_b = jnp.broadcast_to(t, (b,))
            rows_b = (jnp.full((b,), s, jnp.int32) if take is None
                      else take)

        def attend(blk, xx, lc, l):
            """The attention sublayer over the carried caches ``lc``:
            the page pool (paged), a layer's dense caches (scanned), or
            under a layer spec the whole dense tree, read at ``l``."""
            if paged:
                xx, pool, h2, kept = _rope_gqa_attn(
                    blk, xx, lc["pool"], t, pos, (b, s, nh, kvh, hd, scale),
                    tables, eps, bt=bt, mesh=mesh, layer=l,
                    qk_norm=qk_norm, indexer=indexer, plan=plan)
                return xx, dict(lc, pool=pool), h2, kept
            own = lc if spec is None else {n: lc[n][l] for n in lc
                                           if n != "state"}
            xx, own, h2, kept = _rope_gqa_attn(
                blk, xx, own, t, pos, (b, s, nh, kvh, hd, scale),
                tables, eps, qk_norm=qk_norm, indexer=indexer)
            if spec is not None:
                own = dict(lc, **{n: lc[n].at[l].set(own[n]) for n in own})
            return xx, own, h2, kept

        def layer(xx, blk, lc, at):
            # ``at``: the scan's layer index or, under a layer spec,
            # this layer's index within each stack it reads
            kept = None
            if "si" in blk:
                xx, held_state = _ssm_mixer(
                    blk, xx, lc["state"], at["ssm"], t_b, rows_b, alive,
                    ssm_dims, eps, slots)
                lc, h2 = dict(lc, state=held_state), None
            elif "ci" in blk:
                xx, held_state = _short_conv_mixer(
                    blk, xx, lc["state"], at["conv"], t_b, rows_b, alive,
                    eps)
                lc = dict(lc, state=held_state)
                h2 = _rms(xx, blk["ln2"], eps)
            elif "wq" in blk:
                xx, lc, h2, kept = attend(blk, xx, lc,
                                          at if spec is None else at["attn"])
            else:       # a layer that is an FFN alone, behind its one norm
                h2 = _rms(xx, blk["ln1"], eps)
            # what each slot's LAST row did, for the decode tick's
            # read-back: the keys it attended, the experts it chose
            aux = {} if kept is None else {"kept": kept[:, -1]}
            if h2 is None:      # a layer that is a mixer alone
                return xx, lc, aux
            if "dg" in blk or not moe:
                wg, wu, wd = (blk[n] for n in (
                    ("dg", "du", "dd") if "dg" in blk
                    else ("wg", "wu", "wd")))
                return xx + _mm(jax.nn.silu(_mm(h2, wg)) * _mm(h2, wu),
                                wd), lc, aux
            kw = (dict(route_kw, bias=blk["rbias"])
                  if "rbias" in blk and use_bias else route_kw)
            if "ld" in blk:
                # the latent expert layer: the router and the shared
                # expert read the full width, the routed experts (two
                # matrices each, not gated) a latent between two
                # projections; the stacks hold ``held`` of the experts
                # the router chose among
                with jax.named_scope("latent_moe"):
                    rows = h2.reshape(b * s, h2.shape[-1])
                    idx, gate = route_topk(rows, blk["router"], top_k,
                                           normalize=norm_topk, **kw)
                    with jax.named_scope("moe_ffn"):
                        y = routed_ffn(
                            _mm(rows, blk["ld"]), idx, gate, None, p["wu"],
                            p["wd"], layer=at["moe"], live=live, held=held,
                            tile=held_tile(rows.shape[0] * top_k, held[1],
                                           blk["router"].shape[-1]))
                    y = _mm(y, blk["lu"]) + _mm(jnp.square(jax.nn.relu(
                        _mm(rows, blk["s1"]))), blk["s2"])
            else:
                with jax.named_scope("moe_ffn"):
                    rows = h2.reshape(b * s, h2.shape[-1])
                    idx, gate = route_topk(rows, blk["router"], top_k,
                                           normalize=norm_topk, **kw)
                    y = routed_ffn(rows, idx, gate, p["wg"], p["wu"],
                                   p["wd"],
                                   layer=at if spec is None else at["moe"],
                                   live=live)
            aux["route"] = idx.reshape(b, s, top_k)[:, -1]
            return xx + y.reshape(xx.shape), lc, aux

        x, caches, aux = _run_layers(layer, x, blk_tree, caches, paged,
                                     spec)
        if paged and s == 1:
            caches = dict(caches, **aux)
        return x, caches

    def step_fn(x, caches, t, take=None, slots=None):
        return _forward(x, caches, t, caches["bt"] if paged else None,
                        take, slots)

    def head_fn(out):
        head = p["head"] if "head" in p else p["table"].T    # tied
        return (_rms(unwrap(out), p["norm"], eps) @ head
                ).astype(jnp.float32)

    if paged:
        embed_tokens = lambda tokens, t0: p["table"][tokens]
        ragged = _make_ragged_prefill_fn(step_fn, head_fn, embed_tokens)
        return init_caches, embed_fn, step_fn, head_fn, ragged
    return init_caches, embed_fn, step_fn, head_fn


def _make_gpt_decode_fns(model, max_cache_len, weight_dtype=None, mesh=None,
              cache_dtype=None, cache_backend="dense", page_size=None,
              num_pages=None):
    """(init_caches, embed_fn, step_fn, head_fn) for GPTForCausalLM —
    learned positions, fused qkv, tied lm head."""
    cfg = model.cfg
    nh = cfg.num_heads
    hd = cfg.hidden_size // nh
    eps = cfg.layer_norm_eps
    if max_cache_len > cfg.max_seq_len:
        raise ValueError(
            f"max_cache_len ({max_cache_len}) exceeds the learned "
            f"position table ({cfg.max_seq_len}); positions past it "
            f"would silently clamp — shorten the cache or grow wpe")

    def stack():
        blocks = [dict(blk.raw_params()) for blk in model.gpt.blocks]
        tree = {
            "table": unwrap(model.gpt.wte.weight),       # [V, H] (tied)
            "wpe": unwrap(model.gpt.wpe.weight),
            "lnf_w": unwrap(model.gpt.ln_f.weight),
            "lnf_b": unwrap(model.gpt.ln_f.bias),
        }
        for name in ("ln1.weight", "ln1.bias", "ln2.weight", "ln2.bias",
                     "attn.qkv.weight", "attn.qkv.bias",
                     "attn.proj.weight", "attn.proj.bias",
                     "mlp.fc1.weight", "mlp.fc1.bias",
                     "mlp.fc2.weight", "mlp.fc2.bias"):
            tree[name] = _stacked(blocks, name)
        return tree

    p = _stacked_weights(model, weight_dtype, mesh, stack, {
        "attn.qkv.weight": 2, "attn.proj.weight": 1,
        "mlp.fc1.weight": 2, "mlp.fc2.weight": 1})
    dtype = p["table"].dtype
    L = cfg.num_layers
    scale = 1.0 / np.sqrt(hd)
    paged = cache_backend == "paged"
    if paged:
        _check_paged_config(max_cache_len, page_size, num_pages,
                            cache_dtype, mesh)

    def init_caches(batch):
        if paged:
            return _init_paged_kv(batch, L, num_pages, page_size,
                                  max_cache_len // page_size, nh, hd,
                                  dtype)
        return _init_kv((L, batch, max_cache_len, nh, hd), dtype,
                        cache_dtype)

    if mesh is not None:
        init_caches = (_mesh_paged_caches(init_caches, mesh, nh) if paged
                       else _mesh_caches(init_caches, mesh))

    def embed_fn(tok, t):
        pos_emb = p["wpe"][t]                # scalar t: [H]; [B] t: [B,H]
        if jnp.ndim(t) == 0:
            pos_emb = pos_emb[None]
        return (p["table"][tok] + pos_emb)[:, None, :]

    def _forward(x, caches, t, bt, take=None):
        x = unwrap(x)
        b, s = x.shape[0], x.shape[1]
        # the attention kernel's grid, once for every layer: a decode
        # step's live pages, a prefill launch's live query tiles and
        # theirs
        plan = None
        if paged:
            plan = (_paged_decode_plan(bt, t, page_size) if s == 1
                    else _paged_prefill_plan(bt, t, take, s, page_size))

        def layer(xx, blk, lc, l):
            h = _ln(xx, blk["ln1.weight"], blk["ln1.bias"], eps)
            qkv = (_mm(h, blk["attn.qkv.weight"]) + blk["attn.qkv.bias"]
                   ).reshape(b, s, 3, nh, hd)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            if paged:
                att, pool, _ = _paged_kv_step(lc["pool"], l, q, k, v, bt,
                                              t, scale, mesh=mesh,
                                              plan=plan)
                lc = {"pool": pool}
            else:
                lc = _kv_write(lc, "k", k, t)
                lc = _kv_write(lc, "v", v, t)
                att = _cached_attend(q, _kv_read(lc, "k", q.dtype),
                                     _kv_read(lc, "v", q.dtype), t, s,
                                     scale)
            xx = xx + (_mm(att.reshape(b, s, nh * hd),
                           blk["attn.proj.weight"])
                       + blk["attn.proj.bias"])
            h2 = _ln(xx, blk["ln2.weight"], blk["ln2.bias"], eps)
            ff = jax.nn.gelu(_mm(h2, blk["mlp.fc1.weight"])
                             + blk["mlp.fc1.bias"], approximate=True)
            xx = xx + _mm(ff, blk["mlp.fc2.weight"]) + blk["mlp.fc2.bias"]
            return xx, lc, None

        blk_tree = {k_: v_ for k_, v_ in p.items()
                    if k_ not in ("table", "wpe", "lnf_w", "lnf_b")}
        return _run_layers(layer, x, blk_tree, caches, paged)[:2]

    def step_fn(x, caches, t, take=None):
        return _forward(x, caches, t, caches["bt"] if paged else None,
                        take)

    def head_fn(out):
        h = _ln(unwrap(out), p["lnf_w"], p["lnf_b"], eps)
        return (h @ p["table"].T).astype(jnp.float32)

    if paged:
        def gpt_embed_tokens(tokens, t0):
            # learned positions: per-slot offsets, [S, C] gather (an
            # idle slot's out-of-range rows pick up jnp's NaN fill —
            # zeroed on the null-page write, discarded on the output)
            pos = _positions(t0, tokens.shape[0], tokens.shape[1])
            return p["table"][tokens] + p["wpe"][pos]

        ragged = _make_ragged_prefill_fn(step_fn, head_fn,
                                         gpt_embed_tokens)
        return init_caches, embed_fn, step_fn, head_fn, ragged
    return init_caches, embed_fn, step_fn, head_fn


class GenerationMixin:
    """``generate()`` for causal-LM models (greedy + sampling), running
    prefill and the whole decode loop as on-device XLA programs."""

    def _decode_bundle(self, max_cache_len, weight_dtype=None, mesh=None,
                       cache_dtype=None, cache_backend="dense",
                       page_size=None, num_pages=None):
        key = ("_pt_decode_bundle", max_cache_len, weight_dtype,
               None if mesh is None else id(mesh), cache_dtype,
               cache_backend, page_size, num_pages)
        cached = getattr(self, "_pt_decode_cache", None)
        if cached is None:
            cached = self._pt_decode_cache = {}
        if key in cached:
            cached[key] = cached.pop(key)      # LRU: move to back
            return cached[key]
        kw = dict(cache_backend=cache_backend, page_size=page_size,
                  num_pages=num_pages)
        # a model names its block family (``decode_family``); what its
        # blocks have beyond the family's plain form the builder reads
        # from the config
        build = {"llama": _make_llama_decode_fns,
                 "gpt": _make_gpt_decode_fns}.get(
                     getattr(self, "decode_family", None))
        if build is None:
            # no-roadmap: model-family dispatch, not a scope cut
            raise NotImplementedError(
                f"generate() not wired for {type(self).__name__}")
        bundle = build(self, max_cache_len, weight_dtype, mesh,
                       cache_dtype, **kw)
        # one prefill program per (bundle, prompt-shape): jit here, not
        # inside generate(), so repeated calls reuse the compile. A
        # bundle has 5 elements (dense) or 6 (paged): paged bundles
        # carry a SIXTH — the jitted ragged-prefill entry point (packed
        # multi-slot prompt chunks straight into pool pages; see
        # _make_ragged_prefill_fn). Dense bundles stay 5-tuples for
        # existing consumers (deploy_decode, speculative).
        # The bundle functions close over the stacked weight tree, so
        # every program over them is built with hoisted_jit: the
        # weights ride as runtime arguments, never as constants of the
        # executable. A program is called what its function is called:
        # ``jit_decode_step`` here, ``jit_prefill_tick`` for the ragged
        # entry point.
        extras = bundle[4:]
        step_fn = bundle[2]

        def decode_step(x, caches, t):
            return step_fn(x, caches, t)

        bundle = bundle[:4] + (hoisted_jit(decode_step, donate_argnums=(1,)),)
        if extras:
            bundle = bundle + (hoisted_jit(extras[0], donate_argnums=(2,)),)
        cached[key] = bundle
        # a bundle pins its stacked weight tree (shared between the
        # bundles of one weight_dtype/mesh, see _stacked_weights) and
        # its compiled programs: cap the cache (LRU). 4 covers the
        # server's dense + paged pair twice over.
        while len(cached) > 4:
            cached.pop(next(iter(cached)))
        return bundle

    def _prefill_embed(self, ids, bundle, t0=0):
        """[B, T] ids -> [B, T, H] input embeddings for a multi-token
        step starting at position ``t0`` (prefill: 0; speculative
        verify: the current decode offset)."""
        if self.decode_family == "gpt":
            table = unwrap(self.gpt.wte.weight)
            wpe = unwrap(self.gpt.wpe.weight)
            return table[ids] + wpe[t0 + jnp.arange(ids.shape[1])][None]
        table = unwrap(self.model.embed_tokens.weight)
        return table[ids]

    def _run_prefill(self, bundle, ids_np, chunk=None, caches=None, t0=0):
        """Prefill ``ids_np`` [B, T] starting at position ``t0`` (fresh
        caches unless ``caches`` resumes a partially-filled tree, e.g. a
        shared-prefix hit); returns (last-position logits [B, V], caches).

        ``chunk``: feed the prompt in fixed-size chunks (prompt padded up
        to a multiple) so ONE compiled prefill program serves every
        prompt length — the serving-side compile-cache bound. Padded
        positions sit above the valid frontier: the causal validity mask
        hides their cache rows, and decode overwrites them."""
        init_caches, embed_fn, step_fn, head_fn, prefill_jit = bundle
        B, T = ids_np.shape
        if caches is None:
            caches = init_caches(B)
        if chunk and chunk < T and "state" in caches:
            raise NotImplementedError(
                "prefill_chunk pads the prompt's last chunk, and this "
                "model's per-slot recurrent state (caches['state']) would "
                "be that of the padding rows: prefill it unchunked here, or "
                "through the paged server's ragged launches, which tell the "
                "program each chunk's real rows (ROADMAP B5)")
        if not chunk or chunk >= T:
            x0 = self._prefill_embed(jnp.asarray(ids_np), bundle, t0=t0)
            out, caches = prefill_jit(x0, caches, jnp.int32(t0))
            return head_fn(out[:, -1:])[:, -1], caches
        pad = (-T) % chunk
        cache_rows = jax.tree_util.tree_leaves(caches)[0].shape[2]
        if t0 + T + pad > cache_rows:
            raise ValueError(
                f"chunked prefill writes rows up to {t0 + T + pad} "
                f"(prompt {T} at offset {t0} padded to a multiple of "
                f"{chunk}) but max_cache_len is {cache_rows} — raise "
                f"max_cache_len by at least {chunk - 1} for chunk "
                f"headroom")
        ids_pad = np.pad(ids_np, ((0, 0), (0, pad)))
        last = None
        for i in range(0, T + pad, chunk):
            x = self._prefill_embed(jnp.asarray(ids_pad[:, i:i + chunk]),
                                    bundle, t0=t0 + i)
            out, caches = prefill_jit(x, caches, jnp.int32(t0 + i))
            if i <= T - 1 < i + chunk:
                last = head_fn(out[:, T - 1 - i:T - i])[:, -1]
        return last, caches

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 seed=None, max_cache_len=None, weight_dtype=None,
                 prefill_chunk=None, mesh=None, cache_dtype=None,
                 num_beams=1, fsm=None):
        """Generate continuations for ``input_ids`` ([B, T] int). Returns
        the FULL sequence (prompt + ``max_new_tokens``) as a framework
        tensor; after every row hits ``eos_token_id`` the tail is padded
        with eos (static shapes — XLA cannot break early).

        Greedy when ``do_sample=False``; otherwise categorical sampling
        with ``temperature``/``top_k``/``top_p`` filtering and a PRNG
        seeded by ``seed`` (``seed=None`` draws a fresh seed from numpy's
        global RNG, so repeated calls differ). Weight-change caveat: decode functions are
        built from the CURRENT weights and cached per ``max_cache_len``;
        call ``model.reset_generate_cache()`` after loading new weights.

        ``weight_dtype="int8"`` turns on weight-only int8 decode: matmul
        weights are stored int8 with per-channel scales, halving the
        weight bytes streamed per decode step (the serving roofline);
        embeddings, norms, routers and the lm head stay full precision.
        """
        from ..inference.decode_loop import (beam_generate, fsm_generate,
                                             greedy_generate,
                                             sample_generate)
        ids_np = np.asarray(unwrap(input_ids))
        if ids_np.ndim == 1:
            ids_np = ids_np[None]
        ids_np = ids_np.astype(np.int32)
        B, T = ids_np.shape
        pad = (-T) % prefill_chunk if prefill_chunk else 0
        if max_cache_len is None:
            max_cache_len = min(self.cfg.max_seq_len,
                                max(T + max_new_tokens, T + pad))
        if T + max_new_tokens > max_cache_len:
            raise ValueError(
                f"prompt ({T}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_cache_len ({max_cache_len})")
        bundle = self._decode_bundle(max_cache_len, weight_dtype, mesh,
                                     cache_dtype)
        init_caches, embed_fn, step_fn, head_fn, prefill_jit = bundle

        last_logits, caches = self._run_prefill(bundle, ids_np,
                                                chunk=prefill_chunk)

        if fsm is not None:
            if num_beams > 1:
                raise ValueError("constrained decoding composes with "
                                 "greedy/sampling, not beam search")
            mask_tab, next_tab = fsm[0], fsm[1]
            start = fsm[2] if len(fsm) > 2 else 0
            if do_sample and seed is None:   # greedy never draws
                seed = int(np.random.randint(0, 2**31))
            new_ids, _ = fsm_generate(
                embed_fn, step_fn, head_fn, caches, last_logits, T,
                max_new_tokens, mask_tab, next_tab, start_state=start,
                do_sample=do_sample,
                key=jax.random.PRNGKey(seed or 0),
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_token_id=eos_token_id)
        elif num_beams > 1:
            if do_sample:
                raise ValueError("beam search and sampling are mutually "
                                 "exclusive (reference decode semantics)")
            new_ids, _ = beam_generate(
                embed_fn, step_fn, head_fn, caches, last_logits, T,
                max_new_tokens, num_beams, eos_token_id=eos_token_id)
        elif do_sample:
            if seed is None:        # fresh entropy per call, like the
                seed = int(np.random.randint(0, 2**31))  # reference's
            key = jax.random.PRNGKey(seed)               # global RNG
            new_ids, _ = sample_generate(
                embed_fn, step_fn, head_fn, caches, last_logits, T,
                max_new_tokens, key, temperature=temperature,
                top_k=top_k, top_p=top_p, eos_token_id=eos_token_id)
        else:
            first = jnp.argmax(last_logits, -1).astype(jnp.int32)
            new_ids, _ = greedy_generate(
                embed_fn, step_fn, head_fn, caches, first, T,
                max_new_tokens, eos_token_id=eos_token_id)
        full = np.concatenate([ids_np, np.asarray(new_ids)], axis=1)
        return wrap(jnp.asarray(full))

    def reset_generate_cache(self):
        """Drop cached decode programs and the stacked weight tree
        they share (call after loading new weights)."""
        self._pt_decode_cache = None
        self._pt_stacked_weights = None
