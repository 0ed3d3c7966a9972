"""Explicit-schedule SPMD pipeline: 1F1B and interleaved-1F1B.

Reference semantics: pipeline_parallel.py:117 (1F1B warmup/steady/cooldown)
and :461 (interleaved virtual stages), with non-uniform stage segmentation
(pp_layers.py SegmentLayers) and embedding/head stages.

TPU-native design (vs the reference's per-rank NCCL loops):

- The schedule is a STATIC tick table (pp_schedules.build_schedule) — an
  event-simulated 1F1B chart. One shard_map + lax.scan executes it in
  lockstep over the "pp" mesh axis; every tick runs two collective
  permutes (activations to the next stage, gradients to the previous) —
  those ride ICI neighbours exactly like the reference's p2p rings.
- Backward uses input-level rematerialization: a stage saves only its
  INPUT activation per in-flight microbatch (ring buffer sized by the
  schedule's true high-water mark) and recomputes its forward inside
  jax.vjp at the backward tick. Peak activation memory is therefore
  O(in-flight × microbatch hidden) — the 1F1B memory bound, stricter
  than storing full per-stage residuals.
- Stages need NOT be uniform: the transformer blocks are segmented by
  param weight into v*S virtual stages with different block counts
  (padded block stacks + per-stage counts); the embedding lives in
  virtual stage 0 and the head/loss in virtual stage v*S-1, so real LM
  shapes (embed → blocks → head) run inside the pipeline like the
  reference's first/last stages.

Embed/head parameters are replicated over "pp" (their grads psum over the
axis); block stacks are sharded [v, S, C, ...] on axis 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from .mesh import HybridMesh, P
from .pp_schedules import (Schedule, build_schedule, FwdSchedule,
                           build_forward_schedule)

__all__ = ["segment_counts", "one_f_one_b_forward_backward",
           "build_1f1b_train_step", "pp_forward", "build_pp_forward_step"]


def segment_counts(num_blocks, num_virtual_stages, weights=None):
    """Split num_blocks into num_virtual_stages contiguous segments.

    weights: per-block cost (param counts); None = uniform. Returns
    (counts [VS], starts [VS]).
    """
    if weights is None:
        weights = [1] * num_blocks
    VS = num_virtual_stages
    total = float(sum(weights))
    per = total / VS
    counts, acc, n = [], 0.0, 0
    for w in weights:
        acc += w
        n += 1
        if acc >= per and len(counts) < VS - 1:
            counts.append(n)
            acc = 0.0
            n = 0
    counts.append(n)
    while len(counts) < VS:
        counts.append(0)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    return np.asarray(counts, np.int32), starts


def _stack_blocks(block_params_list, VS, counts, starts):
    """blocks: list of per-block param dicts (identical structure) ->
    padded stack dict name -> [VS, C, ...]. ShapeDtypeStruct leaves stay
    abstract (AOT compile checks at full model size)."""
    C = int(max(int(c) for c in counts)) or 1
    names = list(block_params_list[0]) if block_params_list else []
    out = {}
    for nme in names:
        proto = block_params_list[0][nme]
        if isinstance(proto, jax.ShapeDtypeStruct):
            out[nme] = jax.ShapeDtypeStruct(
                (VS, C) + tuple(proto.shape), proto.dtype)
            continue
        stack = np.zeros((VS, C) + tuple(proto.shape), proto.dtype)
        for vs in range(VS):
            for j in range(int(counts[vs])):
                stack[vs, j] = np.asarray(
                    block_params_list[int(starts[vs]) + j][nme])
        out[nme] = jnp.asarray(stack)
    return out, C


def _remat_wrap(block_fn, remat_block):
    """remat_block: False (save everything), True (full remat — the 1F1B
    memory bound), or "dots" (jax.checkpoint_policies: save MXU matmul
    outputs, recompute the cheap elementwise tail — trades a little HBM
    for skipping the recompute of the FLOP-heavy ops)."""
    if not remat_block:
        return block_fn
    if remat_block == "dots":
        return jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(block_fn)


def one_f_one_b_forward_backward(
        sched: Schedule, block_fn, embed_fn, head_loss_fn,
        blocks_local, embed_params, head_params, counts_vs,
        ids_micro, labels_micro, hidden_shape, remat_block=True,
        uniform_collectives=False, ct_scale=None):
    """Run the 1F1B schedule. MUST be called inside shard_map with axis
    "pp" of size sched.S.

    block_fn(one_block_params, x) -> x           (shape-preserving)
    embed_fn(embed_params, ids [mb,s]) -> [mb,s,h]
    head_loss_fn(head_params, hidden, labels) -> scalar (mean loss)
    blocks_local: dict name -> [v, C, ...] THIS device's chunk stacks
    counts_vs: int32 [v] block counts for this device's virtual stages
    ids_micro: [M, mb, s] int32; labels_micro: [M, mb, s]
    hidden_shape: (mb, s, h) static
    Returns (loss_mean, d_blocks_local, d_embed, d_head) — loss/d_embed/
    d_head are psum-replicated over pp; d_blocks_local stays per-device.

    ``uniform_collectives=True``: every rank executes embed and the full
    block stack (forward AND backward) every tick, selecting the role's
    result via ``where`` — grads to unselected branches vanish through
    the select. Required when block_fn contains collectives that must
    run in lockstep across pipeline roles — concretely RING ATTENTION
    over an "sp" axis: under the default role `cond`s, ranks in
    different roles would execute different numbers of sp ppermutes per
    tick and deadlock. The head vjp stays role-gated (its mp-only
    collective groups never cross pp coordinates, so the cond predicate
    is uniform within them — same argument as the default path). Cost:
    embed every tick (cheap) + idle-role block compute (bounded by the
    padded chunk size C, which the default path pays inside fori_loop
    anyway).
    """
    S, M, v = sched.S, sched.M, sched.v
    VS = S * v
    i_dev = jax.lax.axis_index("pp")
    mb, s, h = hidden_shape
    dt = jax.tree_util.tree_leaves(blocks_local)[0].dtype

    bf = _remat_wrap(block_fn, remat_block)

    def apply_blocks(chunk_params, x, n):
        C = jax.tree_util.tree_leaves(chunk_params)[0].shape[0]

        if uniform_collectives:
            def body(j, xx):
                blk = jax.tree_util.tree_map(lambda a: a[j], chunk_params)
                out = bf(blk, xx)
                return jnp.where(j < n, out, xx)
        else:
            def body(j, xx):
                blk = jax.tree_util.tree_map(lambda a: a[j], chunk_params)
                return jax.lax.cond(j < n, lambda q: bf(blk, q),
                                    lambda q: q, xx)

        return jax.lax.fori_loop(0, C, body, x)

    def chunk_of(c):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, False),
            blocks_local)

    perm_up = [(i, (i + 1) % S) for i in range(S)]
    perm_dn = [(i, (i - 1) % S) for i in range(S)]

    zero_hidden = jnp.zeros((mb, s, h), dt)

    tables = dict(
        f_vs=sched.f_vs, f_mb=sched.f_mb, f_read=sched.f_read,
        f_save=sched.f_save, b_vs=sched.b_vs, b_mb=sched.b_mb,
        b_gread=sched.b_gread, b_xread=sched.b_xread,
        recv_a=sched.recv_a, recv_g=sched.recv_g)
    tables = {k: jnp.asarray(val) for k, val in tables.items()}

    def tick(carry, row):
        (a_buf, g_buf, x_buf, d_blk, d_emb, d_head, loss_sum) = carry
        g = lambda key: row[key][i_dev]
        f_vs, f_mb_ = g("f_vs"), g("f_mb")
        b_vs, b_mb_ = g("b_vs"), g("b_mb")

        # ---------------- forward op
        do_f = f_vs >= 0
        chunk_f = jnp.maximum(f_vs, 0) // S
        n_f = counts_vs[chunk_f]
        ids_f = jax.lax.dynamic_index_in_dim(
            ids_micro, jnp.maximum(f_mb_, 0), 0, False)
        x_in = jax.lax.dynamic_index_in_dim(
            a_buf, jnp.maximum(g("f_read"), 0), 0, False)

        def role_f_first(_):
            hdn = embed_fn(embed_params, ids_f).astype(dt)
            return apply_blocks(chunk_of(chunk_f), hdn, n_f)

        def role_f_mid(_):
            return apply_blocks(chunk_of(chunk_f), x_in, n_f)

        def role_f_last(_):
            return zero_hidden  # last vstage sends nothing; bwd recomputes

        case_f = jnp.where(f_vs == 0, 0, jnp.where(f_vs == VS - 1, 2, 1))
        if uniform_collectives:
            # every rank runs embed + blocks every tick; result selected
            hdn_f = embed_fn(embed_params, ids_f).astype(dt)
            x0f = jnp.where(case_f == 0, hdn_f, x_in)
            y_all = apply_blocks(chunk_of(chunk_f), x0f, n_f)
            y = jnp.where(do_f & (case_f != 2), y_all, zero_hidden)
        else:
            y = jax.lax.cond(
                do_f,
                lambda _: jax.lax.switch(case_f, [role_f_first,
                                                  role_f_mid,
                                                  role_f_last], None),
                lambda _: zero_hidden, None)
        # save this fwd's input for the bwd recompute (vs > 0 only)
        slot_s = g("f_save")
        x_buf = jnp.where(
            slot_s >= 0,
            jax.lax.dynamic_update_index_in_dim(
                x_buf, x_in, jnp.maximum(slot_s, 0), 0),
            x_buf)

        # ---------------- backward op (recompute + vjp)
        do_b = b_vs >= 0
        chunk_b = jnp.maximum(b_vs, 0) // S
        n_b = counts_vs[chunk_b]
        ids_b = jax.lax.dynamic_index_in_dim(
            ids_micro, jnp.maximum(b_mb_, 0), 0, False)
        lbl_b = jax.lax.dynamic_index_in_dim(
            labels_micro, jnp.maximum(b_mb_, 0), 0, False)
        g_in = jax.lax.dynamic_index_in_dim(
            g_buf, jnp.maximum(g("b_gread"), 0), 0, False)
        x_sv = jax.lax.dynamic_index_in_dim(
            x_buf, jnp.maximum(g("b_xread"), 0), 0, False)
        ck_b = chunk_of(chunk_b)
        zero_ck = jax.tree_util.tree_map(
            lambda a: jnp.zeros_like(a, jnp.float32), ck_b)
        zero_emb = jax.tree_util.tree_map(
            lambda a: jnp.zeros_like(a, jnp.float32), embed_params)
        zero_hd = jax.tree_util.tree_map(
            lambda a: jnp.zeros_like(a, jnp.float32), head_params)

        def role_b_first(_):
            def f(ck, ep):
                hdn = embed_fn(ep, ids_b).astype(dt)
                return apply_blocks(ck, hdn, n_b)

            _, vjp = jax.vjp(f, ck_b, embed_params)
            dck, dep = vjp(g_in)
            f32 = lambda t: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), t)
            return f32(dck), f32(dep), zero_hd, zero_hidden, jnp.float32(0)

        def role_b_mid(_):
            def f(ck, xx):
                return apply_blocks(ck, xx, n_b)

            _, vjp = jax.vjp(f, ck_b, x_sv)
            dck, dx = vjp(g_in)
            f32 = lambda t: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), t)
            return (f32(dck), zero_emb, zero_hd, dx.astype(dt),
                    jnp.float32(0))

        def role_b_last(_):
            def f(ck, hp, xx):
                hdn = apply_blocks(ck, xx, n_b)
                return head_loss_fn(hp, hdn, lbl_b) / M

            lv, vjp = jax.vjp(f, ck_b, head_params, x_sv)
            seed = (jnp.ones_like(lv) if ct_scale is None
                    else jnp.full_like(lv, ct_scale))
            dck, dhp, dx = vjp(seed)
            f32 = lambda t: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), t)
            return (f32(dck), zero_emb, f32(dhp), dx.astype(dt),
                    lv.astype(jnp.float32) * M)

        case_b = jnp.where(b_vs == 0, 0, jnp.where(b_vs == VS - 1, 2, 1))
        if uniform_collectives:
            # Uniform BLOCK vjp (the sp rings live in block_fn, so its
            # forward+backward must run identically on every rank every
            # tick); the HEAD vjp — the model's largest matmul, with only
            # mp collectives whose groups never cross pp coordinates —
            # stays role-gated under a cond, exactly like the default
            # path. `where` routes embed vs saved-input; grads to the
            # unselected branch are hard zeros through the select.
            is_first_b = case_b == 0
            is_last_b = case_b == 2

            def f_blocks(ck, ep, xx):
                x0b = jnp.where(is_first_b,
                                embed_fn(ep, ids_b).astype(dt), xx)
                return apply_blocks(ck, x0b, n_b)

            hdn_b, vjp_blocks = jax.vjp(f_blocks, ck_b, embed_params,
                                        x_sv)

            def head_branch(_):
                lv, vjp_h = jax.vjp(
                    lambda hp, hd: head_loss_fn(hp, hd, lbl_b) / M,
                    head_params, hdn_b)
                seed = (jnp.ones_like(lv) if ct_scale is None
                        else jnp.full_like(lv, ct_scale))
                dhp_, ct_ = vjp_h(seed)
                f32_ = lambda t: jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32), t)
                return (f32_(dhp_), ct_.astype(dt),
                        lv.astype(jnp.float32))

            def nohead_branch(_):
                return zero_hd, g_in, jnp.float32(0)

            dhp, ct_h, head_val = jax.lax.cond(
                is_last_b, head_branch, nohead_branch, None)
            dck, dep, dx = vjp_blocks(ct_h)
            f32 = lambda t: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), t)
            gate = lambda t: jax.tree_util.tree_map(
                lambda a: jnp.where(do_b, a, jnp.zeros_like(a)), t)
            dck = gate(f32(dck))
            dep = gate(f32(dep))
            dhp = gate(dhp)
            dx = jnp.where(do_b & ~is_first_b, dx.astype(dt), zero_hidden)
            lval = jnp.where(do_b & is_last_b,
                             head_val * M, jnp.float32(0))
        else:
            dck, dep, dhp, dx, lval = jax.lax.cond(
                do_b,
                lambda _: jax.lax.switch(case_b, [role_b_first, role_b_mid,
                                                  role_b_last], None),
                lambda _: (zero_ck, zero_emb, zero_hd, zero_hidden,
                           jnp.float32(0)),
                None)

        # accumulate grads (scatter-add this chunk's block grads)
        d_blk = jax.tree_util.tree_map(
            lambda acc, dv: acc.at[chunk_b].add(
                jnp.where(do_b, dv, jnp.zeros_like(dv))), d_blk, dck)
        d_emb = jax.tree_util.tree_map(lambda a, b: a + b, d_emb, dep)
        d_head = jax.tree_util.tree_map(lambda a, b: a + b, d_head, dhp)
        loss_sum = loss_sum + lval / M

        # ---------------- communicate (unconditional collectives)
        a_arr = jax.lax.ppermute(y, "pp", perm_up)
        g_arr = jax.lax.ppermute(dx, "pp", perm_dn)
        ra, rg = g("recv_a"), g("recv_g")
        a_buf = jnp.where(
            ra >= 0,
            jax.lax.dynamic_update_index_in_dim(
                a_buf, a_arr, jnp.maximum(ra, 0), 0), a_buf)
        g_buf = jnp.where(
            rg >= 0,
            jax.lax.dynamic_update_index_in_dim(
                g_buf, g_arr, jnp.maximum(rg, 0), 0), g_buf)

        return (a_buf, g_buf, x_buf, d_blk, d_emb, d_head, loss_sum), None

    a0 = jnp.zeros((sched.n_aslots, mb, s, h), dt)
    g0 = jnp.zeros((sched.n_gslots, mb, s, h), dt)
    x0 = jnp.zeros((sched.n_xslots, mb, s, h), dt)
    db0 = jax.tree_util.tree_map(
        lambda a: jnp.zeros_like(a, jnp.float32), blocks_local)
    de0 = jax.tree_util.tree_map(
        lambda a: jnp.zeros_like(a, jnp.float32), embed_params)
    dh0 = jax.tree_util.tree_map(
        lambda a: jnp.zeros_like(a, jnp.float32), head_params)

    (a_buf, g_buf, x_buf, d_blk, d_emb, d_head, loss_sum), _ = \
        jax.lax.scan(tick, (a0, g0, x0, db0, de0, dh0, jnp.float32(0)),
                     tables)

    loss = jax.lax.psum(loss_sum, "pp")
    d_emb = jax.lax.psum(d_emb, "pp")
    d_head = jax.lax.psum(d_head, "pp")
    return loss, d_blk, d_emb, d_head


def pp_forward(sched: FwdSchedule, block_fn, embed_fn, head_fn,
               blocks_local, embed_params, head_params, counts_vs,
               ids_micro, labels_micro, hidden_shape,
               uniform_collectives=False):
    """Forward-only pipeline pass (Engine.evaluate/predict under pp —
    reference PipelineParallel.eval_batch, pipeline_parallel.py:357).
    MUST be called inside shard_map with axis "pp" of size sched.S.

    head_fn(head_params, hidden, labels_mb) -> per-microbatch output:
    a scalar loss for evaluate, [mb, s', V] logits for predict — any
    pytree of arrays. Returns the [M, ...]-stacked outputs,
    psum-replicated over "pp" (only the device hosting the last virtual
    stage computes them; everyone else contributes zeros).

    ``uniform_collectives`` has the same contract as the train executor:
    block_fn collectives (sp rings) run on every rank every tick with
    where-selected results; the head stays cond-gated (mp-only groups
    never cross pp coordinates).
    """
    S, M, v = sched.S, sched.M, sched.v
    VS = S * v
    i_dev = jax.lax.axis_index("pp")
    mb, s, h = hidden_shape
    dt = jax.tree_util.tree_leaves(blocks_local)[0].dtype

    def apply_blocks(chunk_params, x, n):
        C = jax.tree_util.tree_leaves(chunk_params)[0].shape[0]

        if uniform_collectives:
            def body(j, xx):
                blk = jax.tree_util.tree_map(lambda a: a[j], chunk_params)
                out = block_fn(blk, xx)
                return jnp.where(j < n, out, xx)
        else:
            def body(j, xx):
                blk = jax.tree_util.tree_map(lambda a: a[j], chunk_params)
                return jax.lax.cond(j < n, lambda q: block_fn(blk, q),
                                    lambda q: q, xx)

        return jax.lax.fori_loop(0, C, body, x)

    def chunk_of(c):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, False),
            blocks_local)

    perm_up = [(i, (i + 1) % S) for i in range(S)]
    zero_hidden = jnp.zeros((mb, s, h), dt)

    out_aval = jax.eval_shape(
        lambda hp, lb: head_fn(hp, zero_hidden, lb),
        head_params, jax.tree_util.tree_map(lambda a: a[0], labels_micro))
    zero_out = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), out_aval)

    tables = {k: jnp.asarray(getattr(sched, k))
              for k in ("f_vs", "f_mb", "f_read", "recv_a")}

    def tick(carry, row):
        a_buf, out_buf = carry
        g = lambda key: row[key][i_dev]
        f_vs, f_mb_ = g("f_vs"), g("f_mb")
        do_f = f_vs >= 0
        chunk_f = jnp.maximum(f_vs, 0) // S
        n_f = counts_vs[chunk_f]
        ids_f = jax.lax.dynamic_index_in_dim(
            ids_micro, jnp.maximum(f_mb_, 0), 0, False)
        lbl_f = jax.lax.dynamic_index_in_dim(
            labels_micro, jnp.maximum(f_mb_, 0), 0, False)
        x_in = jax.lax.dynamic_index_in_dim(
            a_buf, jnp.maximum(g("f_read"), 0), 0, False)
        is_first = f_vs == 0
        is_last = f_vs == VS - 1

        if uniform_collectives:
            hdn = embed_fn(embed_params, ids_f).astype(dt)
            x0 = jnp.where(is_first, hdn, x_in)
            y_all = apply_blocks(chunk_of(chunk_f), x0, n_f)
        else:
            def run(_):
                x0 = jax.lax.cond(
                    is_first,
                    lambda _: embed_fn(embed_params, ids_f).astype(dt),
                    lambda _: x_in, None)
                return apply_blocks(chunk_of(chunk_f), x0, n_f)

            y_all = jax.lax.cond(do_f, run, lambda _: zero_hidden, None)

        out_mb = jax.lax.cond(
            do_f & is_last,
            lambda _: head_fn(head_params, y_all, lbl_f),
            lambda _: zero_out, None)
        out_buf = jax.tree_util.tree_map(
            lambda buf, o: jnp.where(
                do_f & is_last,
                jax.lax.dynamic_update_index_in_dim(
                    buf, o, jnp.maximum(f_mb_, 0), 0), buf),
            out_buf, out_mb)

        # ---------------- communicate (unconditional collective)
        y = jnp.where(do_f & ~is_last, y_all, zero_hidden)
        a_arr = jax.lax.ppermute(y, "pp", perm_up)
        ra = g("recv_a")
        a_buf = jnp.where(
            ra >= 0,
            jax.lax.dynamic_update_index_in_dim(
                a_buf, a_arr, jnp.maximum(ra, 0), 0), a_buf)
        return (a_buf, out_buf), None

    a0 = jnp.zeros((sched.n_aslots, mb, s, h), dt)
    out0 = jax.tree_util.tree_map(
        lambda a: jnp.zeros((M,) + a.shape, a.dtype), out_aval)
    (_a, out_buf), _ = jax.lax.scan(tick, (a0, out0), tables)
    return jax.tree_util.tree_map(
        lambda a: jax.lax.psum(a, "pp"), out_buf)


def build_pp_forward_step(block_fn, embed_fn, head_fn,
                          block_params_list, embed_params, head_params,
                          mesh: HybridMesh, num_micro, interleave=1,
                          block_weights=None, block_param_specs=None,
                          embed_param_specs=None, head_param_specs=None,
                          batch_axes=("dp",), tie_embed_head=False,
                          seq_axis=None, uniform_collectives=None,
                          out_batch_dims=None):
    """Assemble the sharded forward-only pipeline function
    (Engine.evaluate/predict under strategy.pipeline — reference
    engine.py:1328 evaluate/predict run every strategy).

    Returns (fwd_fn, (stacked, embed, head, sched)) where
      fwd_fn(blocks, embed, head, ids [B,s], labels [B,s]) ->
          [M, ...]-stacked head_fn outputs (psum-replicated over pp).
    The param trees use the SAME stacking and sharding layout as
    build_1f1b_train_step, so params produced by the train builder (or
    build_hybrid_train_step) feed straight in.

    ``out_batch_dims``: dims of head_fn's output that carry the
    microbatch/sequence (after the stacked M axis) — e.g. (0, 1) for
    [mb, s', V] logits. They shard over batch_axes/seq_axis in the
    assembled global output; scalar outputs (losses) replicate.
    """
    st = _prepare_pp_state(
        block_fn, embed_fn, head_fn, block_params_list,
        embed_params, head_params, mesh, num_micro, interleave,
        block_weights, block_param_specs, embed_param_specs,
        head_param_specs, batch_axes, tie_embed_head, seq_axis,
        uniform_collectives, forward_only=True)
    S, counts_dev, sched = st["S"], st["counts_dev"], st["sched"]
    stacked, blocks_spec = st["stacked"], st["blocks_spec"]
    embed_params, embed_spec = st["embed_params"], st["embed_spec"]
    head_params, head_spec = st["head_params"], st["head_spec"]
    uniform, mean_axes, bspec = st["uniform"], st["mean_axes"], st["bspec"]
    tie = tie_embed_head

    if out_batch_dims:
        tail = [None] * (1 + max(out_batch_dims))
        tail[out_batch_dims[0]] = tuple(batch_axes)
        if len(out_batch_dims) > 1 and seq_axis:
            tail[out_batch_dims[1]] = seq_axis
        out_spec = P(None, *tail)
    else:
        out_spec = P()

    def sharded_body(blocks, embed, head, ids_micro, labels_micro):
        blocks_local = jax.tree_util.tree_map(lambda a: a[:, 0], blocks)
        i_dev = jax.lax.axis_index("pp")
        counts_vs = counts_dev[:, i_dev]
        mb = ids_micro.shape[1]
        s = ids_micro.shape[2]
        if tie:
            table_full = jax.lax.all_gather(
                embed["table"], "pp", axis=0, tiled=True)
            embed_in = dict(embed, table=table_full)
            head_in = dict(head, table=table_full)
        else:
            embed_in, head_in = embed, head
        h = jax.eval_shape(lambda e: embed_fn(e, ids_micro[0]),
                           embed_in).shape[-1]
        out = pp_forward(
            sched, block_fn, embed_fn, head_fn, blocks_local, embed_in,
            head_in, counts_vs, ids_micro, labels_micro, (mb, s, h),
            uniform_collectives=uniform)
        if mean_axes and not out_batch_dims:
            # scalar (loss) outputs average over data replicas; sharded
            # outputs reassemble through out_specs instead
            out = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, mean_axes), out)
        return out

    in_specs = (blocks_spec, embed_spec, head_spec, bspec, bspec)

    smapped = jax.shard_map(
        sharded_body, mesh=mesh.mesh, in_specs=in_specs,
        out_specs=out_spec, check_vma=False)

    def fwd_fn(blocks, embed, head, ids, labels):
        B, seq = ids.shape[0], ids.shape[-1]
        data_ways = int(np.prod([mesh.degree(a) for a in batch_axes]))
        if B % (num_micro * data_ways):
            raise ValueError(
                f"batch {B} must divide by num_micro*|{batch_axes}| = "
                f"{num_micro}*{data_ways}")
        if seq_axis and seq % mesh.degree(seq_axis):
            raise ValueError(
                f"sequence {seq} must divide by the {seq_axis} degree "
                f"{mesh.degree(seq_axis)}")
        mb = B // num_micro
        ids_micro = ids.reshape(num_micro, mb, -1)
        labels_micro = labels.reshape(num_micro, mb, -1)
        return smapped(blocks, embed, head, ids_micro, labels_micro)

    return fwd_fn, (stacked, embed_params, head_params, sched)


def make_tied_lm_fns():
    """(embed_fn, head_loss_fn) for ``tie_embed_head=True`` on meshes
    with mp degree 1: both receive the pp-gathered FULL embedding table
    and the head is embedᵀ (reference SharedLayerDesc weight tying,
    pp_layers.py:430-517). On mp>1 meshes the gathered table is only
    this mp rank's [V/mp, h] vocab-parallel slice — use the mp-aware
    ``parallel.hybrid.make_tied_tp_lm_fns`` instead (the builder
    enforces this)."""
    def embed_fn(p, ids):
        return p["table"][ids]

    def head_loss_fn(p, hidden, labels):
        lg = (hidden @ p["table"].T).astype(jnp.float32)
        logp = jax.nn.log_softmax(lg, -1)
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()

    return embed_fn, head_loss_fn


def _prepare_pp_state(block_fn, embed_fn, head_loss_fn,
                      block_params_list, embed_params, head_params,
                      mesh, num_micro, interleave, block_weights,
                      block_param_specs, embed_param_specs,
                      head_param_specs, batch_axes, tie_embed_head,
                      seq_axis, uniform_collectives, forward_only=False):
    """Shared state prep for the train and forward-only pp builders:
    segment + stack the blocks, device_put with pp (and tied) specs,
    validate mp/sp fn contracts, build the tick schedule."""
    S = mesh.degree("pp")
    v = interleave
    VS = S * v
    L = len(block_params_list)
    counts, starts = segment_counts(L, VS, block_weights)
    stacked_flat, C = _stack_blocks(block_params_list, VS, counts, starts)
    # [VS, C, ...] -> [v, S, C, ...]: device i holds chunks {c*S+i}
    stacked = {n: (jax.ShapeDtypeStruct((v, S, C) + a.shape[2:], a.dtype)
                   if isinstance(a, jax.ShapeDtypeStruct)
                   else a.reshape((v, S, C) + a.shape[2:]))
               for n, a in stacked_flat.items()}
    counts_dev = jnp.asarray(counts.reshape(v, S))     # [v, S]
    sched = (build_forward_schedule(S, num_micro, v) if forward_only
             else build_schedule(S, num_micro, v))

    def _stacked_spec(name):
        raw = (block_param_specs or {}).get(name)
        tail = tuple(raw) if raw is not None else ()
        return P(None, "pp", None, *tail)

    blocks_spec = {n: _stacked_spec(n) for n in stacked}
    abstract = any(isinstance(a, jax.ShapeDtypeStruct)
                   for a in stacked.values())
    if not abstract:
        stacked = {n: jax.device_put(a, NamedSharding(mesh.mesh,
                                                      blocks_spec[n]))
                   for n, a in stacked.items()}
    else:
        stacked = {n: jax.ShapeDtypeStruct(
                       a.shape, a.dtype,
                       sharding=NamedSharding(mesh.mesh, blocks_spec[n]))
                   for n, a in stacked.items()}
    if tie_embed_head:
        assert "table" not in head_params, \
            "tie_embed_head: the head reuses embed's table; extra " \
            "replicated head params (final LN, ...) are fine"
        assert "table" in embed_params, \
            "tie_embed_head expects embed_params['table'] = [V, h]"
        vocab = embed_params["table"].shape[0]
        mp_deg = mesh.degree("mp")
        assert vocab % (S * mp_deg) == 0, (vocab, S, mp_deg)
        if mp_deg > 1 and not (getattr(embed_fn, "_mp_aware", False) and
                               getattr(head_loss_fn, "_mp_aware", False)):
            raise ValueError(
                "tie_embed_head on an mp>1 mesh: the pp-gathered table "
                "is this mp rank's [V/mp, h] vocab-parallel slice, not "
                "the full table, so embed/head fns must be built for "
                "vocab-parallel lookup (marked _mp_aware) — use "
                "parallel.hybrid.make_tied_tp_lm_fns, not a plain "
                "full-table decompose")
        # mp-MAJOR row sharding: gathering over "pp" then yields each mp
        # rank its CONTIGUOUS vocab-parallel slice [V/mp, h] — tied TP
        # embedding/head compose for free (mp=1 degenerates to pp-only).
        # Non-table params (positional embeddings, final LN, ...) stay
        # replicated alongside.
        tied_spec = P(("mp", "pp"), None)
        embed_spec = {n: (tied_spec if n == "table"
                          else (embed_param_specs or {}).get(n, P()))
                      for n in embed_params}
        head_spec = {n: (head_param_specs or {}).get(n, P())
                     for n in head_params}
        t = embed_params["table"]
        if isinstance(t, jax.ShapeDtypeStruct):
            embed_params = dict(embed_params, table=jax.ShapeDtypeStruct(
                t.shape, t.dtype,
                sharding=NamedSharding(mesh.mesh, tied_spec)))
        else:
            embed_params = dict(embed_params, table=jax.device_put(
                jnp.asarray(t), NamedSharding(mesh.mesh, tied_spec)))
    else:
        embed_spec = {n: (embed_param_specs or {}).get(n, P())
                      for n in embed_params}
        head_spec = {n: (head_param_specs or {}).get(n, P())
                     for n in head_params}

    # ring attention's per-block sp collectives must execute uniformly
    # across pipeline roles — auto-enable the uniform tick under seq_axis
    uniform = (uniform_collectives if uniform_collectives is not None
               else seq_axis is not None)
    # seq_axis and the block fns' sp wiring MUST agree: sequence-sharded
    # inputs into non-ring attention would silently train a wrong model
    fn_sp = getattr(block_fn, "_sp_axis", "unknown")
    if fn_sp != "unknown" and fn_sp != seq_axis:
        raise ValueError(
            f"seq_axis={seq_axis!r} but the block fns were built with "
            f"sp_axis={fn_sp!r} (make_llama_tp_fns/make_moe_tp_fns "
            "sp_axis must match the builder's seq_axis)")
    data_axes = tuple(batch_axes) + ((seq_axis,) if seq_axis else ())
    mean_axes = tuple(ax for ax in data_axes if mesh.degree(ax) > 1)
    # batch over the batch axes; with seq_axis, the SEQUENCE dim shards
    # over it too (context parallel — block fns must run ring attention)
    bspec = P(None, tuple(batch_axes), seq_axis)
    return dict(S=S, v=v, VS=VS, counts_dev=counts_dev, sched=sched,
                stacked=stacked, blocks_spec=blocks_spec,
                embed_params=embed_params, embed_spec=embed_spec,
                head_params=head_params, head_spec=head_spec,
                uniform=uniform, mean_axes=mean_axes, bspec=bspec)


def build_1f1b_train_step(block_fn, embed_fn, head_loss_fn,
                          block_params_list, embed_params, head_params,
                          mesh: HybridMesh, num_micro, interleave=1,
                          block_weights=None, remat_block=True,
                          block_param_specs=None, embed_param_specs=None,
                          head_param_specs=None, batch_axes=("dp",),
                          tie_embed_head=False, seq_axis=None,
                          uniform_collectives=None):
    """Assemble the sharded 1F1B loss-and-grad function.

    Returns (grad_fn, state) where
      state = (blocks_stacked [v,S,C,...] pp-sharded, embed, head, sched)
      grad_fn(blocks, embed, head, ids [B,s], labels [B,s]) ->
          (loss, (d_blocks, d_embed, d_head))
    Batch B is sharded over ``batch_axes`` (default "dp"); microbatching
    is over the leading axis.

    TP composition (the reference's mp×pp hybrid,
    fleet/base/topology.py:251): ``block_param_specs[name]`` gives a
    PartitionSpec over the RAW per-block param dims (e.g. P(None, "mp")
    for a column-parallel weight); the stage stacking prepends
    (None, "pp", None). ``embed_param_specs``/``head_param_specs``
    likewise shard the embedding/head over "mp". When any of these are
    set, block_fn/embed_fn/head_loss_fn must be mp-aware (psum over "mp"
    at row-parallel boundaries) — see parallel.hybrid for ready-made fns.

    ``tie_embed_head=True`` (reference SharedLayerDesc,
    meta_parallel/parallel_layers/pp_layers.py:430-517): the head IS the
    embeddingᵀ and ``head_params`` must be ``{}``. TPU-native storage:
    the table lives SHARDED over ("mp","pp") rows (params, grads and
    optimizer state), is all_gathered over "pp" ONCE per step outside
    the tick scan (collectives must be tick-uniform), and embed_fn /
    head_loss_fn receive the gathered table: the FULL [V, h] on mp=1
    meshes (use ``make_tied_lm_fns``) or this mp rank's contiguous
    vocab-parallel [V/mp, h] slice on mp>1 (use the mp-aware
    ``parallel.hybrid.make_tied_tp_lm_fns``; enforced). Grads for both
    uses flow into one psum over pp and are sliced back to the local
    shard — beating the reference, which replicates a full fp32 grad
    accumulator for the shared weight on every stage.
    """
    st = _prepare_pp_state(
        block_fn, embed_fn, head_loss_fn, block_params_list,
        embed_params, head_params, mesh, num_micro, interleave,
        block_weights, block_param_specs, embed_param_specs,
        head_param_specs, batch_axes, tie_embed_head, seq_axis,
        uniform_collectives)
    S, counts_dev, sched = st["S"], st["counts_dev"], st["sched"]
    stacked, blocks_spec = st["stacked"], st["blocks_spec"]
    embed_params, embed_spec = st["embed_params"], st["embed_spec"]
    head_params, head_spec = st["head_params"], st["head_spec"]
    uniform, mean_axes, bspec = st["uniform"], st["mean_axes"], st["bspec"]

    def sharded_body(blocks, embed, head, ids_micro, labels_micro,
                     ct_scale):
        # local blocks: [v, 1, C, ...] -> [v, C, ...]
        blocks_local = jax.tree_util.tree_map(lambda a: a[:, 0], blocks)
        i_dev = jax.lax.axis_index("pp")
        counts_vs = counts_dev[:, i_dev]
        mb = ids_micro.shape[1]
        s = ids_micro.shape[2]
        if tie_embed_head:
            # gather the pp-sharded table ONCE, outside the tick scan
            # (collectives inside device-varying tick roles would not be
            # uniform); both ends of the model use the gathered copy,
            # plus their own replicated extras
            table_full = jax.lax.all_gather(
                embed["table"], "pp", axis=0, tiled=True)
            embed_in = dict(embed, table=table_full)
            head_in = dict(head, table=table_full)
        else:
            embed_in, head_in = embed, head
        h = jax.eval_shape(lambda e: embed_fn(e, ids_micro[0]),
                           embed_in).shape[-1]
        loss, d_blk, d_emb, d_head = one_f_one_b_forward_backward(
            sched, block_fn, embed_fn, head_loss_fn,
            blocks_local, embed_in, head_in, counts_vs,
            ids_micro, labels_micro, (mb, s, h), remat_block=remat_block,
            uniform_collectives=uniform, ct_scale=ct_scale)
        if tie_embed_head:
            # d_emb/d_head are already psum'd over pp -> global [V, h]
            # sums; tie them and keep only this stage's vocab slice.
            # Extras (positional embeds, final LN) keep their own grads.
            vl = embed["table"].shape[0]
            d_tab = d_emb["table"] + d_head["table"]
            d_emb = dict(d_emb, table=jax.lax.dynamic_slice_in_dim(
                d_tab, i_dev * vl, vl, 0))
            d_head = {n: g_ for n, g_ in d_head.items() if n != "table"}
        # average over data replicas (dp and, in ZeRO hybrids, "sharding")
        if mean_axes:
            loss = jax.lax.pmean(loss, mean_axes)
            d_blk = jax.lax.pmean(d_blk, mean_axes)
            d_emb = jax.lax.pmean(d_emb, mean_axes)
            d_head = jax.lax.pmean(d_head, mean_axes)
        d_blk = jax.tree_util.tree_map(lambda a: a[:, None], d_blk)
        return loss, d_blk, d_emb, d_head

    in_specs = (blocks_spec, embed_spec, head_spec, bspec, bspec, P())
    out_specs = (P(), blocks_spec, embed_spec, head_spec)

    smapped = jax.shard_map(
        sharded_body, mesh=mesh.mesh, in_specs=in_specs,
        out_specs=out_specs, check_vma=False)

    def grad_fn(blocks, embed, head, ids, labels, scale=None):
        """``scale``: optional backward seed (loss-scaling for fp16 —
        reference GradScaler): grads come back MULTIPLIED by it; the
        returned loss stays unscaled. None = 1."""
        B, seq = ids.shape[0], ids.shape[-1]
        data_ways = int(np.prod([mesh.degree(a) for a in batch_axes]))
        if B % (num_micro * data_ways):
            raise ValueError(
                f"batch {B} must divide by num_micro*|{batch_axes}| = "
                f"{num_micro}*{data_ways}")
        if seq_axis and seq % mesh.degree(seq_axis):
            raise ValueError(
                f"sequence {seq} must divide by the {seq_axis} degree "
                f"{mesh.degree(seq_axis)}")
        mb = B // num_micro
        ids_micro = ids.reshape(num_micro, mb, -1)
        labels_micro = labels.reshape(num_micro, mb, -1)
        ct = jnp.asarray(1.0 if scale is None else scale, jnp.float32)
        loss, d_blk, d_emb, d_head = smapped(
            blocks, embed, head, ids_micro, labels_micro, ct)
        return loss, (d_blk, d_emb, d_head)

    return grad_fn, (stacked, embed_params, head_params, sched)
