"""Static-graph collective ops: c_* op insertion for Programs.

Reference: paddle/fluid/operators/collective/ (c_allreduce_sum_op.cc,
c_allgather_op.cc, c_broadcast_op.cc, c_concat_op.cc,
c_softmax_with_cross_entropy, partial ops, ...) — ops inserted into a
static ProgramDesc carrying a ring_id, executed by NCCL at run time.

TPU-native design: the recorded op's fn IS the XLA collective
(lax.psum/all_gather/ppermute) keyed by a mesh axis name instead of a
ring id. A Program containing c_* ops replays to a function with
collective primitives; executing it inside ``shard_map`` over the target
mesh (``run_program_sharded`` below, or any user shard_map) lowers them
to ICI collectives — the compiler plays NCCL's role. Executing on one
device without a mesh raises jax's unbound-axis error, mirroring the
reference's "ring not initialized" failure mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import dispatch


def _aval_of(x):
    v = getattr(x, "_value", x)
    return jax.ShapeDtypeStruct(v.shape, v.dtype)


def _nranks(ax):
    from ..parallel.mesh import get_mesh
    from ..utils.enforce import InvalidArgumentError
    m = get_mesh()
    # degree() defaults unknown axes to 1 — require the axis to actually
    # exist in the mesh, else the un-gathered shape would be recorded
    if m is None or ax not in m.degrees:
        raise InvalidArgumentError(
            f"c_* op needs the gather width for axis {ax!r} at build "
            "time: initialize a mesh (paddle_tpu.parallel.init_mesh) "
            "before recording, or pass nranks explicitly",
            hint="a silent nranks=1 would record the un-gathered shape")
    return m.degree(ax)

__all__ = ["c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
           "c_allgather", "c_broadcast", "c_concat", "c_identity",
           "c_softmax_with_cross_entropy", "run_program_sharded"]


def _axis(ring_id, axis_name):
    # ring_id kept for API parity; the mesh axis is the real key
    return axis_name or "mp"


def c_allreduce_sum(x, ring_id=0, axis_name=None, use_calc_stream=True):
    ax = _axis(ring_id, axis_name)
    return dispatch(lambda v: jax.lax.psum(v, ax), x,
                    name="c_allreduce_sum", static_out_aval=_aval_of(x))


def c_allreduce_max(x, ring_id=0, axis_name=None, use_calc_stream=True):
    ax = _axis(ring_id, axis_name)
    return dispatch(lambda v: jax.lax.pmax(v, ax), x,
                    name="c_allreduce_max", static_out_aval=_aval_of(x))


def c_allreduce_min(x, ring_id=0, axis_name=None, use_calc_stream=True):
    ax = _axis(ring_id, axis_name)
    return dispatch(lambda v: jax.lax.pmin(v, ax), x,
                    name="c_allreduce_min", static_out_aval=_aval_of(x))


def c_allgather(x, nranks=None, ring_id=0, axis_name=None):
    ax = _axis(ring_id, axis_name)
    a = _aval_of(x)
    n = nranks or _nranks(ax)
    out = jax.ShapeDtypeStruct((a.shape[0] * n,) + a.shape[1:], a.dtype)
    return dispatch(lambda v: jax.lax.all_gather(v, ax, axis=0,
                                                 tiled=True), x,
                    name="c_allgather", static_out_aval=out)


def c_broadcast(x, root=0, ring_id=0, axis_name=None):
    ax = _axis(ring_id, axis_name)

    def fn(v):
        # select root's value on every member (psum of masked value)
        idx = jax.lax.axis_index(ax)
        contrib = jnp.where(idx == root, v, jnp.zeros_like(v))
        return jax.lax.psum(contrib, ax)

    return dispatch(fn, x, name="c_broadcast",
                    static_out_aval=_aval_of(x))


def c_concat(x, nranks=None, ring_id=0, axis_name=None):
    """Gather along the LAST axis (reference c_concat_op: TP column
    outputs concatenated)."""
    ax = _axis(ring_id, axis_name)
    a = _aval_of(x)
    n = nranks or _nranks(ax)
    out = jax.ShapeDtypeStruct(a.shape[:-1] + (a.shape[-1] * n,), a.dtype)
    return dispatch(lambda v: jax.lax.all_gather(
        v, ax, axis=len(a.shape) - 1, tiled=True), x, name="c_concat",
        static_out_aval=out)


def c_identity(x, ring_id=0, axis_name=None):
    """Forward identity whose grad is an allreduce (reference
    c_identity_op — the TP input marker)."""
    ax = _axis(ring_id, axis_name)

    @jax.custom_vjp
    def ident(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, g):
        return (jax.lax.psum(g, ax),)

    ident.defvjp(fwd, bwd)
    return dispatch(ident, x, name="c_identity",
                    static_out_aval=_aval_of(x))


def c_softmax_with_cross_entropy(logits, label, ring_id=0, axis_name=None,
                                 ignore_index=-100):
    """Vocab-sharded softmax CE (reference
    c_softmax_with_cross_entropy_op.cu): each rank holds a vocab slice;
    max/denominator reduce over the axis."""
    ax = _axis(ring_id, axis_name)

    def fn(lg, lb):
        vocab_local = lg.shape[-1]
        rank = jax.lax.axis_index(ax)
        lo = rank * vocab_local
        m = jax.lax.pmax(jnp.max(lg, -1), ax)
        e = jnp.exp(lg - m[..., None])
        denom = jax.lax.psum(jnp.sum(e, -1), ax)
        local_lb = lb - lo
        in_range = (local_lb >= 0) & (local_lb < vocab_local)
        safe_lb = jnp.clip(local_lb, 0, vocab_local - 1)
        picked = jnp.take_along_axis(lg, safe_lb[..., None], -1)[..., 0]
        picked = jnp.where(in_range, picked, 0.0)
        picked = jax.lax.psum(picked, ax)
        loss = jnp.log(denom) + m - picked
        # ignored labels contribute zero loss (reference + eager
        # _ce_hard semantics)
        return jnp.where(lb == ignore_index, 0.0, loss)

    la = _aval_of(logits)
    out = jax.ShapeDtypeStruct(la.shape[:-1], jnp.float32)
    return dispatch(fn, logits, label,
                    name="c_softmax_with_cross_entropy",
                    nondiff_args=(1,), static_out_aval=out)


def run_program_sharded(program, mesh, feed, fetch_list, in_specs,
                        out_specs=None, scope=None, check_vma=False):
    """Execute a Program containing c_* ops under shard_map over `mesh`.

    feed: {name: GLOBAL array}; in_specs: {name: PartitionSpec for its
    shard_map split}; out_specs: {name: PartitionSpec} for each fetch
    (default replicated — correct for post-collective results; fetching
    a still-sharded intermediate needs its real spec or shard_map
    assembles one shard's local value as the global answer; pass
    check_vma=True to have jax verify replication instead of trusting
    the default).
    """
    from jax.sharding import PartitionSpec as P

    from .executor import _referenced_scope_names, _replay, global_scope

    scope = scope or global_scope()
    ops = list(program.global_block.ops)
    fetch_names = [f.name if hasattr(f, "name") else str(f)
                   for f in fetch_list]
    feed_names = list(feed)
    out_specs = out_specs or {}
    scope_names = [n for n in _referenced_scope_names(program, scope)
                   if n not in feed_names]
    scope_vals = [scope._vars[n] for n in scope_names]

    def body(*vals):
        env = dict(zip(feed_names + scope_names, vals))
        _replay(ops, env)
        return tuple(env[n] for n in fetch_names)

    m = mesh.mesh if hasattr(mesh, "mesh") else mesh
    specs = tuple(in_specs.get(n, P()) for n in feed_names) + \
        tuple(P() for _ in scope_names)
    out = jax.shard_map(body, mesh=m, in_specs=specs,
                        out_specs=tuple(out_specs.get(n, P())
                                        for n in fetch_names),
                        check_vma=check_vma)(
        *[feed[n] for n in feed_names], *scope_vals)
    return list(out)
