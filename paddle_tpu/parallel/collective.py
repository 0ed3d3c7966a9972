"""Collective communication API (paddle.distributed.* parity).

Reference: python/paddle/distributed/communication/ (all_reduce.py etc.) over
ProcessGroupNCCL (process_group_nccl.cc). TPU-native story (SURVEY §2.2
mapping): a collective is an *in-program* XLA op over a named mesh axis —
`jax.lax.psum/all_gather/ppermute/all_to_all` — legal only inside a
`shard_map`/pjit trace. This module gives them the paddle signature:

- inside shard_map: ops apply over the group's mesh axis name.
- eager outside any mesh context: world is the single process; collectives
  are identity (matching the reference when world_size == 1).

`ReduceOp`, `new_group`, `get_rank`, `get_world_size`, barrier and the
object-list helpers complete the surface for parity tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, dispatch, unwrap, wrap
from .mesh import get_mesh

__all__ = ["ReduceOp", "all_reduce", "all_gather", "all_gather_object",
           "reduce_scatter", "broadcast", "reduce", "scatter", "alltoall",
           "all_to_all", "send", "recv", "isend", "irecv", "barrier",
           "get_rank", "get_world_size", "new_group", "wait",
           "in_shard_map", "axis_or_none", "split_group",
           "alltoall_single", "broadcast_object_list",
           "scatter_object_list", "get_group", "destroy_process_group",
           "is_available", "get_backend", "gloo_init_parallel_env",
           "gloo_barrier", "gloo_release", "partial_allgather",
           "partial_ppermute", "partial_send", "partial_recv"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """Thin group handle: names a mesh axis (or explicit ranks for parity)."""

    def __init__(self, axis_name=None, ranks=None, pg_id=0):
        self.axis_name = axis_name
        self.ranks = ranks or []
        self.id = pg_id

    @property
    def nranks(self):
        if self.axis_name:
            m = get_mesh()
            if m is not None:
                return m.degree(self.axis_name)
        return max(len(self.ranks), 1)

    @property
    def rank(self):
        return 0

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else rank

    process_group = property(lambda self: self)


_DEFAULT_GROUP = Group(axis_name=None, ranks=[0])
_GROUPS = {0: _DEFAULT_GROUP}


def in_shard_map() -> bool:
    """True when tracing inside shard_map (axis names bound)."""
    try:
        return bool(jax.core.nonempty_axis_env_DO_NOT_USE())
    except Exception:
        return False


def _bound_axes():
    try:
        return set(jax.core.unsafe_get_axis_names_DO_NOT_USE())
    except Exception:
        return set()


def axis_or_none(group):
    """Resolve a group to a mesh-axis name if that axis is bound here."""
    axis = None
    if group is None:
        axis = getattr(_DEFAULT_GROUP, "axis_name", None)
    elif isinstance(group, Group):
        axis = group.axis_name
    elif isinstance(group, str):
        axis = group
    else:
        axis = getattr(group, "axis_name", None)
    if axis is not None and axis in _bound_axes():
        return axis
    return None


def set_default_axis(axis_name):
    _DEFAULT_GROUP.axis_name = axis_name


def get_rank(group=None):
    from . import env
    return env.get_rank()


def get_world_size(group=None):
    from . import env
    if group is not None and getattr(group, "axis_name", None):
        return Group(group.axis_name).nranks
    return env.get_world_size()


def new_group(ranks=None, backend=None, timeout=None, axis_name=None):
    """paddle.distributed.new_group parity (collective.py:185). On TPU the
    meaningful identity of a group is its mesh axis."""
    gid = max(_GROUPS) + 1
    g = Group(axis_name=axis_name, ranks=ranks or [], pg_id=gid)
    _GROUPS[gid] = g
    return g


def split_group(axis_name):
    return new_group(axis_name=axis_name)


# ----------------------------------------------------------- collectives


def _reduce_fn(op):
    return {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
            ReduceOp.MIN: jax.lax.pmin,
            ReduceOp.AVG: jax.lax.pmean}[op]


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = axis_or_none(group)
    if axis is None:
        if op == ReduceOp.AVG:
            return tensor  # world of 1
        return tensor

    def fn(v):
        return _reduce_fn(op)(v, axis)

    out = dispatch(fn, tensor, name="all_reduce")
    if isinstance(tensor, Tensor):
        tensor._replace_value(unwrap(out))
        return tensor
    return out


def all_gather(tensor_list, tensor=None, group=None, sync_op=True, axis=0):
    """Dual API: paddle (tensor_list out-param) or functional (returns array).

    Functional form: all_gather(tensor, group=...) -> concatenated array.
    """
    if tensor is None or isinstance(tensor_list, (Tensor, jax.Array, np.ndarray)):
        # functional: first arg is the tensor
        t = tensor_list
        ax = axis_or_none(group)
        if ax is None:
            return t
        return dispatch(
            lambda v: jax.lax.all_gather(v, ax, axis=axis, tiled=True),
            t, name="all_gather")
    ax = axis_or_none(group)
    if ax is None:
        tensor_list.append(tensor)
        return
    out = dispatch(lambda v: jax.lax.all_gather(v, ax, axis=0, tiled=False),
                   tensor, name="all_gather")
    n = Group(ax).nranks
    for i in range(n):
        tensor_list.append(out[i])


def all_gather_object(object_list, obj, group=None):
    object_list.append(obj)  # single-process parity


def reduce_scatter(tensor, tensor_or_tensor_list=None, op=ReduceOp.SUM,
                   group=None, sync_op=True, axis=0):
    src = tensor_or_tensor_list if tensor_or_tensor_list is not None else tensor
    ax = axis_or_none(group)
    if isinstance(src, (list, tuple)):
        from ..ops.manipulation import concat
        src = concat(list(src), axis=axis)
    if ax is None:
        if tensor_or_tensor_list is not None and isinstance(tensor, Tensor):
            tensor._replace_value(unwrap(src))
            return tensor
        return src
    out = dispatch(
        lambda v: jax.lax.psum_scatter(v, ax, scatter_dimension=axis,
                                       tiled=True), src,
        name="reduce_scatter")
    if tensor_or_tensor_list is not None and isinstance(tensor, Tensor):
        tensor._replace_value(unwrap(out))
        return tensor
    return out


def broadcast(tensor, src=0, group=None, sync_op=True):
    ax = axis_or_none(group)
    if ax is None:
        return tensor
    # value from axis-index src to all: gather the slice at src

    def fn(v):
        return jax.lax.all_gather(v, ax)[src]

    out = dispatch(fn, tensor, name="broadcast")
    if isinstance(tensor, Tensor):
        tensor._replace_value(unwrap(out))
        return tensor
    return out


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # on SPMD hardware reduce == all_reduce (every shard holds the result)
    return all_reduce(tensor, op=op, group=group, sync_op=sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """paddle.distributed.scatter parity. In shard_map: the src rank's
    stacked inputs are broadcast (all_gather + select, same pattern as
    broadcast above) and every rank keeps its own slice — XLA folds the
    redundant transfer into one collective."""
    ax = axis_or_none(group)
    if ax is None:
        # single-process: rank 0 keeps slice 0 (list form or stacked array)
        if tensor_list is not None:
            if isinstance(tensor_list, (list, tuple)):
                val = tensor_list[0] if tensor_list else None
            else:
                val = unwrap(tensor_list)[0]
            if val is not None and isinstance(tensor, Tensor):
                tensor._replace_value(unwrap(val))
            if tensor is None:
                return val
        return tensor
    if tensor_list is None:
        raise ValueError("scatter inside shard_map needs tensor_list "
                         "(stacked array or per-rank list)")
    if isinstance(tensor_list, (list, tuple)):
        stacked = jnp.stack([unwrap(t) for t in tensor_list])
    else:
        stacked = unwrap(tensor_list)

    def fn(v):
        v = jax.lax.all_gather(v, ax)[src]      # src rank's stack, everywhere
        idx = jax.lax.axis_index(ax)
        return jax.lax.dynamic_index_in_dim(v, idx, keepdims=False)

    out = dispatch(fn, stacked, name="scatter")
    if isinstance(tensor, Tensor):
        tensor._replace_value(unwrap(out))
        return tensor
    return out


def all_to_all(out_tensor_list, in_tensor_list=None, group=None, sync_op=True):
    """paddle.distributed.alltoall parity. Functional form: pass a single
    array with leading dim == group size -> returns exchanged array."""
    if in_tensor_list is None or isinstance(
            out_tensor_list, (Tensor, jax.Array, np.ndarray)):
        t = out_tensor_list
        ax = axis_or_none(group)
        if ax is None:
            return t
        return dispatch(
            lambda v: jax.lax.all_to_all(v, ax, split_axis=0, concat_axis=0,
                                         tiled=True), t, name="all_to_all")
    ax = axis_or_none(group)
    if ax is None:
        out_tensor_list.extend(in_tensor_list)
        return
    from ..ops.manipulation import stack
    stacked = stack(list(in_tensor_list), axis=0)
    out = dispatch(
        lambda v: jax.lax.all_to_all(v, ax, split_axis=0, concat_axis=0),
        stacked, name="all_to_all")
    n = len(in_tensor_list)
    for i in range(n):
        out_tensor_list.append(out[i])


alltoall = all_to_all


def ppermute(tensor, perm, group=None):
    """Point-to-point ring shift (reference: partial_send/recv for PP)."""
    ax = axis_or_none(group)
    if ax is None:
        return tensor
    return dispatch(lambda v: jax.lax.ppermute(v, ax, perm),
                    tensor, name="ppermute")


def send(tensor, dst=0, group=None, sync_op=True):
    raise RuntimeError(
        "TPU-native p2p is expressed as ppermute inside the pipeline "
        "schedule (parallel/pipeline.py); free-form send/recv has no XLA "
        "equivalent")


def recv(tensor, src=0, group=None, sync_op=True):
    raise RuntimeError("see send()")


def isend(tensor, dst=0, group=None):
    return send(tensor, dst, group)


def irecv(tensor, src=0, group=None):
    return recv(tensor, src, group)


class _Task:
    def wait(self):
        return True

    def is_completed(self):
        return True


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor):
        unwrap(tensor).block_until_ready()


def barrier(group=None):
    from . import env
    env.barrier()


# the richer task-returning stream namespace lives in parallel/stream.py
# (reference communication/stream/); collective.py keeps only the core ops


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """paddle.distributed.alltoall_single parity: single-tensor all-to-all
    over the group axis (leading dim split evenly unless sizes given).

    Uneven splits (reference alltoall_single with in/out_split_sizes) are
    compiled as pad-to-max + one XLA all_to_all + static slices: chunk j
    (rows ``in_split_sizes[j]``) goes to rank j; the output concatenates
    ``out_split_sizes[j]`` rows received from each rank j. Under one SPMD
    trace the size lists are trace-constants shared by all ranks (the
    standard shard_map usage); per-rank ragged lists cannot compile to a
    single program — use the object/host APIs for those."""
    ax = axis_or_none(group)
    if ax is None:
        if isinstance(out_tensor, Tensor) and in_tensor is not None:
            out_tensor._replace_value(unwrap(in_tensor))
            return out_tensor
        return in_tensor
    val = in_tensor if in_tensor is not None else out_tensor

    if in_split_sizes is not None and len(in_split_sizes) and \
            isinstance(in_split_sizes[0], (list, tuple, np.ndarray)):
        # rank-varying uneven splits: ONE SPMD trace serves every rank,
        # so the sizes must be the full [world, world] matrix
        # (sizes[i][j] = rows rank i sends to rank j); offsets become
        # axis_index-dynamic. Output length = column sum, which must be
        # uniform across ranks (static shapes) — the reference's fully
        # ragged case needs per-process programs and maps to the
        # object/host APIs instead.
        sizes = np.asarray(in_split_sizes, np.int64)
        world = jax.lax.axis_size(ax)
        if sizes.shape != (world, world):
            raise ValueError(f"size matrix must be [{world}, {world}], "
                             f"got {sizes.shape}")
        col = sizes.sum(0)
        if not (col == col[0]).all():
            raise ValueError(
                "uneven alltoall_single needs uniform per-rank output "
                "rows (equal column sums) to compile to one program; "
                f"got {col.tolist()}")
        out_len = int(col[0])
        m = int(sizes.max()) or 1
        in_off = np.concatenate(
            [np.zeros((world, 1), np.int64), np.cumsum(sizes, 1)[:, :-1]],
            1)
        out_off = np.concatenate(
            [np.zeros((1, world), np.int64), np.cumsum(sizes, 0)[:-1]], 0)

        def fn(v):
            i = jax.lax.axis_index(ax)
            sz = jnp.asarray(sizes)
            ioff = jnp.asarray(in_off)
            ooff = jnp.asarray(out_off)
            vp = jnp.concatenate(
                [v, jnp.zeros((m,) + v.shape[1:], v.dtype)], 0)
            chunks = []
            for j in range(world):
                c = jax.lax.dynamic_slice_in_dim(vp, ioff[i, j], m, 0)
                valid = (jnp.arange(m) < sz[i, j])
                chunks.append(jnp.where(
                    valid.reshape((m,) + (1,) * (v.ndim - 1)), c, 0))
            ex = jax.lax.all_to_all(jnp.stack(chunks), ax, split_axis=0,
                                    concat_axis=0, tiled=False)
            # sequential increasing writes: chunk j+1 starts exactly at
            # offset_j + size_j, overwriting chunk j's zero tail
            out = jnp.zeros((out_len + m,) + v.shape[1:], v.dtype)
            for j in range(world):
                out = jax.lax.dynamic_update_slice_in_dim(
                    out, ex[j], ooff[j, i], 0)
            return out[:out_len]

        out = dispatch(fn, val, name="alltoall_single_uneven")
    elif in_split_sizes is not None or out_split_sizes is not None:
        # a FLAT per-rank list is only self-consistent under one SPMD
        # trace when all sizes are equal (every rank would send the same
        # list, so rank i receives ins[i] from each peer — not outs[j]);
        # honoring it would silently return padding. Demand the matrix.
        raise ValueError(
            "uneven alltoall_single under SPMD needs the full "
            "[world, world] size matrix as in_split_sizes "
            "(sizes[i][j] = rows rank i sends to rank j); a flat "
            "per-rank list cannot describe rank-varying splits in one "
            "traced program")
    else:
        def fn(v):
            return jax.lax.all_to_all(v, ax, split_axis=0, concat_axis=0,
                                      tiled=True)

        out = dispatch(fn, val, name="alltoall_single")
    if isinstance(out_tensor, Tensor):
        out_tensor._replace_value(unwrap(out))
        return out_tensor
    return out


def partial_allgather(tensor, nranks=None, rank_id=None, group=None):
    """Reference partial_allgather_op: each rank contributes its own
    1/nranks segment of the buffer; the gather reassembles the full
    tensor on every rank. ``rank_id`` defaults to the caller's group
    rank (the only value the reference op is launched with)."""
    ax = axis_or_none(group)
    if ax is None:
        return tensor
    world = jax.lax.axis_size(ax)
    nranks = nranks or world
    if nranks != world:
        raise ValueError(f"partial_allgather nranks={nranks} != group "
                         f"size {world}")

    def fn(v):
        if v.shape[0] % world != 0:
            raise ValueError(
                f"partial_allgather: leading dim {v.shape[0]} not "
                f"divisible by nranks {world} — the tail rows would be "
                f"silently dropped; pad the buffer")
        seg = v.shape[0] // world
        rid = jax.lax.axis_index(ax) if rank_id is None else rank_id
        mine = jax.lax.dynamic_slice_in_dim(v, rid * seg, seg, 0)
        return jax.lax.all_gather(mine, ax, axis=0, tiled=True)

    return dispatch(fn, tensor, name="partial_allgather")


def partial_ppermute(tensor, perm, nranks=None, index=None, group=None):
    """TPU-native form of reference partial_send/partial_recv (the PP
    wire-compression pair: send only segment ``index`` of the buffer,
    receive the peer's segment into the same slot). One ppermute moves
    1/nranks of the bytes; the received segment replaces the local one,
    everything else is kept. ``index`` defaults to the sender's rank."""
    ax = axis_or_none(group)
    if ax is None:
        return tensor
    nranks = nranks or jax.lax.axis_size(ax)

    def fn(v):
        if v.shape[0] % nranks != 0:
            raise ValueError(
                f"partial_ppermute: leading dim {v.shape[0]} not "
                f"divisible by nranks {nranks} — the tail rows would be "
                f"silently dropped; pad the buffer")
        seg = v.shape[0] // nranks
        idx = jax.lax.axis_index(ax) if index is None else index
        start = idx * seg
        mine = jax.lax.dynamic_slice_in_dim(v, start, seg, 0)
        got = jax.lax.ppermute(mine, ax, perm)
        return jax.lax.dynamic_update_slice_in_dim(v, got, start, 0)

    return dispatch(fn, tensor, name="partial_ppermute")


def partial_send(tensor, dst=0, nranks=1, rank_id=0, group=None):
    raise RuntimeError(
        "TPU-native partial p2p is the paired partial_ppermute() (one "
        "XLA ppermute of the segment); free-form partial_send/recv has "
        "no single-program equivalent")


def partial_recv(tensor, src=0, nranks=1, rank_id=0, group=None):
    raise RuntimeError("see partial_send()")


def _object_to_tensor(obj):
    import pickle
    data = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
    return jnp.asarray(data), data.size


def _tensor_to_object(arr, size):
    import pickle
    return pickle.loads(np.asarray(arr)[:int(size)].tobytes())


def broadcast_object_list(object_list, src=0, group=None):
    """paddle.distributed.broadcast_object_list parity. Single-process
    (SPMD) semantics: every rank already holds src's objects — pickle
    round-trip keeps reference behavior (mutating the list in place)."""
    ax = axis_or_none(group)
    if ax is None:
        return object_list
    raise RuntimeError(
        "broadcast_object_list inside shard_map is not expressible; "
        "broadcast tensors instead")


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """Single-process semantics: rank 0 keeps element 0."""
    ax = axis_or_none(group)
    if ax is None:
        if in_object_list:
            del out_object_list[:]
            out_object_list.append(in_object_list[0])
        return out_object_list
    raise RuntimeError(
        "scatter_object_list inside shard_map is not expressible; "
        "scatter tensors instead")


def get_group(gid=0):
    """Return the group registered under id (reference collective._get_group)."""
    return _GROUPS.get(gid)


def destroy_process_group(group=None):
    """Tear down group bookkeeping (XLA collectives hold no persistent
    comm state to destroy)."""
    if group is None:
        for k in list(_GROUPS):
            if k != 0:
                del _GROUPS[k]
    else:
        _GROUPS.pop(getattr(group, "id", group), None)


def is_available():
    return True


def get_backend(group=None):
    return "xla"


def gloo_init_parallel_env(rank_id, rank_num, server_endpoint):
    """Reference gloo CPU barrier bootstrap — the TCPStore rendezvous
    (runtime/csrc/tcp_store.cc) is the TPU-native replacement."""
    from .env import init_parallel_env
    return init_parallel_env()


def gloo_barrier():
    return barrier()


def gloo_release():
    return None
