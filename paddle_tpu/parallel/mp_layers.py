"""Megatron-style tensor-parallel layers.

Reference: python/paddle/distributed/fleet/layers/mpu/mp_layers.py —
VocabParallelEmbedding(:35), ColumnParallelLinear(:173),
RowParallelLinear(:332), ParallelCrossEntropy(:498).

TPU-native dual execution:
- **GSPMD mode** (default, the perf path): the layer holds the FULL logical
  weight annotated with a PartitionSpec (`param._sharding_axes`); under pjit
  with those shardings XLA partitions the matmul and inserts the
  all-reduce/all-gather that the reference issues manually. Forward adds
  `with_sharding_constraint` so the activation layout is pinned the same way
  the reference pins it via explicit collectives.
- **shard_map mode** (parity/escape hatch): inside `shard_map` the same
  forward uses explicit mp_ops collectives with per-rank weight shards.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import dispatch
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from . import mp_ops
from .collective import in_shard_map
from .mesh import P, get_mesh

__all__ = ["VocabParallelEmbedding", "ColumnParallelLinear",
           "RowParallelLinear", "ParallelCrossEntropy"]


def _constraint(spec):
    """with_sharding_constraint when a mesh is active (trace-time no-op otherwise)."""
    def fn(v):
        m = get_mesh()
        if m is None or in_shard_map():
            return v
        try:
            return jax.lax.with_sharding_constraint(v, m.sharding(*spec))
        except Exception:
            return v
    return fn


class VocabParallelEmbedding(Layer):
    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.mp_group = mp_group or "mp"
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=I.Normal(0.0, 1.0))  # == nn.Embedding default
        self.weight._sharding_axes = P("mp", None)
        self.weight.is_distributed = True

    def forward(self, x):
        if in_shard_map():
            # explicit: local rows hold [start, end); mask + psum
            def fn(idx, w):
                n = jax.lax.axis_size("mp")
                rank = jax.lax.axis_index("mp")
                rows = w.shape[0]
                start = rank * rows
                local = idx - start
                ok = (local >= 0) & (local < rows)
                safe = jnp.clip(local, 0, rows - 1)
                out = jnp.take(w, safe, axis=0)
                out = out * ok[..., None].astype(out.dtype)
                return jax.lax.psum(out, "mp")

            return dispatch(fn, x, self.weight, nondiff_args=(0,),
                            name="vocab_parallel_embedding")
        out = F.embedding(x, self.weight)
        return dispatch(_constraint((None, None, None)), out,
                        name="shard_constraint")


class ColumnParallelLinear(Layer):
    """Y = X @ W, W sharded on columns (out features across mp)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight._sharding_axes = P(None, "mp")
        self.weight.is_distributed = True
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
            self.bias._sharding_axes = P("mp")
            self.bias.is_distributed = True
        else:
            self.bias = None

    def forward(self, x):
        if in_shard_map():
            x = mp_ops.c_identity(x) if not isinstance(x, jax.Array) else \
                dispatch(lambda v: mp_ops.c_identity(v), x, name="c_identity")
            out = F.linear(x, self.weight, self.bias)
            if self.gather_output:
                out = dispatch(lambda v: mp_ops.c_concat(v), out,
                               name="c_concat")
            return out
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            return dispatch(_constraint((None, None, None)), out,
                            name="shard_constraint")
        return dispatch(_constraint((None, None, "mp")), out,
                        name="shard_constraint")


class RowParallelLinear(Layer):
    """Y = X @ W, W sharded on rows (in features across mp); output psum."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight._sharding_axes = P("mp", None)
        self.weight.is_distributed = True
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        if in_shard_map():
            def fn(v, w):
                if not self.input_is_parallel:
                    v = mp_ops.c_split(v)
                part = jnp.matmul(v, w)
                return mp_ops.mp_allreduce(part)

            out = dispatch(fn, x, self.weight, name="row_parallel_linear")
            if self.bias is not None:
                out = out + self.bias
            return out
        out = F.linear(x, self.weight, None)
        out = dispatch(_constraint((None, None, None)), out,
                       name="shard_constraint")
        if self.bias is not None:
            out = out + self.bias
        return out


class ParallelCrossEntropy(Layer):
    """Reference mp_layers.py:498 → c_softmax_with_cross_entropy."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):  # noqa: A002
        return dispatch(
            lambda lg, lb: mp_ops.c_softmax_with_cross_entropy(
                lg, lb, ignore_index=self.ignore_index),
            input, label, nondiff_args=(1,), name="parallel_cross_entropy")
