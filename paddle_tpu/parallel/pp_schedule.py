"""SPMD pipeline schedule: stage-stacked GPipe wave over the "pp" mesh axis.

TPU-native replacement for the reference's multi-process 1F1B
(pipeline_parallel.py:117: per-rank send/recv over NCCL with SendRecvMeta
shape handshakes). Here the whole pipeline is ONE SPMD program:

- per-stage params are stacked on a leading stage dim sharded over "pp";
- the wave is a `lax.scan` over ticks; at each tick every stage applies its
  block-stack to its current activation and `ppermute`s the result to the
  next stage (collective-permute rides ICI neighbours);
- `jax.grad` through the scan + ppermute yields the reverse-schedule
  backward automatically — no hand-written backward pass;
- microbatch accumulation falls out of the scan; bubbles are the usual
  (S-1) startup/cooldown ticks.

Static shapes everywhere: no shape handshake needed, which is exactly the
SendRecvMeta machinery deleted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from .mesh import HybridMesh, P

__all__ = ["stack_stage_params", "spmd_pipeline_forward",
           "pipeline_train_step"]


def stack_stage_params(pipe):
    """Stack per-stage param trees: name -> [S, ...] arrays.

    Requires structurally identical stages (uniform transformer segmentation;
    same assumption the reference's interleave makes). Returns
    (stacked: dict relname -> array, template_stage module).
    """
    from ..core.tensor import unwrap

    stages = list(pipe.stages)
    S = len(stages)
    names0 = [n for n, _ in stages[0].named_parameters()]
    stacked = {}
    for n in names0:
        leaves = []
        for s in range(S):
            named = dict(stages[s].named_parameters())
            if n not in named:
                raise ValueError(
                    f"stage {s} missing param {n}: stages must be uniform")
            leaves.append(unwrap(named[n]))
        stacked[n] = jnp.stack(leaves, axis=0)
    return stacked, stages[0]


def spmd_pipeline_forward(stage_fn, stacked_local, x_micro, num_stages,
                          first_stage_only_input=True):
    """Run the pipeline wave. MUST be called inside shard_map with axis "pp".

    stage_fn: (params_one_stage, x) -> y    (pure, shapes preserved)
    stacked_local: pytree with leading local stage dim of size 1 ([1, ...])
    x_micro: [M, mb, s, h] microbatched input (replicated over pp)
    Returns: [M, mb, s, h] last-stage outputs, psum-replicated over pp.
    """
    S = num_stages
    M = x_micro.shape[0]
    T = M + S - 1
    stage_idx = jax.lax.axis_index("pp")
    local = jax.tree_util.tree_map(lambda a: a[0], stacked_local)
    state0 = jnp.zeros_like(x_micro[0])
    perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(state, t):
        mb_id = jnp.clip(t, 0, M - 1)
        inp = jax.lax.dynamic_index_in_dim(x_micro, mb_id, axis=0,
                                           keepdims=False)
        x_in = jnp.where(stage_idx == 0, inp, state)
        y = stage_fn(local, x_in)
        nxt = jax.lax.ppermute(y, "pp", perm)
        out = jnp.where(stage_idx == S - 1, y, jnp.zeros_like(y))
        return nxt, out

    _, outs = jax.lax.scan(tick, state0, jnp.arange(T))
    # outputs for microbatch m emerge at tick m + S - 1 on the last stage
    outs = outs[S - 1:]                       # [M, mb, s, h]
    outs = jax.lax.psum(outs, "pp")           # replicate to all pp ranks
    return outs


def pipeline_train_step(pipe, embed_fn, head_loss_fn, optimizer,
                        mesh: HybridMesh, num_micro, extra_params=None,
                        remat=True, donate=True, grad_clip_norm=None):
    """Build a jitted full train step for a PipelineLayer transformer LM.

    embed_fn(extra_params, ids) -> [B, s, h]      (runs GSPMD, pre-pipeline)
    head_loss_fn(extra_params, hidden, labels) -> scalar loss
    The pipeline body covers pipe.stages (uniform blocks).

    Returns (step_fn, stacked_params, extra_params, opt_state).
    step_fn(stacked, extra, opt_state, ids, labels, step_i) ->
        (loss, stacked, extra, opt_state)
    """
    from ..jit import functional_call

    S = len(pipe.stages)
    stacked, template = stack_stage_params(pipe)
    extra_params = extra_params or {}

    def stage_fn(params_one, x):
        return functional_call(template, params_one, x)

    stage_fn_r = jax.checkpoint(stage_fn) if remat else stage_fn

    pp_shard = {n: NamedSharding(mesh.mesh, P("pp"))
                for n in stacked}
    extra_shard = {n: NamedSharding(mesh.mesh, P())
                   for n in extra_params}
    stacked = {n: jax.device_put(v, pp_shard[n]) for n, v in stacked.items()}
    extra_params = {n: jax.device_put(v, extra_shard[n])
                    for n, v in extra_params.items()}

    init_fn, update_fn = optimizer.functional()
    opt_state_stacked = init_fn(stacked)
    opt_state_extra = init_fn(extra_params)

    in_specs_body = (
        jax.tree_util.tree_map(lambda _: P("pp"), stacked),
        P(None, "dp"),  # x_micro [M, mb, s, h]
    )

    def body(stk, x_micro):
        return spmd_pipeline_forward(stage_fn_r, stk, x_micro, S)

    def loss_of(stacked, extra, ids, labels):
        x = embed_fn(extra, ids)                    # [B, s, h]
        B = x.shape[0]
        mb = B // num_micro
        x_micro = x.reshape((num_micro, mb) + x.shape[1:])
        outs = jax.shard_map(
            body, mesh=mesh.mesh,
            in_specs=in_specs_body,
            out_specs=P(None, "dp"),
            check_vma=False,
        )(stacked, x_micro)
        hidden = outs.reshape((B,) + outs.shape[2:])
        return head_loss_fn(extra, hidden, labels)

    def step(stacked, extra, states, ids, labels, step_i):
        st_stacked, st_extra = states
        loss, grads = jax.value_and_grad(loss_of, argnums=(0, 1))(
            stacked, extra, ids, labels)
        g_stacked, g_extra = grads
        if grad_clip_norm is not None:
            from ..nn.clip import clip_by_global_norm_tree
            g_all, _ = clip_by_global_norm_tree(
                {"s": g_stacked, "e": g_extra}, grad_clip_norm)
            g_stacked, g_extra = g_all["s"], g_all["e"]
        new_stacked, new_sst = update_fn(g_stacked, stacked, st_stacked,
                                         step=step_i)
        new_extra, new_est = update_fn(g_extra, extra, st_extra, step=step_i)
        return loss, new_stacked, new_extra, (new_sst, new_est)

    jit_step = jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())
    return jit_step, stacked, extra_params, (opt_state_stacked,
                                             opt_state_extra)
