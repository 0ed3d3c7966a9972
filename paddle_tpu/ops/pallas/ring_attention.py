"""Ring attention over the "sp" (sequence/context parallel) mesh axis.

Capability the reference LACKS (SURVEY §5.7: no sequence/context
parallelism in the snapshot) but the north star requires for long-context.
TPU-native design: sequence is sharded over "sp"; each step every rank
attends its local Q block against the K/V block it currently holds, merges
with running online-softmax stats, then `ppermute`s K/V around the ring so
compute overlaps the neighbour-to-neighbour ICI transfer. Expressed as a
`lax.scan` so reverse-mode AD yields the reverse ring for the backward pass
automatically.

Used inside shard_map (parallel/sp.py wires it into models); single-rank
call degrades to ordinary causal attention.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _block_attn(q, k, v, sm_scale, mask=None):
    """One blockwise attention contribution with stats.

    q: [B,H,Sq,D], k/v: [B,H,Sk,D] -> (numer [B,H,Sq,D], m, l).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                          # [B,H,Sq]
    # avoid -inf - -inf
    m_safe = jnp.maximum(m, NEG_INF)
    p = jnp.exp(s - m_safe[..., None])
    l = jnp.sum(p, axis=-1)
    numer = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return numer, m_safe, l


def ring_attention(q, k, v, axis_name="sp", causal=True, sm_scale=None):
    """q,k,v: LOCAL shards [B, H, S_local, D] inside shard_map over
    `axis_name`. Returns local attention output [B, H, S_local, D].

    Each ring step runs the Pallas flash kernel (XLA reference off-TPU)
    on the KV block currently held and merges (o, lse) pairs with
    logaddexp weights — the flash backward consumes the lse cotangent
    exactly (flash_attention.py _fwl_bwd), so the whole ring
    differentiates through the fused kernel. Causal steps dispatch per
    block origin: diagonal → causal kernel, below → full kernel, above →
    skipped entirely (no FLOPs for fully-masked tiles)."""
    from .flash_attention import flash_attention_with_lse

    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    # GQA: permute the RAW kv shards (ICI bytes stay at the kv-head
    # size); repeat to the query head count only inside each step
    rep = h // k.shape[1]
    assert h % k.shape[1] == 0, (h, k.shape[1])
    perm = [(i, (i + 1) % n) for i in range(n)]  # kv travels to next rank

    def step(carry, i):
        (k_i, v_i), o_run, lse_run = carry
        src = (my - i) % n  # rank where the held kv block originated
        k_r = jnp.repeat(k_i, rep, axis=1) if rep > 1 else k_i
        v_r = jnp.repeat(v_i, rep, axis=1) if rep > 1 else v_i

        def full(_):
            return flash_attention_with_lse(q, k_r, v_r, sm_scale, False)

        def diag(_):
            return flash_attention_with_lse(q, k_r, v_r, sm_scale, True)

        def masked(_):
            return (jnp.zeros((b, h, sq, d), q.dtype),
                    jnp.full((b, h, sq), NEG_INF, jnp.float32))

        if causal:
            # 0: src < my (full), 1: src == my (diagonal), 2: src > my
            case = jnp.where(src == my, 1, jnp.where(src > my, 2, 0))
            o_blk, lse_blk = jax.lax.switch(case, [full, diag, masked],
                                            None)
        else:
            o_blk, lse_blk = full(None)

        lse_new = jnp.logaddexp(lse_run, lse_blk)
        w_run = jnp.exp(lse_run - lse_new)[..., None]
        w_blk = jnp.exp(lse_blk - lse_new)[..., None]
        o_new = o_run * w_run + o_blk.astype(jnp.float32) * w_blk
        k_n = jax.lax.ppermute(k_i, axis_name, perm)
        v_n = jax.lax.ppermute(v_i, axis_name, perm)
        return ((k_n, v_n), o_new, lse_new), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    lse0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    (_, o_f, lse_f), _ = jax.lax.scan(step, ((k, v), o0, lse0),
                                      jnp.arange(n))
    return o_f.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name="sp", causal=True, sm_scale=None,
                      attn_fn=None):
    """DeepSpeed-Ulysses alternative: all_to_all heads<->sequence so each
    rank holds ALL tokens for H/n heads, runs full (flash) attention
    locally, then all_to_alls back. Needs heads % axis_size == 0."""
    n = jax.lax.axis_size(axis_name)
    if q.shape[1] % n != 0:
        raise ValueError(
            f"ulysses_attention: local heads {q.shape[1]} not divisible "
            f"by {axis_name!r} size {n} — the heads<->sequence "
            f"all_to_all needs heads % sp == 0 (use ring attention or "
            f"reduce the sp degree)")
    # [B, H, S_loc, D] -> gather seq, split heads
    q_ = jax.lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    k_ = jax.lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    v_ = jax.lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    if attn_fn is None:
        # default to the Pallas flash kernel (auto-falls back to the
        # reference composition off-TPU / on non-block-aligned shapes)
        from .flash_attention import _flash
        if sm_scale is None:
            sm_scale = 1.0 / math.sqrt(q.shape[-1])
        out = _flash(q_, k_, v_, sm_scale, causal)
    else:
        out = attn_fn(q_, k_, v_)
    # back: split seq, gather heads
    return jax.lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)
