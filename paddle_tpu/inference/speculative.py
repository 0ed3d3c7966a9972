"""Speculative decoding: a small draft model proposes, the target model
verifies a whole block in one forward.

Serving-side counterpart of the reference's fused decode op — but
instead of one target forward per token, each round costs one draft scan
(cheap) plus ONE target forward over ``gamma + 1`` positions, and
accepts ``k + 1`` tokens (the matched draft prefix plus the target's own
token at the first divergence). With greedy acceptance the output is
BIT-IDENTICAL to the target model's own greedy decode — speculation
changes latency, never results.

Cache discipline: neither model rolls anything back. Rejected draft
positions leave stale KV rows ABOVE the accepted frontier; the causal
validity mask (models/generation.py _cached_attend: key position <=
query position) hides them, and the next round's feed overwrites exactly
those rows before they ever become visible.
"""
import numpy as np

import jax.numpy as jnp

from ..core.tensor import unwrap, wrap

__all__ = ["speculative_generate"]


def speculative_generate(target, draft, input_ids, max_new_tokens=32,
                         gamma=4, eos_token_id=None, max_cache_len=None,
                         return_stats=False):
    """Greedy speculative decoding (single sequence).

    ``target`` and ``draft`` are CausalLM models sharing a vocabulary
    (any mix of GPT/Llama/Mixtral). ``gamma`` is the draft block length.
    Returns the full sequence (prompt + new tokens), exactly equal to
    ``target.generate(input_ids, max_new_tokens)``; with
    ``return_stats=True`` also a dict with per-round acceptance counts.
    """
    from .decode_loop import greedy_generate

    ids_np = np.asarray(unwrap(input_ids)).astype(np.int32)
    if ids_np.ndim == 1:
        ids_np = ids_np[None]
    if ids_np.shape[0] != 1:
        raise ValueError("speculative_generate is single-sequence; "
                         "batch via the continuous-batching server")
    T0 = ids_np.shape[1]
    if max_cache_len is None:
        max_cache_len = min(target.cfg.max_seq_len,
                            T0 + max_new_tokens + gamma + 1)
    if T0 + max_new_tokens + gamma + 1 > max_cache_len:
        raise ValueError(
            f"prompt ({T0}) + max_new_tokens ({max_new_tokens}) + "
            f"gamma+1 ({gamma + 1}) exceeds max_cache_len "
            f"({max_cache_len}) — the verify block needs headroom")

    t_init, t_embed, t_step, t_head, t_prefill = \
        target._decode_bundle(max_cache_len)
    d_init, d_embed, d_step, d_head, d_prefill = \
        draft._decode_bundle(max_cache_len)

    # prefill both models on the prompt; first token is the target's
    ids_j = jnp.asarray(ids_np)
    t_caches = t_init(1)
    out, t_caches = t_prefill(target._prefill_embed(ids_j, None),
                              t_caches, jnp.int32(0))
    a = int(jnp.argmax(t_head(out[:, -1:])[:, -1], -1)[0])
    d_caches = d_init(1)
    _, d_caches = d_prefill(draft._prefill_embed(ids_j, None),
                            d_caches, jnp.int32(0))

    # the verify forward IS the bundle's prefill program (step_fn over a
    # multi-token block, caches donated, weights as runtime arguments)
    verify_jit = t_prefill

    emitted = [a]
    t = T0                      # next feed position (token `a` sits here)
    accepts = []
    while len(emitted) < max_new_tokens and not (
            eos_token_id is not None and emitted[-1] == eos_token_id):
        # 1) draft proposes gamma tokens from its own caches
        d_ids, d_caches = greedy_generate(
            d_embed, d_step, d_head, d_caches,
            jnp.asarray([emitted[-1]], jnp.int32), t, gamma + 1)
        # greedy_generate emits [a, d1..dgamma]; drop the echo of `a`
        drafts = np.asarray(d_ids)[0, 1:]                 # gamma tokens

        # 2) one target forward over [a, d1..dgamma]
        block = np.concatenate([[emitted[-1]], drafts]).astype(np.int32)
        x = target._prefill_embed(jnp.asarray(block[None]), None, t0=t)
        out, t_caches = verify_jit(x, t_caches, jnp.int32(t))
        m = np.asarray(jnp.argmax(t_head(out), -1))[0]    # gamma+1 preds

        # 3) accept matched prefix + the target's correction token
        k = 0
        while k < gamma and m[k] == drafts[k]:
            k += 1
        new = list(drafts[:k]) + [int(m[k])]
        accepts.append(k)
        emitted.extend(new)
        t += k + 1
        # draft cache rows for accepted tokens were written while
        # drafting; the correction token is fed next round (as `a`).
        # Rows above the frontier are stale-but-masked (see module doc).

    emitted = emitted[:max_new_tokens]
    if eos_token_id is not None and eos_token_id in emitted:
        # match generate()'s static-shape contract: eos-pad the tail
        emitted = emitted[:emitted.index(eos_token_id) + 1]
        emitted += [eos_token_id] * (max_new_tokens - len(emitted))
    full = np.concatenate([ids_np[0], np.asarray(emitted, np.int32)])
    result = wrap(jnp.asarray(full[None]))
    if return_stats:
        return result, {
            "rounds": len(accepts),
            "accepted_per_round": accepts,
            "mean_accepted": float(np.mean(accepts)) if accepts else 0.0,
            "tokens_per_target_forward":
                (len(emitted) / len(accepts)) if accepts else 1.0,
        }
    return result
