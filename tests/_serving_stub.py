"""A serving-contract test double for ContinuousBatchingServer.

Reliability and chaos tests exercise HOST-side machinery — queues,
deadlines, supervision, page accounting — where real transformer
numerics only add compile time and noise. ``StubModel`` implements
exactly the decode-bundle contract the server consumes
(``_decode_bundle`` + ``_run_prefill``, dense AND paged) with a closed
-form token recurrence, so every test can predict full outputs:

    first  = (7 * prompt[-1] + len(prompt)) % V          (prefill)
    tok_k+1 = (7 * tok_k + t_k + 1) % V,  t_k = T, T+1, ...

``stub_tokens(prompt, n)`` is the oracle. Prefill writes token values
into the cache rows it covers, so page fills / prefix sharing move real
data; decode steps pass caches through untouched (logits depend only on
(token, position), which is what makes the oracle exact). The paged
bundle carries the ragged-prefill entry point (element 5, ISSUE 6)
with the same write-token-values semantics, so the ragged scheduler's
chunk packing, null-redirects and prefix-offset resumes are exercised
against the oracle too.
"""
import types

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.models.generation import paged_pool_shape, pool_lanes

V = 16


def stub_tokens(prompt, n):
    """The n new tokens a StubModel-backed server must emit."""
    prompt = np.asarray(prompt).reshape(-1)
    T = len(prompt)
    toks = [(7 * int(prompt[-1]) + T) % V]
    t = T
    while len(toks) < n:
        toks.append((7 * toks[-1] + t + 1) % V)
        t += 1
    return np.asarray(toks[:n], np.int32)


class StubModel:
    L, H, HD = 1, 1, 2           # layers / kv heads / head dim
    V = V
    cfg = types.SimpleNamespace(num_heads=H)   # what the server reads

    def _decode_bundle(self, max_cache_len, weight_dtype=None, mesh=None,
                       cache_dtype=None, cache_backend="dense",
                       page_size=None, num_pages=None):
        L, h, hd, vocab = self.L, self.H, self.HD, self.V
        C = int(max_cache_len)

        if cache_backend == "paged":
            pg = int(page_size)
            maxp = C // pg

            def init_caches(batch):
                shape = paged_pool_shape(L, num_pages, pg, h, hd)
                return {"pool": {"k": jnp.zeros(shape, jnp.float32),
                                 "v": jnp.zeros(shape, jnp.float32)},
                        "bt": jnp.zeros((batch, maxp), jnp.int32)}
        else:
            def init_caches(batch):
                shape = (L, batch, C, h, hd)
                return {"k": jnp.zeros(shape, jnp.float32),
                        "v": jnp.zeros(shape, jnp.float32)}

        def embed_fn(tok, t):
            return jnp.stack([tok.astype(jnp.float32),
                              t.astype(jnp.float32)], axis=-1)

        def step_fn(x, caches, t):
            return x, caches

        def head_fn(out):
            tok = out[..., 0].astype(jnp.int32)
            t = out[..., 1].astype(jnp.int32)
            nxt = (7 * tok + t + 1) % vocab
            return jax.nn.one_hot(nxt, vocab, dtype=jnp.float32) * 10.0

        if cache_backend == "paged":
            def ragged_prefill(tokens, t0, caches, out_idx, take, slots):
                """Ragged-prefill contract (paged bundle element 5):
                tokens [P, C] packed chunks, row j being slot slots[j],
                t0 [P] start positions (padding rows name a slot past
                the last and carry t0 = max_cache_len — every write
                null-redirects zeroed), out_idx [P] row of each chunk's
                last prompt token, take [P] each chunk's real rows
                (unread: pages are this model's whole state). Writes
                token VALUES into pool pages (page fills move real data,
                like _run_prefill) and returns the oracle's next-token
                logits per row."""
                pool = caches["pool"]
                bt = caches["bt"][jnp.minimum(slots,
                                              caches["bt"].shape[0] - 1)]
                S, Cc = tokens.shape
                pos = t0[:, None] + jnp.arange(Cc, dtype=jnp.int32)[None]
                pidx = pos // pg
                oob = pidx >= maxp
                page = jnp.where(
                    oob, 0, jnp.take_along_axis(
                        bt, jnp.minimum(pidx, maxp - 1), axis=1))
                vals = jnp.where(oob, 0.0, tokens.astype(jnp.float32))
                n = S * Cc
                flat = pool_lanes(jnp.broadcast_to(
                    vals.reshape(n)[:, None, None], (n, h, hd)))
                fp, fo = page.reshape(n), (pos % pg).reshape(n)
                pool = {"k": pool["k"].at[:, fp, fo].set(flat[None]),
                        "v": pool["v"].at[:, fp, fo].set(flat[None])}
                last_tok = jnp.take_along_axis(
                    tokens, out_idx[:, None], axis=1)[:, 0]
                last_pos = t0 + out_idx
                nxt = (7 * last_tok + last_pos + 1) % vocab
                logits = jax.nn.one_hot(nxt, vocab,
                                        dtype=jnp.float32) * 10.0
                return logits, dict(caches, pool=pool)

            return (init_caches, embed_fn, step_fn, head_fn, None,
                    jax.jit(ragged_prefill, donate_argnums=(2,)))
        return init_caches, embed_fn, step_fn, head_fn, None

    def _run_prefill(self, bundle, ids_np, chunk=None, caches=None, t0=0):
        init_caches = bundle[0]
        ids = np.asarray(ids_np)
        B, T = ids.shape
        if caches is None:
            caches = init_caches(B)
        L, h, hd = self.L, self.H, self.HD
        vals = jnp.asarray(ids, jnp.float32)[None, :, :, None, None]
        vals = jnp.broadcast_to(vals, (L, B, T, h, hd))
        caches = {"k": caches["k"].at[:, :, t0:t0 + T].set(vals),
                  "v": caches["v"].at[:, :, t0:t0 + T].set(vals)}
        nxt = (7 * ids[:, -1].astype(np.int64) + (t0 + T - 1) + 1) % self.V
        logits = jax.nn.one_hot(jnp.asarray(nxt), self.V,
                                dtype=jnp.float32) * 10.0
        return logits, caches
