"""The language model of Keye-VL-2.0-30B-A3B, plain: ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``, with no
kernel, no cache, no batching and no grouped dispatch. It is the
benchmark's own yardstick for ``correct`` and calls nothing of the
program under test; it only reads the weights by the names
``model.raw_params()`` gives them (block weights stacked over layers
under ``model.layers.<leaf>``).

The layer, for token t with residual x (hidden 2,048; 32 query heads and
4 K/V heads of 128; RMSNorm eps 1e-6; no biases; untied head):

- h = RMSNorm(x); q_i = R(n_q(W_q^i h)), k_g = R(n_k(W_k^g h)), v_g =
  W_v^g h; head i reads K/V head i // 8. R is the multimodal rotary
  embedding: the 64 frequencies (theta 1e7) split ``mrope_section`` =
  [16, 24, 24] over the (time, height, width) parts of a 3-part position.
- indexer (``sa_config``): q^I_j = R64(W_qI^j h) for j < 16, one key k^I =
  R64(W_kI h), w = W_w h; I(t, s) = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)
  for s <= t; S_t = every s <= t while t + 1 <= topk (2,048), else the
  topk positions of largest I(t, s), ties to the lower s.
- a_i = softmax over S_t of (q_i . k_s / sqrt 128) times v_s;
  x' = x + W_o [a_1 .. a_32].
- h2 = RMSNorm(x'); p = softmax(W_r h2) over 128 experts; T = the 8
  largest; g_e = p_e / sum_T p; x'' = x' + sum_{e in T} g_e W_d^e(silu(W_g^e
  h2) * W_u^e h2), expert width 768.

ASSUMED (the published ``config.json`` does not say; the configuration
file lists the same under ``assumed``): n_q and n_k are RMSNorms over a
head's 128 dims with a learned gain (the Qwen3-MoE decoder, whose keys
these are, has them); q^I and w are projections of h (this model has no
query latent); R64 is the plain rotary embedding over all 64 indexer
dims at the token's TIME component with the model's theta; k^I has no
norm; a positive scale on I changes no S_t, so none is applied;
``q_chunk_size``/``kv_chunk_size`` are tile sizes of the published
kernel and enter no equation. The rotation pairs dim d with d + D/2
(rotate-half, the Qwen convention).

How it is computed, which changes no number: one sequence at a time;
rows in blocks of ``ROWS`` against key/value buffers of the run's fixed
width, so 16,384 positions fit beside the bfloat16 weights (each weight
is cast to float32 where it is used; an expert at a time); every expert
is applied to every row of a block and kept where the row chose it.
``select=False`` leaves the selection out (attention over every key):
the negative control, not the model.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 512
PRE = "model.layers."


def sizes_key(c):
    """The sizes the reference needs, hashable (a jit's static arg)."""
    sa = c["sa_config"]
    return (c["num_hidden_layers"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["num_experts"],
            c["num_experts_per_tok"], bool(c["norm_topk_prob"]),
            float(c["rms_norm_eps"]), float(c["rope_theta"]),
            tuple(c["rope_scaling"]["mrope_section"]),
            sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos3, theta, sections):
    """x [R, heads, D]; pos3 [3, R]; frequency i turns with the position
    part its section names; dim d pairs with d + D/2."""
    d2 = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(d2, dtype=jnp.float32) / d2)
    part = np.repeat(np.arange(len(sections)), sections)
    ang = pos3.astype(jnp.float32).T[:, part] * inv            # [R, D/2]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _selected(scores, valid, topk):
    """[R, W] mask of each row's ``topk`` largest valid scores, ties to
    the lower position: the k-th value from a sort, then a running count
    over the ties."""
    s = jnp.where(valid, scores, -jnp.inf)
    kth = jax.lax.top_k(s, min(topk, s.shape[-1]))[0][:, -1:]
    above = s > kth
    tie = (s == kth) & valid
    room = topk - above.sum(-1, keepdims=True)
    return valid & (above | (tie & (jnp.cumsum(tie, -1) <= room)))


@functools.partial(jax.jit, static_argnames=("key", "select"),
                   donate_argnums=(2, 3, 4))
def _layer_rows(w, x, kbuf, vbuf, ibuf, layer, start, pos3, key, select):
    """One layer over one block of rows ``x`` [R, H] (sequence indices
    start .. start + R - 1, rope positions ``pos3`` [3, R]); the block's
    keys, values and indexer keys go into the buffers [W, ...] first."""
    (_, nh, kvh, hd, n_exp, top_e, norm_p, eps, theta, sections, ni, di,
     topk) = key

    def at(name):                       # this layer's weight, in float32
        return w[PRE + name][layer].astype(jnp.float32)

    r = x.shape[0]
    width = kbuf.shape[0]
    h = _rms(x, at("input_layernorm"), eps)
    q = _rope(_rms((h @ at("q_proj")).reshape(r, nh, hd), at("q_norm"),
                   eps), pos3, theta, sections)
    k = _rope(_rms((h @ at("k_proj")).reshape(r, kvh, hd), at("k_norm"),
                   eps), pos3, theta, sections)
    v = (h @ at("v_proj")).reshape(r, kvh, hd)
    time = pos3[:1]
    qi = _rope((h @ at("indexer_wq")).reshape(r, ni, di), time, theta,
               (di // 2,))
    ki = _rope((h @ at("indexer_wk")).reshape(r, 1, di), time, theta,
               (di // 2,))[:, 0]
    wi = h @ at("indexer_weights_proj")                        # [R, ni]
    kbuf = jax.lax.dynamic_update_slice(kbuf, k, (start, 0, 0))
    vbuf = jax.lax.dynamic_update_slice(vbuf, v, (start, 0, 0))
    ibuf = jax.lax.dynamic_update_slice(ibuf, ki, (start, 0))
    valid = (jnp.arange(width)[None]
             <= (start + jnp.arange(r))[:, None])              # s <= t
    keep = valid
    if select:
        def head(acc, qw):
            qj, wj = qw                                        # [R, di], [R]
            return acc + wj[:, None] * jax.nn.relu(qj @ ibuf.T), None
        scores, _ = jax.lax.scan(
            head, jnp.zeros((r, width), jnp.float32),
            (jnp.swapaxes(qi, 0, 1), wi.T))
        keep = _selected(scores, valid, topk)
    qg = q.reshape(r, kvh, nh // kvh, hd)
    s = jnp.einsum("rgmd,tgd->gmrt", qg, kbuf) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), -1)
    a = jnp.einsum("gmrt,tgd->rgmd", p, vbuf).reshape(r, nh * hd)
    x = x + a @ at("o_proj")

    h2 = _rms(x, at("post_attention_layernorm"), eps)
    probs = jax.nn.softmax(h2 @ at("router"), -1)
    top_p, top_i = jax.lax.top_k(probs, top_e)
    if norm_p:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(r)[:, None], top_i].set(top_p)

    def expert(e, acc):
        def one(name):
            a_ = w[PRE + name]
            return jax.lax.dynamic_slice(
                a_, (layer, e, 0, 0), (1, 1) + a_.shape[2:]
            )[0, 0].astype(jnp.float32)
        y = (jax.nn.silu(h2 @ one("experts_gate_proj"))
             * (h2 @ one("experts_up_proj"))) @ one("experts_down_proj")
        g = jax.lax.dynamic_slice(gates, (0, e), (r, 1))
        return acc + g * y

    x = x + jax.lax.fori_loop(0, n_exp, expert, jnp.zeros_like(x))
    return x, kbuf, vbuf, ibuf


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(g, head, x, eps):
    return _rms(x, g.astype(jnp.float32), eps) @ head.astype(jnp.float32)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(jnp.float32)


def row_logits(params, ids, width, c, position_ids=None, select=True,
               rows=ROWS):
    """float32 logits ``[len(ids), V]`` (a numpy array) of ONE sequence
    ``ids``, computed in row blocks against buffers of the fixed
    ``width`` so that every sequence of a run shares its compiled
    programs. ``c``: the configuration's keys; ``position_ids`` [3, T]
    (None: text, all three parts the token index)."""
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    key = sizes_key(c)
    layers, _, kvh, hd = key[:4]
    di = key[11]
    rows = min(rows, width)
    if width % rows or n > width:
        raise ValueError(f"width {width} must be a multiple of {rows} "
                         f"and hold {n} tokens")
    blocks = -(-n // rows)
    padded = np.zeros((blocks * rows,), np.int32)
    padded[:n] = ids
    pos3 = np.broadcast_to(np.arange(blocks * rows, dtype=np.int32),
                           (3, blocks * rows)).copy()
    if position_ids is not None:
        pos3[:, :n] = np.asarray(position_ids, np.int32)
    subset = {k: v for k, v in params.items() if k.startswith(PRE)}
    with jax.default_matmul_precision("highest"):
        xs = [_embed(params["model.embed_tokens.weight"],
                     jnp.asarray(padded[b * rows:(b + 1) * rows]))
              for b in range(blocks)]
        for layer in range(layers):
            kbuf = jnp.zeros((width, kvh, hd), jnp.float32)
            vbuf = jnp.zeros((width, kvh, hd), jnp.float32)
            ibuf = jnp.zeros((width, di), jnp.float32)
            for b in range(blocks):
                xs[b], kbuf, vbuf, ibuf = _layer_rows(
                    subset, xs[b], kbuf, vbuf, ibuf, jnp.int32(layer),
                    jnp.int32(b * rows),
                    jnp.asarray(pos3[:, b * rows:(b + 1) * rows]),
                    key=key, select=bool(select))
        out = np.empty((n, params["lm_head.weight"].shape[1]), np.float32)
        for b in range(blocks):
            lg = _head(params["model.norm.weight"], params["lm_head.weight"],
                       xs[b], eps=key[7])
            if lg.dtype != jnp.float32:
                raise TypeError(f"the reference ran in {lg.dtype}, not "
                                f"float32")
            take = min(rows, n - b * rows)
            out[b * rows:b * rows + take] = np.asarray(lg[:take])
    return out
