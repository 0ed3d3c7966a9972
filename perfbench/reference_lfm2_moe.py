"""The ``lfm2_moe`` decoder (LiquidAI/LFM2-24B-A2B), plain: ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``, with no kernel, no
cache, no batching and no grouped dispatch. It is the benchmark's own
yardstick for ``correct`` and calls nothing of the program under test; it only
reads the weights by the names ``model.raw_params()`` gives them (stacked a
KIND of sublayer: ``model.conv_layers.*`` over the conv layers,
``model.attn_layers.*`` over the attention layers, ``model.dense_layers.*``,
``model.moe_layers.*``, both norms of every layer under ``model.layers.*``).

The decoder as the ``transformers`` library publishes it (hidden 2,048; RMSNorm
eps 1e-5 throughout; no bias anywhere), for token t with residual x:

    x = E[token]
    for l in layers:
        h = RMSNorm(x; operator_norm_l)
        if layer_types[l] == "full_attention":
            q = h Wq (32 x 64), k = h Wk (8 x 64), v = h Wv (8 x 64)
            q, k = RMSNorm_64(q; q_layernorm), RMSNorm_64(k; k_layernorm)
            q, k = rope(q, k; theta 1e6, rotate-half over all 64 dims)
            y = causal_softmax(q k^T / 8) v; each K/V head serves 4 query
            heads; y = y Wo
        else:  # "conv"
            B, C, X = split3(h W_in)            # each 2,048 wide, this order
            u = B * X
            c_t = sum_{j=0..2} w[j] * u_{t-2+j} # depthwise causal, 3 taps,
                                                # u before the prompt = 0
            y = (C * c) W_out
        x = x + y
        g = RMSNorm(x; ffn_norm_l)
        if l < num_dense_layers: f = (silu(g W1) * (g W3)) W2     # 11,776
        else:
            s = sigmoid(g Wr), 64 scores
            sel = top4(s + expert_bias)         # the bias chooses only
            w = s[sel]; w = w / (sum(w) + 1e-6); w = w * routed_scaling_factor
            f = sum_j w_j SwiGLU_{sel_j}(g)     # 1,536 wide
        x = x + f
    logits = RMSNorm(x; embedding_norm) E^T

ASSUMED (the configuration file lists the same under ``assumed``): the head is
the embedding table (``tie_word_embeddings``) and the final norm is
``embedding_norm``, the LFM2 family's convention; the conv's tap ``w[j]`` of
channel d is ``model.conv_layers.conv[layer, j, d]``.

How it is computed, which changes no number: one sequence at a time; rows in
blocks of ``ROWS`` against key/value buffers of the run's fixed width, a conv
layer carrying the last two rows of ``u`` from block to block; each weight is
cast to float32 where it is used, an expert at a time; every expert is applied
to every row of a block and kept where the row chose it. ``bias=False`` routes
without the selection bias and ``carry=False`` starts every block's convolution
from zeros: negative controls, not the model.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 512


def sizes_key(c):
    """The sizes the reference needs, hashable (a jit's static arg)."""
    return (tuple(c["layer_types"]), int(c["num_dense_layers"]),
            c["num_attention_heads"], c["num_key_value_heads"],
            c["num_experts"], c["num_experts_per_tok"],
            bool(c["norm_topk_prob"]), bool(c["use_expert_bias"]),
            float(c["routed_scaling_factor"]), float(c["norm_eps"]),
            float(c["rope_parameters"]["rope_theta"]), int(c["conv_L_cache"]))


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x [R, heads, D]; pos [R]; dim d pairs with d + D/2 (rotate-half)."""
    d2 = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = pos.astype(jnp.float32)[:, None] * inv               # [R, D/2]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _at(w, name, i):
    """Slice ``i`` of a stacked weight, in float32."""
    return jax.lax.dynamic_index_in_dim(w[name], i, 0, keepdims=False
                                        ).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("key",), donate_argnums=(2, 3))
def _attention_rows(w, x, kbuf, vbuf, layer, ia, start, key):
    """An attention sublayer over one block of rows ``x`` [R, H]
    (positions start .. start + R - 1); the block's keys and values go
    into the buffers [W, kvh, hd] first."""
    nh, kvh, eps, theta = key[2], key[3], key[9], key[10]
    r, hidden = x.shape
    hd = hidden // nh
    pos = start + jnp.arange(r)
    h = _rms(x, _at(w, "model.layers.operator_norm", layer), eps)
    a = "model.attn_layers."
    q = _rope(_rms((h @ _at(w, a + "q_proj", ia)).reshape(r, nh, hd),
                   _at(w, a + "q_layernorm", ia), eps), pos, theta)
    k = _rope(_rms((h @ _at(w, a + "k_proj", ia)).reshape(r, kvh, hd),
                   _at(w, a + "k_layernorm", ia), eps), pos, theta)
    v = (h @ _at(w, a + "v_proj", ia)).reshape(r, kvh, hd)
    kbuf = jax.lax.dynamic_update_slice(kbuf, k, (start, 0, 0))
    vbuf = jax.lax.dynamic_update_slice(vbuf, v, (start, 0, 0))
    valid = jnp.arange(kbuf.shape[0])[None] <= pos[:, None]     # s <= t
    qg = q.reshape(r, kvh, nh // kvh, hd)
    s = jnp.einsum("rgmd,tgd->gmrt", qg, kbuf) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(valid[None, None], s, -jnp.inf), -1)
    y = jnp.einsum("gmrt,tgd->rgmd", p, vbuf).reshape(r, nh * hd)
    return x + y @ _at(w, a + "out_proj", ia), kbuf, vbuf


@functools.partial(jax.jit, static_argnames=("key",))
def _conv_rows(w, x, tail, layer, ic, key):
    """A conv sublayer over one block of rows ``x`` [R, H]; ``tail``
    [K-1, H] are the last rows of ``u`` before the block (zeros at the
    start of the sequence). Returns the block and its own tail."""
    eps, taps = key[9], key[11]
    r = x.shape[0]
    h = _rms(x, _at(w, "model.layers.operator_norm", layer), eps)
    gb, gc, gx = jnp.split(h @ _at(w, "model.conv_layers.in_proj", ic), 3,
                           axis=-1)
    full = jnp.concatenate([tail, gb * gx], axis=0)            # [K-1+R, H]
    wt = _at(w, "model.conv_layers.conv", ic)                  # [K, H]
    c = sum(wt[j] * full[j:j + r] for j in range(taps))
    y = (gc * c) @ _at(w, "model.conv_layers.out_proj", ic)
    return x + y, full[r:]


@functools.partial(jax.jit, static_argnames=("key",))
def _dense_ffn_rows(w, x, layer, idn, key):
    g = _rms(x, _at(w, "model.layers.ffn_norm", layer), key[9])
    d = "model.dense_layers."
    return x + (jax.nn.silu(g @ _at(w, d + "w1", idn))
                * (g @ _at(w, d + "w3", idn))) @ _at(w, d + "w2", idn)


@functools.partial(jax.jit, static_argnames=("key", "bias"))
def _expert_ffn_rows(w, x, layer, ie, key, bias):
    n_exp, top_e, norm_p, use_bias, scaling, eps = key[4:10]
    r = x.shape[0]
    m = "model.moe_layers."
    g = _rms(x, _at(w, "model.layers.ffn_norm", layer), eps)
    s = jax.nn.sigmoid(g @ _at(w, m + "router", ie))           # [R, E]
    choose = s + _at(w, m + "expert_bias", ie) if bias and use_bias else s
    top_i = jax.lax.top_k(choose, top_e)[1]
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if norm_p:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-6)
    top_w = top_w * scaling
    gates = jnp.zeros_like(s).at[jnp.arange(r)[:, None], top_i].set(top_w)

    def expert(e, acc):
        def one(name):
            a_ = w[m + name]
            return jax.lax.dynamic_slice(
                a_, (ie, e, 0, 0), (1, 1) + a_.shape[2:]
            )[0, 0].astype(jnp.float32)
        y = (jax.nn.silu(g @ one("experts_w1"))
             * (g @ one("experts_w3"))) @ one("experts_w2")
        return acc + jax.lax.dynamic_slice(gates, (0, e), (r, 1)) * y

    return x + jax.lax.fori_loop(0, n_exp, expert, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(g, table, x, eps):
    return _rms(x, g.astype(jnp.float32), eps) @ table.astype(jnp.float32).T


@jax.jit
def _embed(table, ids):
    return table[ids].astype(jnp.float32)


def row_logits(params, ids, width, c, bias=True, carry=True, rows=ROWS):
    """float32 logits ``[len(ids), V]`` (a numpy array) of ONE sequence
    ``ids``, computed in row blocks against buffers of the fixed ``width`` so
    that every sequence of a run shares its compiled programs. ``c``: the
    configuration's keys."""
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    key = sizes_key(c)
    types, n_dense, nh, kvh = key[:4]
    taps = key[11]
    rows = min(rows, width)
    if width % rows or n > width:
        raise ValueError(f"width {width} must be a multiple of {rows} "
                         f"and hold {n} tokens")
    blocks = -(-n // rows)
    padded = np.zeros((blocks * rows,), np.int32)
    padded[:n] = ids
    table = params["model.embed_tokens.weight"]
    hidden = table.shape[1]
    hd = hidden // nh
    subset = {k: v for k, v in params.items()
              if k != "model.embed_tokens.weight"}
    with jax.default_matmul_precision("highest"):
        xs = [_embed(table, jnp.asarray(padded[b * rows:(b + 1) * rows]))
              for b in range(blocks)]
        ia = ic = 0
        for layer, kind in enumerate(types):
            li = jnp.int32(layer)
            if kind == "conv":
                tail = jnp.zeros((taps - 1, hidden), jnp.float32)
                for b in range(blocks):
                    xs[b], tail = _conv_rows(subset, xs[b], tail, li,
                                             jnp.int32(ic), key=key)
                    if not carry:
                        tail = jnp.zeros_like(tail)
                ic += 1
            else:
                kbuf = jnp.zeros((width, kvh, hd), jnp.float32)
                vbuf = jnp.zeros((width, kvh, hd), jnp.float32)
                for b in range(blocks):
                    xs[b], kbuf, vbuf = _attention_rows(
                        subset, xs[b], kbuf, vbuf, li, jnp.int32(ia),
                        jnp.int32(b * rows), key=key)
                ia += 1
            for b in range(blocks):
                if layer < n_dense:
                    xs[b] = _dense_ffn_rows(subset, xs[b], li, li, key=key)
                else:
                    xs[b] = _expert_ffn_rows(
                        subset, xs[b], li, jnp.int32(layer - n_dense),
                        key=key, bias=bool(bias))
        out = np.empty((n, table.shape[0]), np.float32)
        for b in range(blocks):
            lg = _head(params["model.embedding_norm.weight"], table, xs[b],
                       eps=key[9])
            if lg.dtype != jnp.float32:
                raise TypeError(f"the reference ran in {lg.dtype}, not "
                                f"float32")
            take = min(rows, n - b * rows)
            out[b * rows:b * rows + take] = np.asarray(lg[:take])
    return out
