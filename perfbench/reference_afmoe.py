"""The ``afmoe`` decoder (arcee-ai's Trinity-Mini 26B-A3B), plain: ``jax.numpy``
in float32 under ``jax.default_matmul_precision("highest")``, with no kernel,
no cache, no batching and no grouped dispatch. It is the benchmark's own
yardstick for ``correct`` and calls nothing of the program under test; it only
reads the weights by the names ``model.raw_params()`` gives them (stacked a
KIND of sublayer: the four norms and the attention of every layer under
``model.layers.*``, ``model.dense_layers.*``, ``model.moe_layers.*``).

The decoder (hidden 2,048; 32 query and 4 K/V heads of 128; RMSNorm eps 1e-5
throughout; no bias anywhere), for token t with residual x:

    x = E[token] * sqrt(hidden)                 # mup_enabled
    for l in layers:
        h = RMSNorm(x; input_layernorm_l)
        q = h Wq (32 x 128), k = h Wk (4 x 128), v = h Wv (4 x 128)
        q, k = RMSNorm_128(q; q_norm), RMSNorm_128(k; k_norm)
        if layer_types[l] == "sliding_attention":
            q, k = rope(q, k; theta 10,000, rotate-half over all 128 dims)
            key s is visible to query t iff s <= t and t - s < 2,048
        else:                                   # "full_attention"
            no positional term; key s visible iff s <= t
        a = softmax(q k^T / sqrt(128) over the visible keys) v; each K/V
            head serves 8 query heads
        x = x + RMSNorm((a * sigmoid(h Wg)) Wo; post_attention_layernorm_l)
        u = RMSNorm(x; pre_mlp_layernorm_l)
        if l < num_dense_layers: f = (silu(u W1) * (u W3)) W2       # 6,144
        else:
            s = sigmoid(u Wr), 128 scores
            sel = top8(s + expert_bias)         # the bias chooses only
            w = s[sel] / (sum(s[sel]) + 1e-20) * route_scale
            f = SwiGLU_shared(u) + sum_j w_j SwiGLU_{sel_j}(u)      # 1,024
        x = x + RMSNorm(f; post_mlp_layernorm_l)
    logits = RMSNorm(x; norm) W_head            # untied

ASSUMED (the configuration file lists the same under ``assumed``, each with
its reason): rope on the window layers only; the sigmoid gate on the
attention output, from the normed input; the four norms; the muP scale of the
embedding; the router's eps 1e-20; the window's edge ``t - s < 2,048``.

How it is computed, which changes no number: one sequence at a time; rows in
blocks of ``ROWS`` against key/value buffers of the run's fixed width, a window
layer reading the buffer's slice that holds its block's windows; each weight is
cast to float32 where it is used, an expert at a time; every expert is applied
to every row of a block and kept where the row chose it. Logits are computed
for the LAST ``head_rows`` positions only (all of them where it is None): the
check reads the positions that predict emitted tokens, and a row the head
skipped holds zeros, whose spread of 0 no comparison can pass.

The negative controls, not the model: ``window=False`` (a window layer
attends the whole context), ``rope_full=True`` (the full layers turn q and k
too), ``gate=False`` (no output gate), ``route="score"`` (the top 8 by ``s``
alone: the bias never chooses), ``route="bias_weighs"`` (the chosen experts
weighed by ``s + expert_bias``).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256


def sizes_key(c):
    """The sizes the reference needs, hashable (a jit's static arg)."""
    return (tuple(c["layer_types"][:c["num_hidden_layers"]]),
            int(c["num_dense_layers"]), c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["num_experts"],
            c["num_experts_per_tok"], bool(c["route_norm"]),
            float(c["route_scale"]), float(c["rms_norm_eps"]),
            float(c["rope_theta"]), int(c["sliding_window"]),
            bool(c["mup_enabled"]))


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


@functools.partial(jax.jit, static_argnames=("key", "span", "rope", "gate"),
                   donate_argnums=(2, 3))
def _attention_rows(w, x, kbuf, vbuf, layer, start, key, span, rope, gate):
    """An attention sublayer over one block of rows ``x`` [R, H]
    (positions start .. start + R - 1); the block's keys and values go
    into the buffers [W, kvh, hd] first. ``span`` None: every key up to a
    row's own is visible; else a window layer: the ``span`` buffer rows
    that end with the block's hold every key its rows' windows see, and a
    key is visible iff it is at most ``window - 1`` behind the row."""
    nh, kvh, hd, eps, theta, window = (key[2], key[3], key[4], key[9],
                                       key[10], key[11])
    r = x.shape[0]
    pos = start + jnp.arange(r)
    m = "model.layers."
    h = _rms(x, _at(w, m + "input_layernorm", layer), eps)
    q = _rms((h @ _at(w, m + "q_proj", layer)).reshape(r, nh, hd),
             _at(w, m + "q_norm", layer), eps)
    k = _rms((h @ _at(w, m + "k_proj", layer)).reshape(r, kvh, hd),
             _at(w, m + "k_norm", layer), eps)
    v = (h @ _at(w, m + "v_proj", layer)).reshape(r, kvh, hd)
    if rope:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    kbuf = jax.lax.dynamic_update_slice(kbuf, k, (start, 0, 0))
    vbuf = jax.lax.dynamic_update_slice(vbuf, v, (start, 0, 0))
    if span is None:
        first, keys, vals = 0, kbuf, vbuf
    else:
        first = jnp.clip(start + r - span, 0, kbuf.shape[0] - span)
        keys = jax.lax.dynamic_slice_in_dim(kbuf, first, span)
        vals = jax.lax.dynamic_slice_in_dim(vbuf, first, span)
    at = first + jnp.arange(keys.shape[0])
    valid = at[None] <= pos[:, None]                           # s <= t
    if span is not None:
        valid = valid & (pos[:, None] - at[None] < window)
    qg = q.reshape(r, kvh, nh // kvh, hd)
    s = jnp.einsum("rgmd,tgd->gmrt", qg, keys) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(valid[None, None], s, -jnp.inf), -1)
    a = jnp.einsum("gmrt,tgd->rgmd", p, vals).reshape(r, nh * hd)
    if gate:
        a = a * jax.nn.sigmoid(h @ _at(w, m + "gate_proj", layer))
    y = _rms(a @ _at(w, m + "o_proj", layer),
             _at(w, m + "post_attention_layernorm", layer), eps)
    return x + y, kbuf, vbuf


def _swiglu(u, g, up, down):
    return (jax.nn.silu(u @ g) * (u @ up)) @ down


@functools.partial(jax.jit, static_argnames=("key",))
def _dense_ffn_rows(w, x, layer, key):
    eps = key[9]
    u = _rms(x, _at(w, "model.layers.pre_mlp_layernorm", layer), eps)
    d = "model.dense_layers."
    f = _swiglu(u, _at(w, d + "gate_proj", layer), _at(w, d + "up_proj", layer),
                _at(w, d + "down_proj", layer))
    return x + _rms(f, _at(w, "model.layers.post_mlp_layernorm", layer), eps)


ROUTES = ("bias_chooses", "score", "bias_weighs")


@functools.partial(jax.jit, static_argnames=("key", "route"))
def _expert_ffn_rows(w, x, layer, ie, key, route):
    n_exp, top_e, norm_p, scale, eps = key[5:10]
    r = x.shape[0]
    m = "model.moe_layers."
    u = _rms(x, _at(w, "model.layers.pre_mlp_layernorm", layer), eps)
    s = jax.nn.sigmoid(u @ _at(w, m + "router", ie))           # [R, E]
    biased = s + _at(w, m + "expert_bias", ie)
    top_i = jax.lax.top_k(s if route == "score" else biased, top_e)[1]
    top_w = jnp.take_along_axis(biased if route == "bias_weighs" else s,
                                top_i, axis=-1)
    if norm_p:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    top_w = top_w * scale
    gates = jnp.zeros_like(s).at[jnp.arange(r)[:, None], top_i].set(top_w)

    def expert(e, acc):
        def one(name):
            a_ = w[m + name]
            return jax.lax.dynamic_slice(
                a_, (ie, e, 0, 0), (1, 1) + a_.shape[2:]
            )[0, 0].astype(jnp.float32)
        y = _swiglu(u, one("experts_gate_proj"), one("experts_up_proj"),
                    one("experts_down_proj"))
        return acc + jax.lax.dynamic_slice(gates, (0, e), (r, 1)) * y

    f = jax.lax.fori_loop(0, n_exp, expert, _swiglu(
        u, _at(w, m + "shared_gate_proj", ie), _at(w, m + "shared_up_proj", ie),
        _at(w, m + "shared_down_proj", ie)))
    return x + _rms(f, _at(w, "model.layers.post_mlp_layernorm", layer), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(g, head, x, eps):
    return _rms(x, g.astype(jnp.float32), eps) @ head.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(table, ids, scale):
    return table[ids].astype(jnp.float32) * scale


def row_logits(params, ids, width, c, window=True, rope_full=False,
               gate=True, route="bias_chooses", rows=ROWS, head_rows=None):
    """float32 logits ``[len(ids), V]`` (a numpy array) of ONE sequence
    ``ids``, computed in row blocks against buffers of the fixed ``width``
    so that every sequence of a run shares its compiled programs. ``c``:
    the configuration's keys. ``head_rows``: the head is applied to the
    last ``head_rows`` positions only (the rest read 0); None: to all."""
    if route not in ROUTES:
        raise ValueError(f"route {route!r} is not one of {ROUTES}")
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    key = sizes_key(c)
    types, n_dense, _, kvh, hd = key[:5]
    rows = min(rows, width)
    if width % rows or n > width:
        raise ValueError(f"width {width} must be a multiple of {rows} "
                         f"and hold {n} tokens")
    # the buffer rows that hold every key a block's windows see
    span = min(width, key[11] - 1 + rows) if window else None
    blocks = -(-n // rows)
    padded = np.zeros((blocks * rows,), np.int32)
    padded[:n] = ids
    table = params["model.embed_tokens.weight"]
    scale = math.sqrt(table.shape[1]) if key[12] else 1.0
    subset = {k: v for k, v in params.items()
              if k.startswith(("model.layers.", "model.dense_layers.",
                               "model.moe_layers."))}
    with jax.default_matmul_precision("highest"):
        xs = [_embed(table, jnp.asarray(padded[b * rows:(b + 1) * rows]),
                     scale=scale) for b in range(blocks)]
        for layer, kind in enumerate(types):
            li = jnp.int32(layer)
            slide = kind == "sliding_attention"
            kbuf = jnp.zeros((width, kvh, hd), jnp.float32)
            vbuf = jnp.zeros((width, kvh, hd), jnp.float32)
            for b in range(blocks):
                xs[b], kbuf, vbuf = _attention_rows(
                    subset, xs[b], kbuf, vbuf, li, jnp.int32(b * rows),
                    key=key, span=span if slide else None,
                    rope=slide or bool(rope_full), gate=bool(gate))
            del kbuf, vbuf
            for b in range(blocks):
                xs[b] = (_dense_ffn_rows(subset, xs[b], li, key=key)
                         if layer < n_dense else _expert_ffn_rows(
                             subset, xs[b], li, jnp.int32(layer - n_dense),
                             key=key, route=route))
        out = np.zeros((n, table.shape[0]), np.float32)
        first = 0 if head_rows is None else max(0, n - int(head_rows))
        for b in range(first // rows, blocks):
            lg = _head(params["model.norm.weight"], params["lm_head.weight"],
                       xs[b], eps=key[9])
            if lg.dtype != jnp.float32:
                raise TypeError(f"the reference ran in {lg.dtype}, not "
                                f"float32")
            take = min(rows, n - b * rows)
            out[b * rows:b * rows + take] = np.asarray(lg[:take])
    return out
