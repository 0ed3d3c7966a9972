"""The ``nemotron_h`` decoder (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B), plain:
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``, with
no kernel, no cache, no batching, no grouped dispatch and no chunked scan: the
state-space recurrence is a PLAIN scan over time, one row a step. It is the
benchmark's own yardstick for ``correct`` and calls nothing of the program under
test; it only reads the weights by the names ``model.raw_params()`` gives them
(stacked a KIND of sublayer: ``model.mamba_layers.*``, ``model.attn_layers.*``,
``model.moe_layers.*``, every layer's norm under ``model.layers.norm``).

The decoder as published with the model (hidden 4,096; RMSNorm eps 1e-5; no bias
but the convolution's), for token t with residual x; a layer is ONE sublayer:

    x = E[token]
    for l, c in enumerate(hybrid_override_pattern):
        u = RMSNorm(x; norm_l)
        if c == "M":      # Mamba-2: 128 heads of 64, 8 groups, N = 128, K = 4
            z, xBC, dt = split(u W_in; 8192, 10240, 128)
            xBC_t = silu(sum_{j=0..3} w[j] * xBC_{t-3+j} + b)   # depthwise causal,
                                                  # inputs before the prompt = 0
            X, B, C = split(xBC; 8192, 1024, 1024); head h reads group h // 16
            d_h = softplus(dt_h + dt_bias_h); a_h = exp(d_h * (-exp(A_log_h)))
            S_h,t = a_h,t S_h,t-1 + d_h,t outer(X_h,t, B_g,t)   # [64, 128], zero
                                                  # before the prompt
            y_h,t = S_h,t C_g,t + D_h X_h,t
            y = GroupRMSNorm(y * silu(z); gain, 8 groups of 1,024)
            o = y W_out
        if c == "*":      # attention: 32 query heads, 2 K/V heads of 128
            o = causal_softmax(q k^T / sqrt(128)) v Wo          # no positional term
        if c == "E":      # latent expert layer
            s = sigmoid(u Wr), 512 scores
            sel = top22(s + e_score_correction_bias)            # the bias chooses only
            w = s[sel] / (sum(s[sel]) + 1e-20) * 5
            v = u W_down                                        # 4,096 -> 1,024
            r = sum_j w_j W2_{sel_j} relu(W1_{sel_j} v)^2       # over the HELD experts
            o = r W_up + W2_s relu(W1_s u)^2                    # shared expert reads u
        x = x + o
    logits = RMSNorm(x; norm_f) W_head

THE SHARE: the weights hold ``n_routed_experts`` of the router's
``router_experts`` experts, ids ``[held_first, held_first + n_routed_experts)``.
``sel`` and ``w`` are over all of them; ``r`` sums the pairs whose expert is
held, and what the others would add is left out, here as in the program.

ASSUMED (the configuration file lists the same under ``assumed``, with reasons):
no positional term in the attention layers; the router and the shared expert read
the full width and only the routed experts work in the latent; the recurrent state
in float32; the multi-token-prediction module is not part of the main model.

How it is computed, which changes no number: one sequence at a time; rows in
blocks of ``ROWS`` against key/value buffers of the run's fixed width, a Mamba
layer carrying its last three rows of ``xBC`` and its state ``S`` from block to
block; each weight is cast to float32 where it is used, an expert at a time; every
held expert is applied to every row of a block and kept where the row chose it.
``bias=False`` routes without the selection bias, ``carry=False`` starts every
block's convolution and recurrence from zeros (``carry="window"``: the
recurrence alone, the convolution's window is kept), ``state_dtype`` rounds ``S``
to that type after every step: negative controls, not the model.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 512
M = "model.mamba_layers."
A = "model.attn_layers."
E = "model.moe_layers."


def sizes_key(c):
    """The sizes the reference needs, hashable (a jit's static arg)."""
    return (str(c["hybrid_override_pattern"]), c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["mamba_num_heads"],
            c["mamba_head_dim"], c["n_groups"], c["ssm_state_size"],
            c["num_experts_per_tok"], bool(c["norm_topk_prob"]),
            float(c["routed_scaling_factor"]),
            float(c["layer_norm_epsilon"]), int(c.get("held_first", 0)))


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _at(w, name, i):
    """Slice ``i`` of a stacked weight, in float32."""
    return jax.lax.dynamic_index_in_dim(w[name], i, 0, keepdims=False
                                        ).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("key", "state_dtype"))
def _mamba_rows(w, x, tail, state, layer, im, key, state_dtype):
    """A Mamba-2 sublayer over one block of rows ``x`` [R, H]; ``tail``
    [K-1, C] are the last rows of ``xBC`` before the block and ``state``
    [heads, P, N] the recurrent state there (zeros at the start of the
    sequence). Returns the block, its own tail and the state after it."""
    heads, p, groups, n, eps = key[4], key[5], key[6], key[7], key[11]
    r = x.shape[0]
    inner = heads * p
    u = _rms(x, _at(w, "model.layers.norm", layer), eps)
    z, xbc, dt = jnp.split(u @ _at(w, M + "in_proj", im),
                           [inner, 2 * inner + 2 * groups * n], axis=-1)
    full = jnp.concatenate([tail, xbc], axis=0)                # [K-1+R, C]
    taps = _at(w, M + "conv_weight", im)                       # [K, C]
    conv = sum(taps[j] * full[j:j + r] for j in range(taps.shape[0]))
    xbc = jax.nn.silu(conv + _at(w, M + "conv_bias", im))
    xs = xbc[:, :inner].reshape(r, heads, p)
    rep = heads // groups
    bs = jnp.repeat(xbc[:, inner:inner + groups * n].reshape(r, groups, n),
                    rep, axis=1)                               # [R, heads, N]
    cs = jnp.repeat(xbc[:, inner + groups * n:].reshape(r, groups, n), rep,
                    axis=1)
    d = jax.nn.softplus(dt + _at(w, M + "dt_bias", im))        # [R, heads]
    decay = jnp.exp(d * -jnp.exp(_at(w, M + "A_log", im)))

    def step(s, row):
        x_t, b_t, c_t, d_t, a_t = row
        s = a_t[:, None, None] * s + (d_t[:, None] * x_t)[:, :, None] \
            * b_t[:, None, :]
        if state_dtype is not None:
            s = s.astype(state_dtype).astype(jnp.float32)
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    state, y = jax.lax.scan(step, state, (xs, bs, cs, d, decay))
    y = y + _at(w, M + "D", im)[:, None] * xs
    y = (y.reshape(r, inner) * jax.nn.silu(z)).reshape(r, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(r, inner) * _at(w, M + "norm", im)
    return x + y @ _at(w, M + "out_proj", im), full[r:], state


@functools.partial(jax.jit, static_argnames=("key",), donate_argnums=(2, 3))
def _attention_rows(w, x, kbuf, vbuf, layer, ia, start, key):
    """An attention sublayer over one block of rows ``x`` [R, H]
    (positions start .. start + R - 1); the block's keys and values go
    into the buffers [W, kvh, hd] first. No positional term."""
    nh, kvh, hd, eps = key[1], key[2], key[3], key[11]
    r = x.shape[0]
    pos = start + jnp.arange(r)
    u = _rms(x, _at(w, "model.layers.norm", layer), eps)
    q = (u @ _at(w, A + "q_proj", ia)).reshape(r, kvh, nh // kvh, hd)
    k = (u @ _at(w, A + "k_proj", ia)).reshape(r, kvh, hd)
    v = (u @ _at(w, A + "v_proj", ia)).reshape(r, kvh, hd)
    kbuf = jax.lax.dynamic_update_slice(kbuf, k, (start, 0, 0))
    vbuf = jax.lax.dynamic_update_slice(vbuf, v, (start, 0, 0))
    valid = jnp.arange(kbuf.shape[0])[None] <= pos[:, None]     # s <= t
    s = jnp.einsum("rgmd,tgd->gmrt", q, kbuf) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(valid[None, None], s, -jnp.inf), -1)
    y = jnp.einsum("gmrt,tgd->rgmd", p, vbuf).reshape(r, nh * hd)
    return x + y @ _at(w, A + "o_proj", ia), kbuf, vbuf


@functools.partial(jax.jit, static_argnames=("key", "bias"))
def _expert_rows(w, x, layer, ie, key, bias):
    top_e, norm_p, scaling, eps, first = key[8:13]
    r = x.shape[0]
    u = _rms(x, _at(w, "model.layers.norm", layer), eps)
    s = jax.nn.sigmoid(u @ _at(w, E + "router", ie))           # [R, router's]
    choose = s + _at(w, E + "e_score_correction_bias", ie) if bias else s
    top_i = jax.lax.top_k(choose, top_e)[1]
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if norm_p:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    top_w = top_w * scaling
    gates = jnp.zeros_like(s).at[jnp.arange(r)[:, None], top_i].set(top_w)
    v = u @ _at(w, E + "latent_down", ie)

    def expert(e, acc):
        def one(name):
            a_ = w[E + name]
            return jax.lax.dynamic_slice(
                a_, (ie, e, 0, 0), (1, 1) + a_.shape[2:]
            )[0, 0].astype(jnp.float32)
        y = jnp.square(jax.nn.relu(v @ one("experts_w1"))) @ one("experts_w2")
        # held expert e is the router's expert first + e
        return acc + jax.lax.dynamic_slice(gates, (0, first + e), (r, 1)) * y

    held = w[E + "experts_w1"].shape[1]
    routed = jax.lax.fori_loop(0, held, expert, jnp.zeros_like(v))
    shared = jnp.square(jax.nn.relu(u @ _at(w, E + "shared_w1", ie))) \
        @ _at(w, E + "shared_w2", ie)
    return x + routed @ _at(w, E + "latent_up", ie) + shared


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(g, head, x, eps):
    return _rms(x, g.astype(jnp.float32), eps) @ head.astype(jnp.float32)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(jnp.float32)


def row_logits(params, ids, width, c, bias=True, carry=True, rows=ROWS,
               state_dtype=None):
    """float32 logits ``[len(ids), V]`` (a numpy array) of ONE sequence
    ``ids``, computed in row blocks against buffers of the fixed ``width`` so
    that every sequence of a run shares its compiled programs. ``c``: the
    configuration's keys."""
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    key = sizes_key(c)
    pattern, _, kvh, hd, heads, p, _, n_state = key[:8]
    rows = min(rows, width)
    if width % rows or n > width:
        raise ValueError(f"width {width} must be a multiple of {rows} "
                         f"and hold {n} tokens")
    blocks = -(-n // rows)
    padded = np.zeros((blocks * rows,), np.int32)
    padded[:n] = ids
    table = params["model.embed_tokens.weight"]
    head = params["lm_head.weight"]
    subset = {k: v for k, v in params.items()
              if k not in ("model.embed_tokens.weight", "lm_head.weight")}
    taps, channels = params[M + "conv_weight"].shape[1:]
    with jax.default_matmul_precision("highest"):
        xs = [_embed(table, jnp.asarray(padded[b * rows:(b + 1) * rows]))
              for b in range(blocks)]
        im = ia = ie = 0
        for layer, kind in enumerate(pattern):
            li = jnp.int32(layer)
            if kind == "M":
                tail = jnp.zeros((taps - 1, channels), jnp.float32)
                state = jnp.zeros((heads, p, n_state), jnp.float32)
                for b in range(blocks):
                    xs[b], tail, state = _mamba_rows(
                        subset, xs[b], tail, state, li, jnp.int32(im),
                        key=key, state_dtype=state_dtype)
                    if carry is not True:       # "window": S alone goes
                        state = jnp.zeros_like(state)
                    if not carry:
                        tail = jnp.zeros_like(tail)
                im += 1
            elif kind == "*":
                kbuf = jnp.zeros((width, kvh, hd), jnp.float32)
                vbuf = jnp.zeros((width, kvh, hd), jnp.float32)
                for b in range(blocks):
                    xs[b], kbuf, vbuf = _attention_rows(
                        subset, xs[b], kbuf, vbuf, li, jnp.int32(ia),
                        jnp.int32(b * rows), key=key)
                ia += 1
            elif kind == "E":
                for b in range(blocks):
                    xs[b] = _expert_rows(subset, xs[b], li, jnp.int32(ie),
                                         key=key, bias=bool(bias))
                ie += 1
            else:
                raise ValueError(f"unknown layer kind {kind!r}")
        out = np.empty((n, head.shape[1]), np.float32)
        for b in range(blocks):
            lg = _head(params["model.norm_f.weight"], head, xs[b],
                       eps=key[11])
            if lg.dtype != jnp.float32:
                raise TypeError(f"the reference ran in {lg.dtype}, not "
                                f"float32")
            take = min(rows, n - b * rows)
            out[b * rows:b * rows + take] = np.asarray(lg[:take])
    return out


def expert_layer_shares(params, x, c, ie=0, layer=None):
    """The share test's yardstick: ``(routed, shared)`` of expert layer
    ``ie`` for rows ``x`` [R, H] of the residual stream, each [R, H]
    float32: what the HELD experts add through the up projection, and what
    the shared expert adds (every chip computes that alike)."""
    key = sizes_key(c)
    pattern = key[0]
    if layer is None:
        layer = [i for i, k in enumerate(pattern) if k == "E"][ie]
    x = jnp.asarray(x, jnp.float32)
    w = {k: v for k, v in params.items() if k.startswith(E)
         or k == "model.layers.norm"}
    with jax.default_matmul_precision("highest"):
        both = _expert_rows(w, x, jnp.int32(layer), jnp.int32(ie), key=key,
                            bias=True) - x
        u = _rms(x, _at(w, "model.layers.norm", layer), key[11])
        shared = jnp.square(jax.nn.relu(u @ _at(w, E + "shared_w1", ie))) \
            @ _at(w, E + "shared_w2", ie)
    return np.asarray(both - shared), np.asarray(shared)
