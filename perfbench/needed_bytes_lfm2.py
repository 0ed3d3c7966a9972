"""How many bytes of HBM a decode tick of the ``lfm2_moe`` decoder NEEDS, from
the model's shapes and what the tick's rows were: the numerator of
``hbm_roofline_lfm2.decode``. A count of needed bytes, not of bytes moved:
whatever implements the tick reads at least these once, so the share of the
roofline cannot pass 100%.

A tick needs:

- every weight that is not an expert's, once: a conv layer's ``in_proj``,
  ``out_proj`` and taps, an attention layer's four projections and q/k gains,
  both norms of every layer, a dense layer's three FFN matrices, an expert
  layer's router and its float32 ``expert_bias``; and the final norm and the
  head, which is the embedding table (tied) read whole;
- each expert that a live row chose in a layer, once: its three matrices;
- for each live row, the keys and values of its context in the ATTENTION
  layers (the pool has no other layer) and its convolution state, the last
  ``conv_L_cache - 1`` gated inputs, in the CONV layers.

Plain arithmetic on plain numbers, so the test checks it by hand.
"""
BF16 = 2
F32 = 4


def layer_counts(c):
    """(attention, conv, dense-FFN, expert) layers."""
    n = c["num_hidden_layers"]
    attn = sum(t == "full_attention" for t in c["layer_types"][:n])
    dense = min(c["num_dense_layers"], n)
    return attn, n - attn, dense, n - dense


def head_dim(c):
    return c["hidden_size"] // c["num_attention_heads"]


def fixed_bytes(c, item=BF16):
    """What every tick reads whatever its rows."""
    h, hd = c["hidden_size"], head_dim(c)
    nq, nkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    attn, conv, dense, moe = layer_counts(c)
    params = (c["num_hidden_layers"] * 2 * h                  # both norms
              + conv * (h * 3 * h + h * h + c["conv_L_cache"] * h)
              + attn * (2 * h * nq + 2 * h * nkv + 2 * hd)
              + dense * 3 * h * c["intermediate_size"]
              + moe * h * c["num_experts"]                    # routers
              + h * c["vocab_size"] + h)                      # head, norm
    return params * item + moe * c["num_experts"] * F32      # expert_bias


def expert_bytes(c, item=BF16):
    """One expert: its three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * item


def row_cache_bytes(c, context, item=BF16):
    """Cache and state bytes ONE live row needs over ALL layers at
    ``context`` keys: K and V in the attention layers, the convolution's
    last inputs in the conv layers."""
    attn, conv, _, _ = layer_counts(c)
    kv = 2 * c["num_key_value_heads"] * head_dim(c) * item
    state = (c["conv_L_cache"] - 1) * c["hidden_size"] * item
    return attn * context * kv + conv * state


def decode_needed_bytes(c, ticks, experts_touched, contexts, item=BF16):
    """Needed bytes of ``ticks`` decode ticks: ``experts_touched`` is the
    sum over those ticks and over expert layers of the distinct experts live
    rows chose, ``contexts`` the context length of each live row of each
    tick."""
    return (ticks * fixed_bytes(c, item)
            + experts_touched * expert_bytes(c, item)
            + sum(row_cache_bytes(c, n, item) for n in contexts))
