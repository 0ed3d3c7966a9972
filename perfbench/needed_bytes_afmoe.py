"""How many bytes of HBM a decode tick of the ``afmoe`` decoder NEEDS, from
the model's shapes and what the tick's rows were: the numerator of
``hbm_roofline_afmoe.decode``. A count of needed bytes, not of bytes moved:
whatever implements the tick reads at least these once, so the share of the
roofline cannot pass 100%.

A tick needs:

- every weight that is not a routed expert's, once: a layer's four norms, its
  attention's q, k, v, gate and output projections and q/k gains, a dense
  layer's three FFN matrices, an expert layer's router, float32
  ``expert_bias`` and shared expert; and the final norm and the untied head,
  read whole (the embedding table gives one row a slot, which is not
  counted);
- each routed expert that a live row chose in a layer, once: its three
  matrices;
- for each live row, the keys and values of its context in the FULL layers,
  and of the last ``sliding_window`` positions of it (its window) in the
  WINDOW layers.

Plain arithmetic on plain numbers, so the test checks it by hand.
"""
BF16 = 2
F32 = 4


def layer_counts(c):
    """(window, full, dense-FFN, expert) layers."""
    n = c["num_hidden_layers"]
    win = sum(t == "sliding_attention" for t in c["layer_types"][:n])
    dense = min(c["num_dense_layers"], n)
    return win, n - win, dense, n - dense


def fixed_bytes(c, item=BF16):
    """What every tick reads whatever its rows."""
    h, hd = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    _, _, dense, moe = layer_counts(c)
    params = (c["num_hidden_layers"] * (4 * h                  # four norms
                                        + 2 * h * nq + nq * h  # q, gate; o
                                        + 2 * h * nkv + 2 * hd)
              + dense * 3 * h * c["intermediate_size"]
              + moe * (h * c["num_experts"]                     # routers
                       + 3 * h * c["moe_intermediate_size"])    # shared
              + h * c["vocab_size"] + h)                        # head, norm
    return params * item + moe * c["num_experts"] * F32        # expert_bias


def expert_bytes(c, item=BF16):
    """One routed expert: its three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * item


def row_cache_bytes(c, context, item=BF16):
    """K and V bytes ONE live row needs over ALL layers at ``context``
    keys: the whole context in the full layers, its window in the window
    layers."""
    win, full, _, _ = layer_counts(c)
    kv = 2 * c["num_key_value_heads"] * c["head_dim"] * item
    return (full * context + win * min(context, c["sliding_window"])) * kv


def decode_needed_bytes(c, ticks, experts_touched, contexts, item=BF16):
    """Needed bytes of ``ticks`` decode ticks: ``experts_touched`` is the
    sum over those ticks and over expert layers of the distinct experts live
    rows chose, ``contexts`` the context length of each live row of each
    tick."""
    return (ticks * fixed_bytes(c, item)
            + experts_touched * expert_bytes(c, item)
            + sum(row_cache_bytes(c, n, item) for n in contexts))
