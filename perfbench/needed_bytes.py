"""How many bytes of HBM a decode tick NEEDS, from the model's shapes and
what the tick's rows were: the numerator of ``hbm_roofline.decode``. A count
of needed bytes, not of bytes moved: whatever implements the tick reads at
least these once, so the share of the roofline cannot pass 100%.

A tick of a routed-expert, key-selecting decoder needs:

- every weight that is not an expert's, once: a layer's attention, norm,
  indexer and router matrices, and the final norm and the output head (the
  embedding table gives one row a slot, which is not counted);
- each expert that a live row chose in a layer, once: its three matrices;
- for each live row and layer, the keys and values of the positions attention
  reads (``min(context, topk)`` of them, all kv heads) and the indexer key of
  EVERY position in its context (the scores rank them all).

Plain arithmetic on plain numbers, so the test checks it by hand.
"""
BF16 = 2


def per_layer_fixed_params(c):
    """Parameters of one layer outside its experts."""
    h, hd = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    sa = c["sa_config"]
    attention = h * nq * 2 + h * nkv * 2              # q, o; k, v
    norms = 2 * h + 2 * hd                            # two layer norms, q/k
    indexer = h * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                   + sa["indexer_head_dim"] + sa["indexer_num_heads"])
    return attention + norms + indexer + h * c["num_experts"]


def expert_bytes(c, item=BF16):
    """One expert: gate, up and down matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * item


def fixed_bytes(c, item=BF16):
    """What every tick reads whatever its rows: the non-expert weights of
    every layer, the final norm and the head."""
    head = c["hidden_size"] * c["vocab_size"] + c["hidden_size"]
    return (c["num_hidden_layers"] * per_layer_fixed_params(c) + head) * item


def row_cache_bytes(c, context, item=BF16):
    """Cache bytes ONE live row needs in ONE layer at ``context`` keys."""
    sa = c["sa_config"]
    kv = 2 * c["num_key_value_heads"] * c["head_dim"] * item
    return (min(context, sa["topk"]) * kv
            + context * sa["indexer_head_dim"] * item)


def decode_needed_bytes(c, ticks, experts_touched, contexts, item=BF16):
    """Needed bytes of ``ticks`` decode ticks: ``experts_touched`` is the
    sum over those ticks and over layers of the distinct experts live rows
    chose, ``contexts`` the context length of each live row of each tick."""
    cache = c["num_hidden_layers"] * sum(row_cache_bytes(c, n, item)
                                         for n in contexts)
    return (ticks * fixed_bytes(c, item)
            + experts_touched * expert_bytes(c, item) + cache)


def roofline_percent(needed_bytes, device_seconds, hbm_bytes_per_s):
    return 100.0 * needed_bytes / device_seconds / hbm_bytes_per_s
