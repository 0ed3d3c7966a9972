"""How many bytes of HBM a decode tick of the ``nemotron_h`` decoder NEEDS, from
the model's shapes and what the tick's rows were: the numerator of
``hbm_roofline_nemotron_h.decode``. A count of needed bytes, not of bytes moved:
whatever implements the tick reads at least these once, so the share of the
roofline cannot pass 100%.

A tick needs:

- every weight that is not a routed expert's, once: a Mamba-2 layer's
  ``in_proj``, ``out_proj``, convolution taps and bias, gain, and its float32
  ``A_log``, ``D`` and ``dt_bias``; an attention layer's four projections; an
  expert layer's router and its float32 correction bias, both latent
  projections and the shared expert; every layer's norm; the final norm and the
  untied head over the vocabulary HELD here;
- each HELD expert that a live row chose in a layer, once: its two matrices;
- for each live row, the keys and values of its context in the ATTENTION layers
  (the pool has no other layer), and in each MAMBA layer its convolution window
  (the last ``conv_kernel - 1`` rows of ``xBC``) and its float32 recurrent state
  ``[heads, head_dim, state]``, each READ AND WRITTEN: a step replaces the state.

What a tick pays for the state of slots that are NOT live is not needed and is
not counted here: it lowers the share.

Plain arithmetic on plain numbers, so the test checks it by hand.
"""
BF16 = 2
F32 = 4


def layer_counts(c):
    """(Mamba-2, attention, expert) layers."""
    p = c["hybrid_override_pattern"]
    return p.count("M"), p.count("*"), p.count("E")


def conv_channels(c):
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    return inner + 2 * c["n_groups"] * c["ssm_state_size"]


def fixed_bytes(c, item=BF16):
    """What every tick reads whatever its rows."""
    h = c["hidden_size"]
    heads = c["mamba_num_heads"]
    inner, channels = heads * c["mamba_head_dim"], conv_channels(c)
    nq = c["num_attention_heads"] * c["head_dim"]
    nkv = c["num_key_value_heads"] * c["head_dim"]
    router = c.get("router_experts") or c["n_routed_experts"]
    mamba, attn, moe = layer_counts(c)
    params = (len(c["hybrid_override_pattern"]) * h               # norms
              + mamba * (h * (inner + channels + heads) + inner * h
                         + (c["conv_kernel"] + 1) * channels + inner)
              + attn * (2 * h * nq + 2 * h * nkv)
              + moe * (h * router + 2 * h * c["moe_latent_size"]
                       + 2 * h * c["moe_shared_expert_intermediate_size"])
              + h * c["vocab_size"] + h)                          # head, norm
    return params * item + (mamba * 3 * heads + moe * router) * F32


def expert_bytes(c, item=BF16):
    """One routed expert: its two matrices, in the latent."""
    return 2 * c["moe_latent_size"] * c["moe_intermediate_size"] * item


def row_state_bytes(c, item=BF16):
    """The per-slot state ONE live row reads and writes in ONE Mamba-2
    layer: the convolution window and the float32 recurrent state."""
    window = (c["conv_kernel"] - 1) * conv_channels(c) * item
    state = (c["mamba_num_heads"] * c["mamba_head_dim"]
             * c["ssm_state_size"] * F32)
    return 2 * (window + state)


def row_cache_bytes(c, context, item=BF16):
    """Cache and state bytes ONE live row needs over ALL layers at
    ``context`` keys."""
    mamba, attn, _ = layer_counts(c)
    kv = 2 * c["num_key_value_heads"] * c["head_dim"] * item
    return attn * context * kv + mamba * row_state_bytes(c, item)


def decode_needed_bytes(c, ticks, experts_touched, contexts, item=BF16):
    """Needed bytes of ``ticks`` decode ticks: ``experts_touched`` is the
    sum over those ticks and over expert layers of the distinct HELD experts
    live rows chose, ``contexts`` the context length of each live row of each
    tick."""
    return (ticks * fixed_bytes(c, item)
            + experts_touched * expert_bytes(c, item)
            + sum(row_cache_bytes(c, n, item) for n in contexts))
