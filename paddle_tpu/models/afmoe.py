"""The ``afmoe`` decoder (arcee-ai's Trinity-Mini 26B-A3B): WINDOW and FULL
attention layers in one model. Every layer is grouped-query attention with
per-head q/k RMSNorm and a sigmoid gate on its output; a ``sliding_attention``
layer sees the last ``sliding_window`` keys and carries a rotary embedding, a
``full_attention`` layer (one in every ``global_attn_every_n_layers``) sees
the whole context and carries NO positional term. Each sublayer sits between
two RMSNorms (input, post-attention, pre-MLP, post-MLP). The FFN is a dense
SwiGLU in the first ``num_dense_layers`` layers and, after them,
``num_experts`` SwiGLU experts routed top-``num_experts_per_tok`` by a sigmoid
score with a selection-only ``expert_bias``, beside ``num_shared_experts``
shared SwiGLU expert of the same width. The embedding is scaled by
``sqrt(hidden_size)`` (``mup_enabled``); the head is untied.

For token t with residual x (every RMSNorm at ``rms_norm_eps``):

    x0 = E[ids] * sqrt(H)
    h  = RMSNorm_in(x); q, k, v = h Wq, h Wk, h Wv; q, k = RMSNorm_q(q), RMSNorm_k(k)
    sliding layer: q, k = RoPE(q, k); key j visible to query p iff p - j < window
    x  = x + RMSNorm_post_attn((softmax(q k^T / sqrt(d)) v * sigmoid(h Wg)) Wo)
    u  = RMSNorm_pre_mlp(x)
    f  = SwiGLU(u)                                   dense layers
    f  = SwiGLU_shared(u) + sum_k w_k SwiGLU_{e_k}(u) expert layers:
         s = sigmoid(u Wr); e = top_k(s + b); w = s[e] / (sum s[e] + 1e-20) * route_scale
    x  = x + RMSNorm_post_mlp(f)
    logits = RMSNorm_final(x) W_head

As in ``lfm2.py`` the block weights are the model's OWN parameters STACKED a
KIND of sublayer (``model.layers.*``: the four norms and the attention of
every layer; ``model.dense_layers.*``; ``model.moe_layers.*``): the decode
bundle's layer loop indexes these very arrays by a layer spec
(``generation._layer_spec``), so serving holds the weights once. The paged
backend keeps the full layers in the page pool and each window layer in a
RING of pages a slot (``generation.ring_pages``), a pool of its own.
"""
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import unwrap, wrap
from paddle_tpu.models.generation import GenerationMixin
from paddle_tpu.models.keye_vl import _Holder

__all__ = ["AfmoeConfig", "AfmoeForCausalLM", "afmoe_tiny"]

_PUBLISHED_TYPES = ("sliding_attention",) * 3 + ("full_attention",)


@dataclass
class AfmoeConfig:
    """The published ``config.json`` keys the program reads, as named
    there; the properties below are the names the llama-family decode
    builder reads."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    num_dense_layers: int = 2
    layer_types: tuple = field(default_factory=lambda: _PUBLISHED_TYPES * 8)
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    mup_enabled: bool = True
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every layer: "
                             f"{len(self.layer_types)} of "
                             f"{self.num_hidden_layers}")
        unknown = set(self.layer_types) - {"sliding_attention",
                                           "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if (self.score_func != "sigmoid" or self.tie_word_embeddings
                or self.num_shared_experts != 1):
            # no-roadmap: the published model's; a config check
            raise NotImplementedError(
                "afmoe is built with a sigmoid router, one shared expert "
                "and an untied head")

    # -- what models/generation.py's llama-family builder reads
    num_heads = property(lambda self: self.num_attention_heads)
    num_kv_heads = property(lambda self: self.num_key_value_heads)
    num_layers = property(lambda self: self.num_hidden_layers)
    rms_eps = property(lambda self: self.rms_norm_eps)
    top_k = property(lambda self: self.num_experts_per_tok)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    norm_topk_prob = property(lambda self: self.route_norm)
    routed_scaling_factor = property(lambda self: self.route_scale)
    embed_scale = property(lambda self: math.sqrt(self.hidden_size)
                           if self.mup_enabled else None)
    qk_norm = True
    router_score = "sigmoid"       # with ``expert_bias`` choosing only
    router_eps = 1e-20


def layer_counts(cfg):
    """(window, full, dense-FFN, expert) layers of ``cfg``."""
    win = sum(t == "sliding_attention" for t in cfg.layer_types)
    dense = min(cfg.num_dense_layers, cfg.num_hidden_layers)
    return (win, cfg.num_hidden_layers - win, dense,
            cfg.num_hidden_layers - dense)


def param_shapes(cfg):
    """``raw_params()`` name -> shape, without building anything."""
    c = cfg
    h, hd, n = c.hidden_size, c.head_dim, c.num_hidden_layers
    nq, nkv = c.num_attention_heads * hd, c.num_key_value_heads * hd
    e, f, i = c.num_experts, c.moe_intermediate_size, c.intermediate_size
    _, _, ld, le = layer_counts(c)
    return {
        "model.embed_tokens.weight": (c.vocab_size, h),
        "model.norm.weight": (h,),
        "lm_head.weight": (h, c.vocab_size),
        "model.layers.input_layernorm": (n, h),
        "model.layers.post_attention_layernorm": (n, h),
        "model.layers.pre_mlp_layernorm": (n, h),
        "model.layers.post_mlp_layernorm": (n, h),
        "model.layers.q_proj": (n, h, nq),
        "model.layers.k_proj": (n, h, nkv),
        "model.layers.v_proj": (n, h, nkv),
        "model.layers.gate_proj": (n, h, nq),
        "model.layers.o_proj": (n, nq, h),
        "model.layers.q_norm": (n, hd),
        "model.layers.k_norm": (n, hd),
        "model.dense_layers.gate_proj": (ld, h, i),
        "model.dense_layers.up_proj": (ld, h, i),
        "model.dense_layers.down_proj": (ld, i, h),
        "model.moe_layers.router": (le, h, e),
        "model.moe_layers.expert_bias": (le, e),
        "model.moe_layers.experts_gate_proj": (le, e, h, f),
        "model.moe_layers.experts_up_proj": (le, e, h, f),
        "model.moe_layers.experts_down_proj": (le, e, f, h),
        "model.moe_layers.shared_gate_proj": (le, h, f),
        "model.moe_layers.shared_up_proj": (le, h, f),
        "model.moe_layers.shared_down_proj": (le, f, h),
    }


_GAINS = ("norm.weight", "_layernorm", "q_norm", "k_norm")
EXPERT_BIAS = "model.moe_layers.expert_bias"

# the decode bundle's leaf names for the stacked parameters
_BUNDLE_LEAVES = {
    "ln1": "model.layers.input_layernorm",
    "pa": "model.layers.post_attention_layernorm",
    "ln2": "model.layers.pre_mlp_layernorm",
    "pm": "model.layers.post_mlp_layernorm",
    "wq": "model.layers.q_proj", "wk": "model.layers.k_proj",
    "wv": "model.layers.v_proj", "wo": "model.layers.o_proj",
    "ag": "model.layers.gate_proj",
    "qn": "model.layers.q_norm", "kn": "model.layers.k_norm",
    "dg": "model.dense_layers.gate_proj", "du": "model.dense_layers.up_proj",
    "dd": "model.dense_layers.down_proj",
    "router": "model.moe_layers.router", "rbias": EXPERT_BIAS,
    "wg": "model.moe_layers.experts_gate_proj",
    "wu": "model.moe_layers.experts_up_proj",
    "wd": "model.moe_layers.experts_down_proj",
    "xg": "model.moe_layers.shared_gate_proj",
    "xu": "model.moe_layers.shared_up_proj",
    "xd": "model.moe_layers.shared_down_proj"}


def is_gain(name):
    """Whether ``name`` (a ``raw_params()`` key) is an RMSNorm gain
    (initialised to 1) and not a matrix."""
    return name.endswith(_GAINS)


def param_dtype(cfg, name):
    """``expert_bias`` is added to float32 scores and stays float32."""
    return jnp.float32 if name == EXPERT_BIAS else jnp.dtype(cfg.dtype)


def init_weights(cfg, seed=0, key=None, scale=None):
    """Every parameter in ONE jitted call, in the type it is served in:
    N(0, ``initializer_range``) matrices (times ``scale[name]`` where
    given), gains 1, and a NON-zero ``expert_bias`` from the same draw,
    so that choosing and weighing experts differ. ``key``: a PRNG key to
    draw from in place of ``PRNGKey(seed)``."""
    shapes = param_shapes(cfg)
    scale = dict(scale or {})
    unknown = sorted(set(scale) - set(shapes))
    if unknown:
        raise KeyError(f"scale names no parameter: {unknown}")
    names = sorted(shapes)

    @jax.jit
    def make(key):
        return {n: (jnp.ones(shapes[n], param_dtype(cfg, n)) if is_gain(n)
                    else cfg.initializer_range * scale.get(n, 1.0)
                    * jax.random.normal(k, shapes[n], param_dtype(cfg, n)))
                for k, n in zip(jax.random.split(key, len(names)), names)}

    return make(jax.random.PRNGKey(seed) if key is None else key)


class AfmoeModel(nn.Layer):
    def __init__(self, cfg, weights):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Holder(
            {"weight": weights["model.embed_tokens.weight"]})
        self.norm = _Holder({"weight": weights["model.norm.weight"]})
        for group in ("layers", "dense_layers", "moe_layers"):
            pre = f"model.{group}."
            setattr(self, group, _Holder(
                {n[len(pre):]: a for n, a in weights.items()
                 if n.startswith(pre)}))


class AfmoeForCausalLM(nn.Layer, GenerationMixin):
    """``weights``: a ``{raw_params() name: array}`` tree to adopt as
    the parameters (no copy); None draws ``init_weights(cfg, seed)``."""
    decode_family = "llama"    # generation.py picks the bundle builder

    def __init__(self, cfg: AfmoeConfig, weights=None, seed=0):
        super().__init__()
        self.cfg = cfg
        if weights is None:
            weights = init_weights(cfg, seed)
        want = param_shapes(cfg)
        got = {n: tuple(a.shape) for n, a in weights.items()}
        if got != want:
            bad = sorted(n for n in set(got) | set(want)
                         if got.get(n) != want.get(n))
            raise ValueError(f"weights do not fit the config: {bad[:4]}")
        self.model = AfmoeModel(cfg, weights)
        self.lm_head = _Holder({"weight": weights["lm_head.weight"]})
        self._dtype = cfg.dtype

    def forward(self, input_ids):
        """Logits [B, T, V] of whole sequences: one prefill of the
        model's own decode program over a fresh dense cache."""
        ids = unwrap(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        bundle = self._decode_bundle(int(ids.shape[1]))
        init_caches, _, _, head_fn, prefill = bundle
        x = self._prefill_embed(jnp.asarray(ids), bundle)
        out, _ = prefill(x, init_caches(ids.shape[0]), jnp.int32(0))
        return wrap(head_fn(out))

    def decode_weights(self):
        """The llama-family decode bundle's weight tree: this model's
        own stacked arrays under the bundle's leaf names."""
        raw = self.raw_params()
        tree = {"table": raw["model.embed_tokens.weight"],
                "norm": raw["model.norm.weight"],
                "head": raw["lm_head.weight"]}
        tree.update({leaf: raw[name]
                     for leaf, name in _BUNDLE_LEAVES.items()})
        return tree


def afmoe_tiny(**kw):
    """CPU-test sizes with every kind of layer: 6 layers (2 dense window
    layers, then ``window full window window`` with experts), hidden 64,
    4 q / 2 kv heads of 16, 8 experts top-2 of width 32 and a shared
    one, dense width 96, a window of 16."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 96)
    kw.setdefault("moe_intermediate_size", 32)
    kw.setdefault("num_hidden_layers", 6)
    kw.setdefault("layer_types", ("sliding_attention",) * 3
                  + ("full_attention",) + ("sliding_attention",) * 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("num_experts", 8)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("sliding_window", 16)
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("dtype", "float32")
    return AfmoeConfig(**kw)
