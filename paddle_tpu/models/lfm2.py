"""The ``lfm2_moe`` decoder (LiquidAI/LFM2-24B-A2B): a hybrid whose
layers are not alike. A layer's MIXER is a gated short convolution
(``conv``: a depthwise causal convolution of ``conv_L_cache`` taps
between two gates, whose only state is the last ``conv_L_cache - 1``
gated inputs of a sequence) or grouped-query attention with per-head
q/k RMSNorm and a rotary embedding (``full_attention``), in the order
``layer_types`` gives; its FFN is a dense SwiGLU in the first
``num_dense_layers`` layers and, after them, ``num_experts`` small
SwiGLU experts routed top-``num_experts_per_tok`` by a sigmoid score
with a selection-only bias. The embedding is tied to the output head.

As in ``keye_vl.py`` the block weights are the model's OWN parameters
STACKED, here a stack a KIND of sublayer (``model.conv_layers.*`` over
the conv layers, ``model.attn_layers.*`` over the attention layers,
``model.dense_layers.*``, ``model.moe_layers.*``; the two norms of every
layer under ``model.layers.*``): the decode bundle's layer loop indexes
these very arrays by a layer spec (``generation._layer_spec``), so
serving holds the weights once, and the page pool has as many layers as
the model has ATTENTION layers.
"""
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import dispatch, unwrap
from paddle_tpu.models.generation import GenerationMixin, _rms
from paddle_tpu.models.keye_vl import _Holder, _rotate, mrope_tables
from paddle_tpu.ops.routed_ffn import route_topk, routed_ffn
from paddle_tpu.ops.short_conv import short_conv

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM", "lfm2_tiny"]

_PUBLISHED_TYPES = ("conv", "conv") + ("full_attention", "conv", "conv",
                                       "conv") * 9 + ("full_attention",
                                                      "conv")


@dataclass
class Lfm2MoeConfig:
    """The published ``config.json`` keys, as named there; the
    properties below are the names the llama-family decode builder
    reads."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 64
    num_experts_per_tok: int = 4
    num_dense_layers: int = 2
    layer_types: tuple = _PUBLISHED_TYPES
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    rope_parameters: dict = field(default_factory=lambda: {
        "rope_theta": 1000000, "rope_type": "default"})
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every layer: "
                             f"{len(self.layer_types)} of "
                             f"{self.num_hidden_layers}")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.conv_bias or not self.tie_word_embeddings:
            # no-roadmap: the published model has neither; a config check
            raise NotImplementedError("lfm2_moe is built without a conv "
                                      "bias and with a tied head")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into the heads")

    # -- what models/generation.py's llama-family builder reads
    num_heads = property(lambda self: self.num_attention_heads)
    num_kv_heads = property(lambda self: self.num_key_value_heads)
    head_dim = property(lambda self: self.hidden_size
                        // self.num_attention_heads)
    num_layers = property(lambda self: self.num_hidden_layers)
    rms_eps = property(lambda self: self.norm_eps)
    top_k = property(lambda self: self.num_experts_per_tok)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    rope_theta = property(lambda self: float(
        self.rope_parameters["rope_theta"]))
    qk_norm = True
    router_score = "sigmoid"       # with ``expert_bias`` choosing only


def layer_counts(cfg):
    """(attention, conv, dense-FFN, expert) layers of ``cfg``."""
    attn = sum(t == "full_attention" for t in cfg.layer_types)
    dense = min(cfg.num_dense_layers, cfg.num_hidden_layers)
    return (attn, cfg.num_hidden_layers - attn, dense,
            cfg.num_hidden_layers - dense)


def param_shapes(cfg):
    """``raw_params()`` name -> shape, without building anything."""
    c = cfg
    h, hd, taps = c.hidden_size, c.head_dim, c.conv_L_cache
    nq, nkv = c.num_attention_heads * hd, c.num_key_value_heads * hd
    e, f, i = c.num_experts, c.moe_intermediate_size, c.intermediate_size
    la, lc, ld, le = layer_counts(c)
    return {
        "model.embed_tokens.weight": (c.vocab_size, h),
        "model.embedding_norm.weight": (h,),
        "model.layers.operator_norm": (c.num_hidden_layers, h),
        "model.layers.ffn_norm": (c.num_hidden_layers, h),
        "model.conv_layers.in_proj": (lc, h, 3 * h),
        "model.conv_layers.conv": (lc, taps, h),   # tap j of channel d
        "model.conv_layers.out_proj": (lc, h, h),
        "model.attn_layers.q_proj": (la, h, nq),
        "model.attn_layers.k_proj": (la, h, nkv),
        "model.attn_layers.v_proj": (la, h, nkv),
        "model.attn_layers.out_proj": (la, nq, h),
        "model.attn_layers.q_layernorm": (la, hd),
        "model.attn_layers.k_layernorm": (la, hd),
        "model.dense_layers.w1": (ld, h, i),
        "model.dense_layers.w3": (ld, h, i),
        "model.dense_layers.w2": (ld, i, h),
        "model.moe_layers.router": (le, h, e),
        "model.moe_layers.expert_bias": (le, e),
        "model.moe_layers.experts_w1": (le, e, h, f),
        "model.moe_layers.experts_w3": (le, e, h, f),
        "model.moe_layers.experts_w2": (le, e, f, h),
    }


_GAINS = ("embedding_norm.weight", "operator_norm", "ffn_norm",
          "q_layernorm", "k_layernorm")
EXPERT_BIAS = "model.moe_layers.expert_bias"

# the decode bundle's leaf names for the stacked parameters
_BUNDLE_LEAVES = {
    "ln1": "model.layers.operator_norm", "ln2": "model.layers.ffn_norm",
    "ci": "model.conv_layers.in_proj", "cw": "model.conv_layers.conv",
    "co": "model.conv_layers.out_proj",
    "wq": "model.attn_layers.q_proj", "wk": "model.attn_layers.k_proj",
    "wv": "model.attn_layers.v_proj", "wo": "model.attn_layers.out_proj",
    "qn": "model.attn_layers.q_layernorm",
    "kn": "model.attn_layers.k_layernorm",
    "dg": "model.dense_layers.w1", "du": "model.dense_layers.w3",
    "dd": "model.dense_layers.w2",
    "router": "model.moe_layers.router", "rbias": EXPERT_BIAS,
    "wg": "model.moe_layers.experts_w1", "wu": "model.moe_layers.experts_w3",
    "wd": "model.moe_layers.experts_w2"}


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


def _forward(cfg, ids, w):
    """Full (uncached) forward over raw arrays ``w`` (the
    ``raw_params()`` names): logits [B, T, V] float32. A Python loop
    over the layers, each indexing its kind's stack."""
    b, t = ids.shape
    nh, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    eps = cfg.norm_eps
    # the plain rotary embedding: one position part turns every frequency
    cos, sin = mrope_tables(jnp.broadcast_to(jnp.arange(t), (1, b, t)), hd,
                            cfg.rope_theta, [hd // 2])
    causal = jnp.tril(jnp.ones((t, t), bool))
    x = w["model.embed_tokens.weight"][ids]
    ia = ic = 0
    for l, kind in enumerate(cfg.layer_types):
        h = _rms(x, w["model.layers.operator_norm"][l], eps)
        if kind == "conv":
            at = lambda n: w["model.conv_layers." + n][ic]
            gb, gc, gx = jnp.split(h @ at("in_proj"), 3, axis=-1)
            c, _ = short_conv(gb * gx, at("conv"))
            y = (gc * c) @ at("out_proj")
            ic += 1
        else:
            at = lambda n: w["model.attn_layers." + n][ia]
            q = _rotate(_rms((h @ at("q_proj")).reshape(b, t, nh, hd),
                             at("q_layernorm"), eps), cos, sin)
            k = _rotate(_rms((h @ at("k_proj")).reshape(b, t, kvh, hd),
                             at("k_layernorm"), eps), cos, sin)
            v = (h @ at("v_proj")).reshape(b, t, kvh, hd)
            qg = q.reshape(b, t, kvh, nh // kvh, hd)
            s = jnp.einsum("btgmd,bsgd->bgmts", qg, k).astype(
                jnp.float32) / np.sqrt(hd)
            p = jax.nn.softmax(jnp.where(causal, s, -1e30), -1).astype(
                v.dtype)
            y = jnp.einsum("bgmts,bsgd->btgmd", p, v).reshape(
                b, t, nh * hd) @ at("out_proj")
            ia += 1
        x = x + y
        g = _rms(x, w["model.layers.ffn_norm"][l], eps)
        if l < cfg.num_dense_layers:
            at = lambda n: w["model.dense_layers." + n][l]
            f = (jax.nn.silu(g @ at("w1")) * (g @ at("w3"))) @ at("w2")
        else:
            le = l - cfg.num_dense_layers
            moe = lambda n: w["model.moe_layers." + n]
            rows = g.reshape(b * t, -1)
            idx, gate = route_topk(
                rows, moe("router")[le], cfg.num_experts_per_tok,
                normalize=cfg.norm_topk_prob, score="sigmoid",
                bias=moe("expert_bias")[le] if cfg.use_expert_bias
                else None, scale=cfg.routed_scaling_factor)
            f = routed_ffn(rows, idx, gate, moe("experts_w1"),
                           moe("experts_w3"), moe("experts_w2"),
                           layer=le).reshape(x.shape)
        x = x + f
    out = _rms(x, w["model.embedding_norm.weight"], eps)
    return (out @ w["model.embed_tokens.weight"].T).astype(jnp.float32)


class Lfm2MoeModel(nn.Layer):
    def __init__(self, cfg, weights):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Holder(
            {"weight": weights["model.embed_tokens.weight"]})
        self.embedding_norm = _Holder(
            {"weight": weights["model.embedding_norm.weight"]})
        for group in ("layers", "conv_layers", "attn_layers",
                      "dense_layers", "moe_layers"):
            pre = f"model.{group}."
            setattr(self, group, _Holder(
                {n[len(pre):]: a for n, a in weights.items()
                 if n.startswith(pre)}))


class Lfm2MoeForCausalLM(nn.Layer, GenerationMixin):
    """``weights``: a ``{raw_params() name: array}`` tree to adopt as
    the parameters (no copy); None draws ``init_weights(cfg, seed)``."""
    decode_family = "llama"    # generation.py picks the bundle builder

    def __init__(self, cfg: Lfm2MoeConfig, weights=None, seed=0):
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
        self.model = Lfm2MoeModel(cfg, weights)
        self._dtype = cfg.dtype

    def forward(self, input_ids):
        """Logits [B, T, V] of whole sequences (no cache)."""
        ids = unwrap(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        names = sorted(param_shapes(self.cfg))
        params = dict(self.named_parameters())
        cfg = self.cfg

        def fn(ids, *arrays):
            return _forward(cfg, ids, dict(zip(names, arrays)))

        return dispatch(fn, ids, *[params[n] for n in names],
                        nondiff_args=(0,), name="lfm2_moe_forward")

    def decode_weights(self):
        """The llama-family decode bundle's weight tree: this model's
        own stacked arrays under the bundle's leaf names (the head is
        the embedding table, transposed where it is used)."""
        raw = self.raw_params()
        tree = {"table": raw["model.embed_tokens.weight"],
                "norm": raw["model.embedding_norm.weight"]}
        tree.update({leaf: raw[name]
                     for leaf, name in _BUNDLE_LEAVES.items()})
        return tree


def lfm2_tiny(**kw):
    """CPU-test sizes with every kind of layer: 6 layers (2 dense conv
    layers, then ``attn conv attn conv`` with experts), hidden 64, 4 q /
    2 kv heads of 16, 8 experts top-2 of width 32, dense width 96."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 96)
    kw.setdefault("moe_intermediate_size", 32)
    kw.setdefault("num_hidden_layers", 6)
    kw.setdefault("layer_types", ("conv", "conv", "full_attention", "conv",
                                  "full_attention", "conv"))
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("num_experts", 8)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("num_dense_layers", 2)
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("dtype", "float32")
    return Lfm2MoeConfig(**kw)
