"""The language model of Keye-VL-2.0 (Kwai-Keye/Keye-VL-2.0-30B-A3B): a
Qwen3-MoE-shaped decoder — GQA with per-head q/k RMSNorm and a
multimodal rotary embedding, every layer an expert layer of 128 small
SwiGLU experts routed top-8 — whose attention reads only the keys a
learned indexer selects (``sa_config``: a lightning indexer in
DeepSeek-V3.2's form, top 2,048 keys a query).

The vision tower is not here: visual tokens reach the language model
as embeddings with 3-part (time, height, width) positions, which the
forward takes; served text has the three parts equal, which is the
plain rotary embedding.

Unlike the other models the block weights are the model's OWN
parameters STACKED over layers (``model.layers.<leaf>`` is ``[layers,
...]``): the decode bundle scans these very arrays, so serving holds
the weights once (30B-A3B's one-chip stage is 8.75 GB in bfloat16).
For the same reason the parameters are created in ``cfg.dtype``
directly, or adopted from ``weights=`` without a copy.
"""
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Parameter, dispatch, unwrap
from paddle_tpu.models.generation import GenerationMixin, _rms
from paddle_tpu.ops.key_selection import select_and_attend
from paddle_tpu.ops.routed_ffn import route_topk, routed_ffn

__all__ = ["KeyeVL2Config", "KeyeVL2Model", "KeyeVL2ForCausalLM",
           "keye_vl2_tiny"]


@dataclass
class KeyeVL2Config:
    """The published ``config.json`` keys (language model), as named
    there; the properties below are the names the llama-family decode
    builder reads."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    rope_scaling: dict = field(default_factory=lambda: {
        "mrope_section": [16, 24, 24], "rope_type": "default"})
    sa_config: dict = field(default_factory=lambda: {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048})
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        if sum(self.rope_scaling["mrope_section"]) * 2 != self.head_dim:
            raise ValueError("mrope_section must split head_dim / 2 "
                             "rotary frequencies")
        if self.sa_config["indexer_num_kv_heads"] != 1:
            # no-roadmap: the published model has one; a config check
            raise NotImplementedError("the indexer has one shared key "
                                      "head")

    # -- what models/generation.py's llama-family builder reads
    num_heads = property(lambda self: self.num_attention_heads)
    num_kv_heads = property(lambda self: self.num_key_value_heads)
    num_layers = property(lambda self: self.num_hidden_layers)
    rms_eps = property(lambda self: self.rms_norm_eps)
    top_k = property(lambda self: self.num_experts_per_tok)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    qk_norm = True
    indexer = property(lambda self: (self.sa_config["indexer_num_heads"],
                                     self.sa_config["indexer_head_dim"],
                                     self.sa_config["topk"]))


def _layer_shapes(c):
    h, hd = c.hidden_size, c.head_dim
    nq, nkv = c.num_attention_heads * hd, c.num_key_value_heads * hd
    ni, di = c.sa_config["indexer_num_heads"], c.sa_config["indexer_head_dim"]
    e, f = c.num_experts, c.moe_intermediate_size
    return {"input_layernorm": (h,), "post_attention_layernorm": (h,),
            "q_proj": (h, nq), "k_proj": (h, nkv), "v_proj": (h, nkv),
            "o_proj": (nq, h), "q_norm": (hd,), "k_norm": (hd,),
            "indexer_wq": (h, ni * di), "indexer_wk": (h, di),
            "indexer_weights_proj": (h, ni), "router": (h, e),
            "experts_gate_proj": (e, h, f), "experts_up_proj": (e, h, f),
            "experts_down_proj": (e, f, h)}


# the decode bundle's leaf names for the stacked block parameters
_BUNDLE_LEAVES = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm",
                  "wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
                  "wo": "o_proj", "qn": "q_norm", "kn": "k_norm",
                  "iq": "indexer_wq", "ik": "indexer_wk",
                  "iw": "indexer_weights_proj", "router": "router",
                  "wg": "experts_gate_proj", "wu": "experts_up_proj",
                  "wd": "experts_down_proj"}
_GAINS = ("input_layernorm", "post_attention_layernorm", "q_norm",
          "k_norm")


def param_shapes(cfg):
    """``raw_params()`` name -> shape, without building anything."""
    L = cfg.num_hidden_layers
    shapes = {"model.embed_tokens.weight": (cfg.vocab_size, cfg.hidden_size),
              "model.norm.weight": (cfg.hidden_size,),
              "lm_head.weight": (cfg.hidden_size, cfg.vocab_size)}
    shapes.update({"model.layers." + n: (L,) + s
                   for n, s in _layer_shapes(cfg).items()})
    return shapes


def is_gain(name):
    """Whether ``name`` (a ``raw_params()`` key) is an RMSNorm gain
    (initialised to 1) and not a matrix."""
    return name == "model.norm.weight" or name.rsplit(".", 1)[-1] in _GAINS


def init_weights(cfg, seed=0, key=None):
    """Every parameter in ONE jitted call, in ``cfg.dtype``: N(0,
    ``initializer_range``) matrices, gains 1. ``key``: a PRNG key to
    draw from in place of ``PRNGKey(seed)``."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)
    dtype = jnp.dtype(cfg.dtype)

    @jax.jit
    def make(key):
        return {n: (jnp.ones(shapes[n], dtype) if is_gain(n) else
                    cfg.initializer_range
                    * jax.random.normal(k, shapes[n], dtype))
                for k, n in zip(jax.random.split(key, len(names)), names)}

    return make(jax.random.PRNGKey(seed) if key is None else key)


class _Holder(nn.Layer):
    """A sublayer that holds given arrays as parameters."""

    def __init__(self, arrays):
        super().__init__()
        for name, a in arrays.items():
            self.add_parameter(name, Parameter(a))


def mrope_tables(position_ids, head_dim, theta, sections):
    """cos/sin ``[B, T, head_dim / 2]`` of the multimodal rotary
    embedding: frequency ``i`` turns with the position component its
    section names (time, height, width). ``position_ids`` [3, B, T]."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    part = np.repeat(np.arange(len(sections)), sections)      # [hd / 2]
    ang = jnp.moveaxis(position_ids.astype(jnp.float32), 0, -1
                       )[..., part] * inv                     # [B, T, hd/2]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    """Rotate-half rope: x [B, T, heads, D], cos/sin [B, T, D / 2]."""
    d2 = x.shape[-1] // 2
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = x[..., :d2].astype(jnp.float32), x[..., d2:].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _forward(cfg, ids, position_ids, w):
    """Full (uncached) forward over raw arrays ``w`` (the
    ``raw_params()`` names): logits [B, T, V] float32."""
    b, t = ids.shape
    nh, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    ni, di, topk = cfg.indexer
    eps = cfg.rms_norm_eps
    cos, sin = mrope_tables(position_ids, hd, cfg.rope_theta,
                            cfg.rope_scaling["mrope_section"])
    icos, isin = mrope_tables(position_ids[:1], di, cfg.rope_theta,
                              [di // 2])                      # time only
    scale = 1.0 / np.sqrt(hd)
    x = w["model.embed_tokens.weight"][ids]
    blocks = {n: w["model.layers." + n] for n in _layer_shapes(cfg)}

    def layer(x, xs):
        blk, l = xs
        h = _rms(x, blk["input_layernorm"], eps)
        q = _rotate(_rms((h @ blk["q_proj"]).reshape(b, t, nh, hd),
                         blk["q_norm"], eps), cos, sin)
        k = _rotate(_rms((h @ blk["k_proj"]).reshape(b, t, kvh, hd),
                         blk["k_norm"], eps), cos, sin)
        v = (h @ blk["v_proj"]).reshape(b, t, kvh, hd)
        qi = _rotate((h @ blk["indexer_wq"]).reshape(b, t, ni, di),
                     icos, isin)
        ki = _rotate((h @ blk["indexer_wk"]).reshape(b, t, 1, di),
                     icos, isin)[:, :, 0]
        wi = h @ blk["indexer_weights_proj"]
        att = jax.lax.map(
            lambda a: select_and_attend(*a, 0, topk, scale)[0],
            (q, qi, wi, k.reshape(b, t, -1), v.reshape(b, t, -1), ki))
        x = x + att.reshape(b, t, nh * hd) @ blk["o_proj"]
        rows = _rms(x, blk["post_attention_layernorm"], eps
                    ).reshape(b * t, -1)
        idx, gate = route_topk(rows, blk["router"],
                               cfg.num_experts_per_tok,
                               normalize=cfg.norm_topk_prob)
        y = routed_ffn(rows, idx, gate, blocks["experts_gate_proj"],
                       blocks["experts_up_proj"],
                       blocks["experts_down_proj"], layer=l)
        return x + y.reshape(x.shape), None

    scanned = {n: a for n, a in blocks.items()
               if not n.startswith("experts_")}
    x, _ = jax.lax.scan(layer, x, (scanned, jnp.arange(
        cfg.num_hidden_layers, dtype=jnp.int32)))
    return (_rms(x, w["model.norm.weight"], eps) @ w["lm_head.weight"]
            ).astype(jnp.float32)


class KeyeVL2Model(nn.Layer):
    def __init__(self, cfg, weights):
        super().__init__()
        self.cfg = cfg
        pre = "model.layers."
        self.embed_tokens = _Holder(
            {"weight": weights["model.embed_tokens.weight"]})
        self.layers = _Holder({n[len(pre):]: a for n, a in weights.items()
                               if n.startswith(pre)})
        self.norm = _Holder({"weight": weights["model.norm.weight"]})


class KeyeVL2ForCausalLM(nn.Layer, GenerationMixin):
    """``weights``: a ``{raw_params() name: array}`` tree to adopt as
    the parameters (no copy); None draws ``init_weights(cfg, seed)``."""
    decode_family = "llama"    # generation.py picks the bundle builder

    def __init__(self, cfg: KeyeVL2Config, weights=None, seed=0):
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
        self.model = KeyeVL2Model(cfg, weights)
        self.lm_head = _Holder({"weight": weights["lm_head.weight"]})
        self._dtype = cfg.dtype

    def forward(self, input_ids, position_ids=None):
        """Logits [B, T, V]. ``position_ids`` [3, B, T] are the (time,
        height, width) positions; None means text (all three the token
        index)."""
        ids = unwrap(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(ids.shape[1], dtype=jnp.int32), (3,) + ids.shape)
        names = sorted(param_shapes(self.cfg))
        params = dict(self.named_parameters())
        cfg = self.cfg

        def fn(ids, pos, *arrays):
            return _forward(cfg, ids, pos, dict(zip(names, arrays)))

        return dispatch(fn, ids, unwrap(position_ids),
                        *[params[n] for n in names], nondiff_args=(0, 1),
                        name="keye_vl2_forward")

    def decode_weights(self):
        """The llama-family decode bundle's weight tree: this model's
        own stacked arrays under the bundle's leaf names."""
        raw = self.raw_params()
        tree = {"table": raw["model.embed_tokens.weight"],
                "norm": raw["model.norm.weight"],
                "head": raw["lm_head.weight"]}
        tree.update({leaf: raw["model.layers." + name]
                     for leaf, name in _BUNDLE_LEAVES.items()})
        return tree


def keye_vl2_tiny(**kw):
    """CPU-test sizes: 2 layers, hidden 64, 4 q / 2 kv heads of 16, 8
    experts top-2 of width 32, indexer 2 heads of 8 keeping 8 keys."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("moe_intermediate_size", 32)
    kw.setdefault("num_experts", 8)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("rope_scaling", {"mrope_section": [2, 3, 3],
                                   "rope_type": "default"})
    kw.setdefault("sa_config", {"indexer_head_dim": 8, "indexer_num_heads": 2,
                                "indexer_num_kv_heads": 1,
                                "kv_chunk_size": 512, "q_chunk_size": 512,
                                "topk": 8})
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("dtype", "float32")
    return KeyeVL2Config(**kw)
