"""The ``nemotron_h`` decoder (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B):
a hybrid in which a layer is ONE sublayer behind ONE RMSNorm, in the
order ``hybrid_override_pattern`` gives: ``M`` a Mamba-2 state-space
mixer (a depthwise causal convolution of ``conv_kernel`` taps, then the
recurrence of ``ops/ssm_scan.py`` over ``mamba_num_heads`` heads whose
state ``[head_dim, ssm_state_size]`` is float32 and per sequence, a gate
and a grouped RMSNorm), ``*`` grouped-query attention with no positional
term, ``E`` a latent expert layer (a sigmoid router over
``router_experts`` experts with a selection-only bias, top-
``num_experts_per_tok``; the routed experts are ungated ``W2 relu(W1
v)^2`` in a ``moe_latent_size``-wide latent between two projections, a
shared expert reads the full width). The head is untied.

A model may hold a SHARE of a layer's routed experts
(``n_routed_experts`` of ``router_experts``, from ``held_first``): the
router scores and normalises over all of them, and the layer adds what
the held ones give (``routed_ffn(held=)``); what the others would add is
another chip's to add. The multi-token-prediction module of the
published model is a draft head the main model's logits do not depend
on; it is not built (ROADMAP B6).

As in ``lfm2.py`` the weights are the model's OWN parameters STACKED a
KIND of sublayer (``model.mamba_layers.*``, ``model.attn_layers.*``,
``model.moe_layers.*``; every layer's norm under ``model.layers.norm``),
which the decode bundle's layer loop indexes by a layer spec
(``generation._layer_spec``): serving holds them once, the page pool
has a layer an ATTENTION layer, and the slot state is a tree of two
leaves, the convolution's window in the model's type and the recurrent
state in float32.
"""
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import dispatch, unwrap
from paddle_tpu.models.generation import GenerationMixin, _rms, _ssm_core
from paddle_tpu.models.keye_vl import _Holder
from paddle_tpu.ops.routed_ffn import route_topk, routed_ffn
from paddle_tpu.ops.short_conv import short_conv

__all__ = ["NemotronHConfig", "NemotronHForCausalLM", "nemotron_h_tiny"]

_PUBLISHED_PATTERN = ("MEMEMEM*E" * 3 + "MEMEMEMEM*E" * 4 + "MEMEMEM*E"
                      + "MEMEMEME")
_KINDS = {"M": "ssm", "*": "attn", "E": "moe"}


@dataclass
class NemotronHConfig:
    """The published ``config.json`` keys, as named there (plus
    ``router_experts`` and ``held_first``, the expert share); the
    properties below are the names the llama-family decode builder
    reads."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = _PUBLISHED_PATTERN
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 512          # the experts this model HOLDS
    router_experts: int = 0              # the router's width; 0: all held
    held_first: int = 0
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    n_group: int = 1
    topk_group: int = 1
    mlp_hidden_act: str = "relu2"
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.router_experts = int(self.router_experts
                                  or self.n_routed_experts)
        if len(self.hybrid_override_pattern) != self.num_hidden_layers:
            raise ValueError(
                "hybrid_override_pattern names every layer: "
                f"{len(self.hybrid_override_pattern)} of "
                f"{self.num_hidden_layers}")
        unknown = set(self.hybrid_override_pattern) - set(_KINDS)
        if unknown:
            # no-roadmap: the published pattern has no '-' (dense FFN) layer
            raise NotImplementedError(
                f"layer kinds {sorted(unknown)}: the nemotron_h decoder is "
                "built for M (Mamba-2), * (attention) and E (experts)")
        if (self.mlp_hidden_act != "relu2" or not self.use_conv_bias
                or self.tie_word_embeddings or self.n_shared_experts != 1
                or self.n_group != 1 or self.topk_group != 1):
            # no-roadmap: the published model has none of these; a check
            raise NotImplementedError(
                "nemotron_h is built as published: relu2 experts, a conv "
                "bias, an untied head, one shared expert, no router groups")
        if self.mamba_num_heads * self.mamba_head_dim \
                != self.expand * self.hidden_size:
            raise ValueError("mamba heads x head_dim must be expand x hidden")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("n_groups must divide the mamba heads")
        if not 0 <= self.held_first <= self.router_experts \
                - self.n_routed_experts:
            raise ValueError("the held experts [held_first, held_first + "
                             "n_routed_experts) lie outside the router's")

    # -- what models/generation.py's llama-family builder reads
    num_heads = property(lambda self: self.num_attention_heads)
    num_kv_heads = property(lambda self: self.num_key_value_heads)
    num_layers = property(lambda self: self.num_hidden_layers)
    rms_eps = property(lambda self: self.layer_norm_epsilon)
    num_experts = property(lambda self: self.n_routed_experts)
    top_k = property(lambda self: self.num_experts_per_tok)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    # each layer's ONE sublayer, by the name of the stack it reads
    sublayers = property(lambda self: tuple(
        _KINDS[c] for c in self.hybrid_override_pattern))
    experts_held = property(lambda self: (self.held_first,
                                          self.n_routed_experts))
    mamba_inner = property(lambda self: self.mamba_num_heads
                           * self.mamba_head_dim)
    conv_dim = property(lambda self: self.mamba_inner
                        + 2 * self.n_groups * self.ssm_state_size)
    # (heads, head_dim, groups, state, chunk): the recurrence's shapes
    ssm_dims = property(lambda self: (
        self.mamba_num_heads, self.mamba_head_dim, self.n_groups,
        self.ssm_state_size, self.chunk_size))
    rope_theta = None              # no positional term in the attention
    router_score = "sigmoid"       # with the correction bias choosing only
    router_eps = 1e-20
    use_expert_bias = True


def layer_counts(cfg):
    """(Mamba-2, attention, expert) layers of ``cfg``."""
    p = cfg.hybrid_override_pattern
    return p.count("M"), p.count("*"), p.count("E")


def param_shapes(cfg):
    """``raw_params()`` name -> shape, without building anything."""
    c = cfg
    h, inner = c.hidden_size, c.mamba_inner
    nq = c.num_attention_heads * c.head_dim
    nkv = c.num_key_value_heads * c.head_dim
    lat, f = c.moe_latent_size, c.moe_intermediate_size
    fs = c.moe_shared_expert_intermediate_size
    lm, la, le = layer_counts(c)
    return {
        "model.embed_tokens.weight": (c.vocab_size, h),
        "model.norm_f.weight": (h,),
        "lm_head.weight": (h, c.vocab_size),
        "model.layers.norm": (c.num_hidden_layers, h),
        # z (the gate), then x B C (the convolution's channels), then dt
        "model.mamba_layers.in_proj": (lm, h, inner + c.conv_dim
                                       + c.mamba_num_heads),
        "model.mamba_layers.conv_weight": (lm, c.conv_kernel, c.conv_dim),
        "model.mamba_layers.conv_bias": (lm, c.conv_dim),
        "model.mamba_layers.A_log": (lm, c.mamba_num_heads),
        "model.mamba_layers.D": (lm, c.mamba_num_heads),
        "model.mamba_layers.dt_bias": (lm, c.mamba_num_heads),
        "model.mamba_layers.norm": (lm, inner),
        "model.mamba_layers.out_proj": (lm, inner, h),
        "model.attn_layers.q_proj": (la, h, nq),
        "model.attn_layers.k_proj": (la, h, nkv),
        "model.attn_layers.v_proj": (la, h, nkv),
        "model.attn_layers.o_proj": (la, nq, h),
        "model.moe_layers.router": (le, h, c.router_experts),
        "model.moe_layers.e_score_correction_bias": (le, c.router_experts),
        "model.moe_layers.latent_down": (le, h, lat),
        "model.moe_layers.latent_up": (le, lat, h),
        "model.moe_layers.experts_w1": (le, c.n_routed_experts, lat, f),
        "model.moe_layers.experts_w2": (le, c.n_routed_experts, f, lat),
        "model.moe_layers.shared_w1": (le, h, fs),
        "model.moe_layers.shared_w2": (le, fs, h),
    }


_GAINS = ("norm_f.weight", "layers.norm", "mamba_layers.norm")
_F32 = ("A_log", ".D", "dt_bias", "e_score_correction_bias")
ROUTER_BIAS = "model.moe_layers.e_score_correction_bias"

# the decode bundle's leaf names for the stacked parameters
_BUNDLE_LEAVES = {
    "ln1": "model.layers.norm",
    "si": "model.mamba_layers.in_proj",
    "sw": "model.mamba_layers.conv_weight",
    "sb": "model.mamba_layers.conv_bias",
    "sa": "model.mamba_layers.A_log", "sd": "model.mamba_layers.D",
    "st": "model.mamba_layers.dt_bias", "sn": "model.mamba_layers.norm",
    "so": "model.mamba_layers.out_proj",
    "wq": "model.attn_layers.q_proj", "wk": "model.attn_layers.k_proj",
    "wv": "model.attn_layers.v_proj", "wo": "model.attn_layers.o_proj",
    "router": "model.moe_layers.router", "rbias": ROUTER_BIAS,
    "ld": "model.moe_layers.latent_down", "lu": "model.moe_layers.latent_up",
    "wu": "model.moe_layers.experts_w1", "wd": "model.moe_layers.experts_w2",
    "s1": "model.moe_layers.shared_w1", "s2": "model.moe_layers.shared_w2"}


def is_gain(name):
    return name.endswith(_GAINS)


def param_dtype(cfg, name):
    """The recurrence's ``A_log``, ``D`` and ``dt_bias`` and the router's
    correction bias are float32 whatever the model's type."""
    return jnp.float32 if name.endswith(_F32) else jnp.dtype(cfg.dtype)


def init_weights(cfg, seed=0, key=None, scale=None):
    """Every parameter in ONE jitted call, in the type it is served in:
    N(0, ``initializer_range``) matrices (times ``scale[name]`` where
    given), gains 1, ``D`` 1, ``A_log`` = log U[1, 16], ``dt_bias`` the
    inverse softplus of a step drawn log-uniform in [``time_step_min``,
    ``time_step_max``] and floored at ``time_step_floor`` (what the
    three keys are for), and a NON-zero correction bias from the same
    draw, so that choosing and weighing experts differ. ``key``: a PRNG
    key to draw from in place of ``PRNGKey(seed)``."""
    shapes = param_shapes(cfg)
    scale = dict(scale or {})
    unknown = sorted(set(scale) - set(shapes))
    if unknown:
        raise KeyError(f"scale names no parameter: {unknown}")
    names = sorted(shapes)
    lo, hi = np.log(cfg.time_step_min), np.log(cfg.time_step_max)

    def draw(k, n):
        shape, dtype = shapes[n], param_dtype(cfg, n)
        if is_gain(n) or n.endswith(".D"):
            return jnp.ones(shape, dtype)
        if n.endswith("A_log"):
            return jnp.log(jax.random.uniform(k, shape, dtype, 1.0, 16.0))
        if n.endswith("dt_bias"):
            step = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, dtype, lo, hi)), cfg.time_step_floor)
            return step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)
        return (cfg.initializer_range * scale.get(n, 1.0)
                * jax.random.normal(k, shape, dtype))

    @jax.jit
    def make(key):
        return {n: draw(k, n) for k, n in zip(
            jax.random.split(key, len(names)), names)}

    return make(jax.random.PRNGKey(seed) if key is None else key)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _forward(cfg, ids, w):
    """Full (uncached) forward over raw arrays ``w`` (the
    ``raw_params()`` names): logits [B, T, V] float32. A Python loop
    over the layers, each indexing its kind's stack."""
    b, t = ids.shape
    nh, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    eps = cfg.layer_norm_epsilon
    causal = jnp.tril(jnp.ones((t, t), bool))
    x = w["model.embed_tokens.weight"][ids]
    count = dict.fromkeys(_KINDS.values(), 0)
    for l, kind in enumerate(cfg.sublayers):
        i = count[kind]
        count[kind] += 1
        u = _rms(x, w["model.layers.norm"][l], eps)
        if kind == "ssm":
            at = lambda n: w["model.mamba_layers." + n][i]
            blk = {"sa": at("A_log"), "sd": at("D"), "st": at("dt_bias"),
                   "sn": at("norm")}
            z, xbc, dt = jnp.split(
                u @ at("in_proj"),
                [cfg.mamba_inner, cfg.mamba_inner + cfg.conv_dim], axis=-1)
            c, _ = short_conv(xbc, at("conv_weight"))
            y, _ = _ssm_core(blk, jax.nn.silu(c + at("conv_bias")), z, dt,
                             jnp.zeros((b,) + cfg.ssm_dims[:2]
                                       + cfg.ssm_dims[3:4], jnp.float32),
                             None, cfg.ssm_dims, eps)
            o = y @ at("out_proj")
        elif kind == "attn":
            at = lambda n: w["model.attn_layers." + n][i]
            q = (u @ at("q_proj")).reshape(b, t, kvh, nh // kvh, hd)
            k = (u @ at("k_proj")).reshape(b, t, kvh, hd)
            v = (u @ at("v_proj")).reshape(b, t, kvh, hd)
            s = jnp.einsum("btgmd,bsgd->bgmts", q, k).astype(
                jnp.float32) / np.sqrt(hd)
            p = jax.nn.softmax(jnp.where(causal, s, -1e30), -1).astype(
                v.dtype)
            o = jnp.einsum("bgmts,bsgd->btgmd", p, v).reshape(
                b, t, nh * hd) @ at("o_proj")
        else:
            moe = lambda n: w["model.moe_layers." + n]
            rows = u.reshape(b * t, -1)
            idx, gate = route_topk(
                rows, moe("router")[i], cfg.num_experts_per_tok,
                normalize=cfg.norm_topk_prob, score="sigmoid",
                bias=moe("e_score_correction_bias")[i],
                scale=cfg.routed_scaling_factor, eps=cfg.router_eps)
            r = routed_ffn(rows @ moe("latent_down")[i], idx, gate, None,
                           moe("experts_w1"), moe("experts_w2"), layer=i,
                           held=cfg.experts_held)
            o = (r @ moe("latent_up")[i]
                 + _relu2(rows @ moe("shared_w1")[i]) @ moe("shared_w2")[i]
                 ).reshape(x.shape)
        x = x + o
    out = _rms(x, w["model.norm_f.weight"], eps)
    return (out @ w["lm_head.weight"]).astype(jnp.float32)


class NemotronHModel(nn.Layer):
    def __init__(self, cfg, weights):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Holder(
            {"weight": weights["model.embed_tokens.weight"]})
        self.norm_f = _Holder({"weight": weights["model.norm_f.weight"]})
        for group in ("layers", "mamba_layers", "attn_layers", "moe_layers"):
            pre = f"model.{group}."
            setattr(self, group, _Holder(
                {n[len(pre):]: a for n, a in weights.items()
                 if n.startswith(pre)}))


class NemotronHForCausalLM(nn.Layer, GenerationMixin):
    """``weights``: a ``{raw_params() name: array}`` tree to adopt as
    the parameters (no copy); None draws ``init_weights(cfg, seed)``."""
    decode_family = "llama"    # generation.py picks the bundle builder

    def __init__(self, cfg: NemotronHConfig, weights=None, seed=0):
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
        self.model = NemotronHModel(cfg, weights)
        self.lm_head = _Holder({"weight": weights["lm_head.weight"]})
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
                        nondiff_args=(0,), name="nemotron_h_forward")

    def decode_weights(self):
        """The llama-family decode bundle's weight tree: this model's
        own stacked arrays under the bundle's leaf names."""
        raw = self.raw_params()
        tree = {"table": raw["model.embed_tokens.weight"],
                "norm": raw["model.norm_f.weight"],
                "head": raw["lm_head.weight"]}
        tree.update({leaf: raw[name]
                     for leaf, name in _BUNDLE_LEAVES.items()})
        return tree


def nemotron_h_tiny(**kw):
    """CPU-test sizes with every kind of layer: ``MEM*E``, hidden 64; 8
    Mamba heads of 16 in 2 groups, state 16, chunks of 8; 4 q / 2 kv
    heads of 16; 8 held of 16 experts top-3 of width 48 in a latent of
    32, a shared expert of 96."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_hidden_layers", 5)
    kw.setdefault("hybrid_override_pattern", "MEM*E")
    kw.setdefault("mamba_num_heads", 8)
    kw.setdefault("mamba_head_dim", 16)
    kw.setdefault("ssm_state_size", 16)
    kw.setdefault("n_groups", 2)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("n_routed_experts", 8)
    kw.setdefault("router_experts", 16)
    kw.setdefault("num_experts_per_tok", 3)
    kw.setdefault("moe_intermediate_size", 48)
    kw.setdefault("moe_latent_size", 32)
    kw.setdefault("moe_shared_expert_intermediate_size", 96)
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("dtype", "float32")
    return NemotronHConfig(**kw)
