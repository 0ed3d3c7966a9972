"""Llama-2 family (RMSNorm pre-norm, RoPE, SwiGLU, GQA-ready).

The flagship perf model (BASELINE.md: Llama-2 7B/70B TP+PP+sharding
targets). RMSNorm and attention route to the Pallas kernels on TPU; rope is
XLA-fused (ops/pallas/rope.py).
"""
from dataclasses import dataclass

import jax.numpy as jnp

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import dispatch
from paddle_tpu.models.generation import GenerationMixin
from paddle_tpu.ops.pallas import rope as rope_mod
from paddle_tpu.parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
)

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama2_7b",
           "llama2_70b", "llama_tiny", "llama_350m"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = None
    intermediate_size: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    tensor_parallel: bool = False
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        hd = cfg.head_dim
        q_out = cfg.num_heads * hd
        kv_out = cfg.num_kv_heads * hd
        Lin = ColumnParallelLinear if cfg.tensor_parallel else None
        if cfg.tensor_parallel:
            self.q_proj = ColumnParallelLinear(h, q_out, has_bias=False,
                                               gather_output=False)
            self.k_proj = ColumnParallelLinear(h, kv_out, has_bias=False,
                                               gather_output=False)
            self.v_proj = ColumnParallelLinear(h, kv_out, has_bias=False,
                                               gather_output=False)
            self.o_proj = RowParallelLinear(q_out, h, has_bias=False,
                                            input_is_parallel=True)
        else:
            self.q_proj = nn.Linear(h, q_out, bias_attr=False)
            self.k_proj = nn.Linear(h, kv_out, bias_attr=False)
            self.v_proj = nn.Linear(h, kv_out, bias_attr=False)
            self.o_proj = nn.Linear(q_out, h, bias_attr=False)
        cos, sin = rope_mod.precompute_freqs(hd, cfg.max_seq_len,
                                             cfg.rope_theta)
        from paddle_tpu.core.tensor import wrap
        self.register_buffer("rope_cos", wrap(cos), persistable=False)
        self.register_buffer("rope_sin", wrap(sin), persistable=False)

    def forward(self, x, position_ids=None):
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([b, s, cfg.num_heads, cfg.head_dim])
        k = self.k_proj(x).reshape([b, s, cfg.num_kv_heads, cfg.head_dim])
        v = self.v_proj(x).reshape([b, s, cfg.num_kv_heads, cfg.head_dim])

        def rot(qv, kv, cosv, sinv):
            return (rope_mod.apply_rotary(qv, cosv, sinv),
                    rope_mod.apply_rotary(kv, cosv, sinv))

        q, k = dispatch(rot, q, k, self.rope_cos, self.rope_sin,
                        nondiff_args=(2, 3), name="rope")
        if cfg.num_kv_heads != cfg.num_heads:
            rep = cfg.num_heads // cfg.num_kv_heads

            def repeat_kv(t):
                return jnp.repeat(t, rep, axis=2)

            k = dispatch(repeat_kv, k, name="repeat_kv")
            v = dispatch(repeat_kv, v, name="repeat_kv")
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        out = out.reshape([b, s, cfg.num_heads * cfg.head_dim])
        return self.o_proj(out)


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, m = cfg.hidden_size, cfg.intermediate_size
        if cfg.tensor_parallel:
            self.gate_proj = ColumnParallelLinear(h, m, has_bias=False,
                                                  gather_output=False)
            self.up_proj = ColumnParallelLinear(h, m, has_bias=False,
                                                gather_output=False)
            self.down_proj = RowParallelLinear(m, h, has_bias=False,
                                               input_is_parallel=True)
        else:
            self.gate_proj = nn.Linear(h, m, bias_attr=False)
            self.up_proj = nn.Linear(h, m, bias_attr=False)
            self.down_proj = nn.Linear(m, h, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaBlock(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, position_ids=None):
        x = x + self.self_attn(self.input_layernorm(x), position_ids)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        if cfg.tensor_parallel:
            self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                       cfg.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        from paddle_tpu.nn.initializer import Normal
        w = self.embed_tokens.weight
        w._replace_value(Normal(0.0, 0.02)(w.shape, w.dtype))
        self.layers = nn.LayerList([LlamaBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids, position_ids=None):
        x = self.embed_tokens(input_ids)
        for blk in self.layers:
            x = blk(x, position_ids)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer, GenerationMixin):
    decode_family = "llama"    # generation.py picks the bundle builder

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if cfg.tensor_parallel:
            self.lm_head = ColumnParallelLinear(cfg.hidden_size,
                                                cfg.vocab_size,
                                                has_bias=False,
                                                gather_output=True)
        else:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, position_ids=None, return_hidden=False):
        h = self.model(input_ids, position_ids)
        if return_hidden:
            # for fused linear+CE losses (ops/fused_ce.py)
            return h
        return self.lm_head(h)

    def loss(self, logits, labels):
        return F.cross_entropy(logits[:, :-1, :], labels[:, 1:])

    def pipeline_decompose(self):
        """Decompose into pure fns + param trees for the 1F1B/hybrid
        builders (reference PipelineLayer's LayerDesc segmentation,
        meta_parallel/parallel_layers/pp_layers.py): returns
        ((block_fn, embed_fn, head_loss_fn), (blocks, embed, head))."""
        import jax
        import jax.numpy as jnp

        from ..core.tensor import unwrap
        from ..jit import functional_call
        if self.cfg.tensor_parallel:
            # no-roadmap: API redirect to the hybrid factories, not a cut
            raise NotImplementedError(
                "pipeline_decompose targets the non-TP module; for mp×pp "
                "use parallel.hybrid.make_llama_tp_fns")
        proto = self.model.layers[0]
        blocks = [dict(blk.raw_params()) for blk in self.model.layers]
        embed = {"table": unwrap(self.model.embed_tokens.weight)}
        head = {"norm": unwrap(self.model.norm.weight),
                "wo": unwrap(self.lm_head.weight)}
        eps = self.cfg.rms_eps

        def block_fn(p, x):
            return functional_call(proto, p, x)

        def embed_fn(p, ids):
            return p["table"][ids]

        def _final_norm(p, hidden):
            var = jnp.mean(jnp.square(hidden.astype(jnp.float32)), -1,
                           keepdims=True)
            return (hidden * jax.lax.rsqrt(var + eps).astype(hidden.dtype)
                    ) * p["norm"]

        def head_loss_fn(p, hidden, labels):
            lg = (_final_norm(p, hidden) @ p["wo"]
                  ).astype(jnp.float32)[:, :-1]
            logp = jax.nn.log_softmax(lg, -1)
            return -jnp.take_along_axis(
                logp, labels[:, 1:, None], -1).mean()

        def head_out_fn(p, hidden, labels):
            # Engine.predict through the pipeline: full-seq logits
            return (_final_norm(p, hidden) @ p["wo"]).astype(jnp.float32)

        return ((block_fn, embed_fn, head_loss_fn),
                (blocks, embed, head), {"head_out_fn": head_out_fn})

    def pipeline_recompose(self, params, layout):
        """Write trained stage-stacked pipeline params back into this
        eager module (inverse of pipeline_decompose + the builder's
        stacking). ``params`` = {"blocks": {name: [v,S,C,...]},
        "embed": ..., "head": ...}; ``layout`` = (counts, starts, S, v)."""
        counts, starts, S, v = layout
        for vs in range(S * v):
            v_idx, s_idx = vs // S, vs % S
            for j in range(int(counts[vs])):
                layer = self.model.layers[int(starts[vs]) + j]
                layer.load_raw_params(
                    {n: a[v_idx, s_idx, j]
                     for n, a in params["blocks"].items()})
        self.model.embed_tokens.weight._replace_value(
            params["embed"]["table"])
        self.model.norm.weight._replace_value(params["head"]["norm"])
        self.lm_head.weight._replace_value(params["head"]["wo"])


def llama2_7b(**kw):
    return LlamaConfig(**kw)


def llama2_70b(**kw):
    kw.setdefault("hidden_size", 8192)
    kw.setdefault("num_layers", 80)
    kw.setdefault("num_heads", 64)
    kw.setdefault("num_kv_heads", 8)
    kw.setdefault("intermediate_size", 28672)
    return LlamaConfig(**kw)


def llama_350m(**kw):
    kw.setdefault("hidden_size", 1024)
    kw.setdefault("num_layers", 24)
    kw.setdefault("num_heads", 16)
    kw.setdefault("intermediate_size", 2816)
    kw.setdefault("max_seq_len", 2048)
    return LlamaConfig(**kw)


def llama_tiny(**kw):
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("max_seq_len", 128)
    return LlamaConfig(**kw)
