"""GPT-2/3 family (decoder-only, learned positions, LayerNorm+GELU).

Reference parity target: the Fleet GPT hybrid-parallel example
(BASELINE.json config 1 — GPT-2 345M). Built from paddle_tpu.nn + the TP
layers, so one model definition serves single-chip, TP (GSPMD), and PP
(via PipelineLayer segmentation in parallel/pipeline.py).
"""
from dataclasses import dataclass

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.models.generation import GenerationMixin
from paddle_tpu.parallel.mp_layers import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding,
)

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt2_345m", "gpt2_tiny"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    max_seq_len: int = 1024
    intermediate_size: int = None
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    tensor_parallel: bool = False
    use_flash_attention: bool = True

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        if cfg.tensor_parallel:
            self.qkv = ColumnParallelLinear(h, 3 * h, gather_output=False)
            self.proj = RowParallelLinear(h, h, input_is_parallel=True)
        else:
            self.qkv = nn.Linear(h, 3 * h)
            self.proj = nn.Linear(h, h)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x):
        b = x.shape[0]
        s = x.shape[1]
        nh, hd = self.cfg.num_heads, self.cfg.hidden_size // self.cfg.num_heads
        qkv = self.qkv(x)
        qkv = qkv.reshape([b, s, 3, nh, hd])
        q, k, v = qkv.unbind(axis=2)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        out = out.reshape([b, s, nh * hd])
        return self.dropout(self.proj(out))


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h, m = cfg.hidden_size, cfg.intermediate_size
        if cfg.tensor_parallel:
            self.fc1 = ColumnParallelLinear(h, m, gather_output=False)
            self.fc2 = RowParallelLinear(m, h, input_is_parallel=True)
        else:
            self.fc1 = nn.Linear(h, m)
            self.fc2 = nn.Linear(m, h)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x):
        return self.dropout(self.fc2(F.gelu(self.fc1(x), approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.mlp = GPTMLP(cfg)

    def forward(self, x):
        x = x + self.attn(self.ln1(x))
        x = x + self.mlp(self.ln2(x))
        return x


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        if cfg.tensor_parallel:
            self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        else:
            self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        # GPT-2 init: N(0, 0.02) embeddings (keeps init CE near ln(V))
        from paddle_tpu.nn.initializer import Normal
        init = Normal(0.0, 0.02)
        self.wte.weight._replace_value(
            init(self.wte.weight.shape, self.wte.weight.dtype))
        self.wpe.weight._replace_value(
            init(self.wpe.weight.shape, self.wpe.weight.dtype))
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def forward(self, input_ids, position_ids=None):
        import paddle_tpu as pt
        s = input_ids.shape[-1]
        if position_ids is None:
            position_ids = pt.ops.arange(0, s, dtype="int32")
        x = self.wte(input_ids) + self.wpe(position_ids)
        x = self.drop(x)
        for blk in self.blocks:
            x = blk(x)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer, GenerationMixin):
    decode_family = "gpt"    # generation.py picks the bundle builder

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        # tied output projection (weight reuse, like the reference example)
        self.lm_head_weight = self.gpt.wte.weight

    def forward(self, input_ids, position_ids=None, return_hidden=False):
        h = self.gpt(input_ids, position_ids)
        if return_hidden:
            # for fused linear+CE losses (ops/fused_ce.py): caller applies
            # the tied lm head inside the chunked loss
            return h
        from ..ops.registry import OPS
        return OPS["matmul"](h, self.lm_head_weight, transpose_y=True)

    def loss(self, logits, labels):
        """Shifted causal LM loss."""
        lg = logits[:, :-1, :]
        lb = labels[:, 1:]
        return F.cross_entropy(lg, lb)

    def pipeline_decompose(self):
        """Pure fns + param trees for the 1F1B/hybrid builders, WITH the
        tied lm head (reference SharedLayerDesc GPT demo): the embedding
        table is the shared weight, so the builder gets
        tie_embed_head=True and stores it pp/mp-sharded; wpe and the
        final LN ride along as replicated extras.

        Returns ((block_fn, embed_fn, head_loss_fn),
                 (blocks, embed, head), {"tie_embed_head": True}).
        """
        import jax
        import jax.numpy as jnp

        from ..core.tensor import unwrap
        from ..jit import functional_call
        if self.cfg.tensor_parallel:
            # no-roadmap: API redirect to the hybrid factories, not a cut
            raise NotImplementedError(
                "pipeline_decompose targets the non-TP module; for mp×pp "
                "use parallel.hybrid factories")
        proto = self.gpt.blocks[0]
        blocks = [dict(blk.raw_params()) for blk in self.gpt.blocks]
        embed = {"table": unwrap(self.gpt.wte.weight),
                 "wpe": unwrap(self.gpt.wpe.weight)}
        head = {"ln_g": unwrap(self.gpt.ln_f.weight),
                "ln_b": unwrap(self.gpt.ln_f.bias)}
        eps = self.cfg.layer_norm_eps

        def block_fn(p, x):
            return functional_call(proto, p, x)

        def embed_fn(p, ids):
            s = ids.shape[-1]
            return p["table"][ids] + p["wpe"][:s][None]

        def _final_ln(p, hidden):
            mu = hidden.mean(-1, keepdims=True)
            var = jnp.var(hidden.astype(jnp.float32), -1, keepdims=True)
            return ((hidden - mu) * jax.lax.rsqrt(var + eps)
                    ) * p["ln_g"] + p["ln_b"]

        def head_loss_fn(p, hidden, labels):
            lg = (_final_ln(p, hidden) @ p["table"].T
                  ).astype(jnp.float32)[:, :-1]
            logp = jax.nn.log_softmax(lg, -1)
            return -jnp.take_along_axis(
                logp, labels[:, 1:, None], -1).mean()

        def head_out_fn(p, hidden, labels):
            # Engine.predict through the pipeline: full-seq logits via
            # the tied table (the builder injects p["table"] gathered)
            return (_final_ln(p, hidden) @ p["table"].T
                    ).astype(jnp.float32)

        return ((block_fn, embed_fn, head_loss_fn),
                (blocks, embed, head),
                {"tie_embed_head": True, "head_out_fn": head_out_fn})

    def pipeline_recompose(self, params, layout):
        """Inverse of pipeline_decompose + stacking: write trained
        stage-stacked params back into this module (the tied table
        writes once — lm_head_weight aliases wte.weight)."""
        counts, starts, S, v = layout
        for vs in range(S * v):
            v_idx, s_idx = vs // S, vs % S
            for j in range(int(counts[vs])):
                layer = self.gpt.blocks[int(starts[vs]) + j]
                layer.load_raw_params(
                    {n: a[v_idx, s_idx, j]
                     for n, a in params["blocks"].items()})
        import numpy as _np
        self.gpt.wte.weight._replace_value(
            _np.asarray(params["embed"]["table"]))
        self.gpt.wpe.weight._replace_value(
            _np.asarray(params["embed"]["wpe"]))
        self.gpt.ln_f.weight._replace_value(
            _np.asarray(params["head"]["ln_g"]))
        self.gpt.ln_f.bias._replace_value(
            _np.asarray(params["head"]["ln_b"]))


def gpt2_345m(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                     num_heads=16, max_seq_len=1024, **kw)


def gpt2_tiny(**kw):
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("max_seq_len", 128)
    return GPTConfig(**kw)
