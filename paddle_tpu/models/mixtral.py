"""Mixtral / DeepSeek-MoE family: Llama backbone with a routed SwiGLU
expert FFN (top-k gating, capacity buckets, load-balance aux loss).

BASELINE.md row "DeepSeek-MoE / Mixtral: expert parallel on TPU mesh —
functional + MFU reported". Reference capability:
python/paddle/incubate/distributed/models/moe/moe_layer.py:261 (MoELayer
over global_scatter/global_gather) — here the TPU-native MoELayer
(parallel/moe/layer.py) with GShard grouped einsum dispatch; experts are
sharded over the mesh's model axis (EP via GSPMD on the stacked expert
dim, or lax.all_to_all inside shard_map).
"""
from dataclasses import dataclass

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.models.generation import GenerationMixin
from paddle_tpu.parallel.moe import ExpertSwiGLU, MoELayer

from .llama import LlamaAttention, LlamaConfig

__all__ = ["MixtralConfig", "MixtralModel", "MixtralForCausalLM",
           "mixtral_8x7b", "mixtral_tiny", "moe_350m_8e"]


@dataclass
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    moe_group_size: int = None   # tokens per dispatch group; None = seq len

    @property
    def active_params_ratio(self):
        """Fraction of expert params active per token (for MFU accounting)."""
        return self.top_k / self.num_experts


class MixtralBlock(nn.Layer):
    def __init__(self, cfg: MixtralConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_eps)
        experts = ExpertSwiGLU(cfg.num_experts, cfg.hidden_size,
                               cfg.intermediate_size)
        self.moe = MoELayer(cfg.hidden_size, experts=experts,
                            gate="gshard", top_k=cfg.top_k,
                            capacity_factor=cfg.capacity_factor,
                            group_size=cfg.moe_group_size or cfg.max_seq_len)

    def forward(self, x, position_ids=None):
        x = x + self.self_attn(self.input_layernorm(x), position_ids)
        x = x + self.moe(self.post_attention_layernorm(x))
        return x


class MixtralModel(nn.Layer):
    def __init__(self, cfg: MixtralConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        from paddle_tpu.nn.initializer import Normal
        w = self.embed_tokens.weight
        w._replace_value(Normal(0.0, 0.02)(w.shape, w.dtype))
        self.layers = nn.LayerList([MixtralBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids, position_ids=None):
        x = self.embed_tokens(input_ids)
        for blk in self.layers:
            x = blk(x, position_ids)
        return self.norm(x)


class MixtralForCausalLM(nn.Layer, GenerationMixin):
    decode_family = "llama"    # generation.py picks the bundle builder

    def __init__(self, cfg: MixtralConfig):
        super().__init__()
        self.cfg = cfg
        self.model = MixtralModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids, position_ids=None):
        return self.lm_head(self.model(input_ids, position_ids))

    def collect_aux_loss(self):
        """Sum of per-layer load-balance losses from the last forward
        (valid inside the same jit trace / eager step)."""
        total = None
        for blk in self.model.layers:
            a = blk.moe.aux_loss
            if a is None:
                continue
            total = a if total is None else total + a
        return total

    def loss(self, logits, labels):
        ce = F.cross_entropy(logits[:, :-1, :], labels[:, 1:])
        aux = self.collect_aux_loss()
        if aux is not None:
            ce = ce + self.cfg.aux_loss_coef * aux
        return ce


def mixtral_8x7b(**kw):
    kw.setdefault("hidden_size", 4096)
    kw.setdefault("num_layers", 32)
    kw.setdefault("num_heads", 32)
    kw.setdefault("num_kv_heads", 8)
    kw.setdefault("intermediate_size", 14336)
    kw.setdefault("num_experts", 8)
    kw.setdefault("top_k", 2)
    return MixtralConfig(**kw)


def moe_350m_8e(**kw):
    """Single-chip MoE bench config: ~190M active / ~530M total params."""
    kw.setdefault("vocab_size", 32000)
    kw.setdefault("hidden_size", 768)
    kw.setdefault("num_layers", 12)
    kw.setdefault("num_heads", 12)
    kw.setdefault("intermediate_size", 2048)
    kw.setdefault("max_seq_len", 1024)
    kw.setdefault("num_experts", 8)
    kw.setdefault("top_k", 2)
    return MixtralConfig(**kw)


def mixtral_tiny(**kw):
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("num_experts", 4)
    kw.setdefault("top_k", 2)
    return MixtralConfig(**kw)
