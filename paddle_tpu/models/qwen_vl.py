"""Qwen-VL-style vision-language model (multimodal, functional).

BASELINE.md row "Qwen-VL: multimodal via auto_parallel ... functional".
Architecture: ViT vision tower (patch embed + pre-norm transformer) →
linear projector → visual tokens prepended to the text embedding stream of
a Llama-family decoder (RoPE positions cover the joint sequence). Loss
masks the visual prefix and scores only text targets.

Reference capability: the PaddleNLP/PaddleMIX VL stack layered on the
reference's fleet/auto_parallel APIs; here everything runs on paddle_tpu.nn
with the Pallas attention path, and parameters can be annotated for a
ProcessMesh via `shard_qwen_vl`.
"""
from dataclasses import dataclass, field

import paddle_tpu.nn as nn
from paddle_tpu.ops.manipulation import concat as pt_ops_concat
import paddle_tpu.nn.functional as F

from ._stem import patches_to_seq, shard_params_by_name
from .llama import LlamaConfig, LlamaModel

__all__ = ["ViTConfig", "VisionTransformer", "QwenVLConfig", "QwenVL",
           "qwen_vl_tiny"]


@dataclass
class ViTConfig:
    image_size: int = 224
    patch_size: int = 14
    in_channels: int = 3
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    mlp_ratio: float = 4.0

    @property
    def num_patches(self):
        return (self.image_size // self.patch_size) ** 2


class ViTBlock(nn.Layer):
    def __init__(self, cfg: ViTConfig):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.norm1 = nn.LayerNorm(h)
        self.qkv = nn.Linear(h, 3 * h)
        self.proj = nn.Linear(h, h)
        self.norm2 = nn.LayerNorm(h)
        m = int(h * cfg.mlp_ratio)
        self.mlp = nn.Sequential(nn.Linear(h, m), nn.GELU(approximate=True),
                                 nn.Linear(m, h))

    def forward(self, x):
        b, s, h = x.shape
        hd = h // self.num_heads
        qkv = self.qkv(self.norm1(x)).reshape([b, s, 3, self.num_heads, hd])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        att = F.scaled_dot_product_attention(q, k, v, is_causal=False,
                                             training=self.training)
        x = x + self.proj(att.reshape([b, s, h]))
        return x + self.mlp(self.norm2(x))


class VisionTransformer(nn.Layer):
    """Pre-norm ViT tower returning patch tokens (no CLS pooling — the VL
    projector consumes the full token grid, Qwen-VL style)."""

    def __init__(self, cfg: ViTConfig):
        super().__init__()
        self.cfg = cfg
        p = cfg.patch_size
        self.patch_embed = nn.Conv2D(cfg.in_channels, cfg.hidden_size,
                                     kernel_size=p, stride=p)
        from paddle_tpu.nn.initializer import Normal
        self.pos_embed = self.create_parameter(
            (1, cfg.num_patches, cfg.hidden_size),
            default_initializer=Normal(0.0, 0.02))
        self.blocks = nn.LayerList([ViTBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.LayerNorm(cfg.hidden_size)

    def forward(self, pixel_values):
        h = patches_to_seq(self.patch_embed(pixel_values)) + self.pos_embed
        for blk in self.blocks:
            h = blk(h)
        return self.norm(h)                        # [B, T_img, D_vit]


@dataclass
class QwenVLConfig:
    vision: ViTConfig = field(default_factory=ViTConfig)
    text: LlamaConfig = field(default_factory=LlamaConfig)
    ignore_index: int = -100


class QwenVL(nn.Layer):
    def __init__(self, cfg: QwenVLConfig):
        super().__init__()
        self.cfg = cfg
        self.visual = VisionTransformer(cfg.vision)
        self.projector = nn.Linear(cfg.vision.hidden_size,
                                   cfg.text.hidden_size)
        self.language_model = LlamaModel(cfg.text)
        self.lm_head = nn.Linear(cfg.text.hidden_size, cfg.text.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids, pixel_values=None):
        """input_ids: [B, S_txt]; pixel_values: [B, C, H, W] or None.
        Visual tokens are prepended; returns logits over the joint seq."""
        emb = self.language_model.embed_tokens(input_ids)
        if pixel_values is not None:
            vis = self.projector(self.visual(pixel_values))
            emb = pt_ops_concat([vis.astype(emb.dtype), emb], axis=1)
        x = emb
        for blk in self.language_model.layers:
            x = blk(x)
        x = self.language_model.norm(x)
        return self.lm_head(x)

    def loss(self, logits, labels, num_visual_tokens=None):
        """CE over text targets only: the visual prefix is sliced off the
        logits before next-token alignment."""
        if num_visual_tokens is None:
            num_visual_tokens = logits.shape[1] - labels.shape[1]
        if num_visual_tokens > 0:
            logits = logits[:, num_visual_tokens:]
        return F.cross_entropy(logits[:, :-1, :], labels[:, 1:])

    def generate(self, input_ids, pixel_values=None, max_new_tokens=32,
                 do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                 eos_token_id=None, seed=None, max_cache_len=None):
        """Multimodal generation: the image's visual tokens prefill the
        joint sequence (rope positions cover prefix + text, matching the
        training forward), then the text decodes through the same
        on-device scan loop the pure-text models use. Returns the full
        TEXT sequence (prompt + new tokens); visual tokens are internal.
        """
        import types

        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..core.tensor import unwrap, wrap
        from ..inference.decode_loop import greedy_generate, sample_generate
        from ..jit.hoist import hoisted_jit
        from .generation import _make_llama_decode_fns

        ids_np = np.asarray(unwrap(input_ids)).astype(np.int32)
        if ids_np.ndim == 1:
            ids_np = ids_np[None]
        B, T = ids_np.shape

        vis = None
        n_vis = 0
        if pixel_values is not None:
            vis = unwrap(self.projector(self.visual(pixel_values)))
            n_vis = vis.shape[1]
        total = n_vis + T
        if max_cache_len is None:
            max_cache_len = min(self.cfg.text.max_seq_len,
                                total + max_new_tokens)
        if total + max_new_tokens > max_cache_len:
            raise ValueError(
                f"visual ({n_vis}) + prompt ({T}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_cache_len "
                f"({max_cache_len})")

        key = ("_pt_vl_bundle", max_cache_len)
        cached = getattr(self, "_pt_decode_cache", None)
        if cached is None:
            cached = self._pt_decode_cache = {}
        bundle = cached.pop(key, None)
        if bundle is None:
            view = types.SimpleNamespace(cfg=self.cfg.text,
                                         model=self.language_model,
                                         lm_head=self.lm_head)
            fns = _make_llama_decode_fns(view, max_cache_len)
            bundle = fns + (hoisted_jit(fns[2], donate_argnums=(1,)),)
        cached[key] = bundle                   # LRU: newest at the back
        while len(cached) > 4:                 # bundles pin weight copies
            cached.pop(next(iter(cached)))
        init_caches, embed_fn, step_fn, head_fn, prefill_jit = bundle

        table = unwrap(self.language_model.embed_tokens.weight)
        x0 = table[jnp.asarray(ids_np)]
        if vis is not None:
            x0 = jnp.concatenate([vis.astype(x0.dtype), x0], axis=1)
        caches = init_caches(B)
        out, caches = prefill_jit(x0, caches, jnp.int32(0))
        last_logits = head_fn(out[:, -1:])[:, -1]

        if do_sample:
            if seed is None:
                seed = int(np.random.randint(0, 2**31))
            new_ids, _ = sample_generate(
                embed_fn, step_fn, head_fn, caches, last_logits, total,
                max_new_tokens, jax.random.PRNGKey(seed),
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_token_id=eos_token_id)
        else:
            first = jnp.argmax(last_logits, -1).astype(jnp.int32)
            new_ids, _ = greedy_generate(
                embed_fn, step_fn, head_fn, caches, first, total,
                max_new_tokens, eos_token_id=eos_token_id)
        full = np.concatenate([ids_np, np.asarray(new_ids)], axis=1)
        return wrap(jnp.asarray(full))


def shard_qwen_vl(model, process_mesh):
    """auto_parallel annotation for a dp×mp ProcessMesh: wide projections
    sharded over 'mp', everything else replicated; GSPMD completes."""
    return shard_params_by_name(model, process_mesh,
                                ("qkv", "mlp", "gate_proj", "up_proj",
                                 "down_proj", "lm_head"))


def qwen_vl_tiny(**kw):
    vis = ViTConfig(image_size=16, patch_size=4, in_channels=3,
                    hidden_size=32, num_layers=2, num_heads=4)
    txt = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=128,
                      max_seq_len=128)
    return QwenVLConfig(vision=vis, text=txt, **kw)
