"""The ``lfm2_moe`` family: from a configuration file (the keys of the
published ``config.json`` of LiquidAI's LFM2-24B-A2B) to the program's model,
and to the plain reference beside it. Serving only: the mechanisms this
configuration brings (per-slot convolution state beside the page pool, a layer
loop over unlike layers) exist only while serving, and at 16 bytes a parameter
an eighth of a layer's experts fits a chip, so the family has no training
functions.

Weights are seeded, not trained: N(0, ``INIT_STD``) matrices, RMSNorm gains
1, and a NON-zero ``expert_bias`` from the same draw (so that choosing and
weighing experts differ), every leaf drawn in ONE jitted call in the type it
is served in; the model adopts those arrays without a copy. Leaves named under
the configuration's ``assumed.init_scale`` are drawn at that multiple of the
range; ``assumed.init_scale_why`` and PERF.md section 6 say why and give every
reading."""
import jax

from perfbench import reference_lfm2_moe

INIT_STD = 0.02
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers", "layer_types",
             "num_attention_heads", "num_key_value_heads", "num_experts",
             "num_experts_per_tok", "num_dense_layers", "conv_L_cache",
             "conv_bias", "norm_eps", "norm_topk_prob", "use_expert_bias",
             "routed_scaling_factor", "rope_parameters",
             "max_position_embeddings")


def sizes(config, rehearse=False):
    """The sizes the run uses: the file's own, or its ``rehearse`` block
    (tiny widths, CPU tests only) over them. ``n_layer`` and ``n_embd`` are
    what ``serving.build_server`` prints a pool size from: the layers that
    have a pool (attention) and the K or V row's width."""
    c = dict(config)
    c.update(config.get("assumed", {}))
    if rehearse:
        c.update(config["rehearse"])
    c["n_layer"] = sum(t == "full_attention" for t in c["layer_types"])
    c["n_embd"] = (c["hidden_size"] // c["num_attention_heads"]
                   * c["num_key_value_heads"])
    return c


def vocab(config, rehearse=False):
    return sizes(config, rehearse)["vocab_size"]


def program_config(config, rehearse=False):
    from paddle_tpu.models.lfm2 import Lfm2MoeConfig
    c = sizes(config, rehearse)
    return Lfm2MoeConfig(dtype=c["dtype"], initializer_range=INIT_STD,
                         tie_word_embeddings=c["tie_word_embeddings"],
                         **{k: c[k] for k in PUBLISHED})


def build_model(config, seed, rehearse=False, train=False):
    """The program's own ``Lfm2MoeForCausalLM`` over weights from ``seed``:
    every leaf drawn in one jitted call on the device in the type it is
    served in, stacked a kind of sublayer and held once."""
    from paddle_tpu.models import lfm2
    if train:
        raise NotImplementedError("the lfm2_moe family serves; it has no "
                                  "train step")
    cfg = program_config(config, rehearse)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                             seed // 2 ** 31)
    weights = lfm2.init_weights(cfg, key=key, scale=sizes(
        config, rehearse).get("init_scale", {}))
    model = lfm2.Lfm2MoeForCausalLM(cfg, weights=weights)
    model.eval()
    return model


def n_params(model):
    return sum(int(a.size) for a in model.raw_params().values())


def reference_row_logits(config, params, ids, width, rehearse=False):
    return reference_lfm2_moe.row_logits(params, ids, width,
                                         sizes(config, rehearse))
