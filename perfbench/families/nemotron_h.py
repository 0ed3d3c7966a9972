"""The ``nemotron_h`` family: from a configuration file (the keys of the
published ``config.json`` of nvidia's NVIDIA-Nemotron-3-Super-120B-A12B, plus
``router_experts`` and ``held_first`` for the expert share) to the program's
model, and to the plain reference beside it. Serving only: what this
configuration brings (a float32 recurrent state a slot, a layer that is one
sublayer, an expert layer that holds a share of the router's experts) does
its work while serving, and at 16 bytes a parameter no cut within the guide's
floors fits a chip, so the family has no training functions.

Weights are seeded, not trained (``paddle_tpu.models.nemotron_h.init_weights``
says how each leaf is drawn), every leaf in ONE jitted call in the type it is
served in; the model adopts those arrays without a copy. Leaves named under
the configuration's ``assumed.init_scale`` are drawn at that multiple of the
range; ``assumed.init_scale_why`` and PERF.md section 6 say why and give every
reading."""
import jax

from perfbench import reference_nemotron_h

INIT_STD = 0.02
PUBLISHED = ("vocab_size", "hidden_size", "num_hidden_layers",
             "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
             "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
             "expand", "use_conv_bias", "time_step_min", "time_step_max",
             "time_step_floor", "num_attention_heads", "num_key_value_heads",
             "head_dim", "n_routed_experts", "router_experts", "held_first",
             "num_experts_per_tok", "moe_intermediate_size",
             "moe_latent_size", "moe_shared_expert_intermediate_size",
             "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
             "n_group", "topk_group", "mlp_hidden_act", "layer_norm_epsilon",
             "tie_word_embeddings", "max_position_embeddings")


def sizes(config, rehearse=False):
    """The sizes the run uses: the file's own, or its ``rehearse`` block
    (tiny widths, CPU tests only) over them. ``n_layer`` and ``n_embd`` are
    what ``serving.build_server`` prints a pool size from: the layers that
    have a pool (attention) and the K or V row's width."""
    c = dict(config)
    c.update(config.get("assumed", {}))
    if rehearse:
        c.update(config["rehearse"])
    c["n_layer"] = c["hybrid_override_pattern"].count("*")
    c["n_embd"] = c["head_dim"] * c["num_key_value_heads"]
    return c


def vocab(config, rehearse=False):
    return sizes(config, rehearse)["vocab_size"]


def program_config(config, rehearse=False):
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    c = sizes(config, rehearse)
    return NemotronHConfig(dtype=c["dtype"], initializer_range=INIT_STD,
                           **{k: c[k] for k in PUBLISHED})


def build_model(config, seed, rehearse=False, train=False):
    """The program's own ``NemotronHForCausalLM`` over weights from
    ``seed``: every leaf drawn in one jitted call on the device in the type
    it is served in, stacked a kind of sublayer and held once."""
    from paddle_tpu.models import nemotron_h
    if train:
        raise NotImplementedError("the nemotron_h family serves; it has no "
                                  "train step")
    cfg = program_config(config, rehearse)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                             seed // 2 ** 31)
    weights = nemotron_h.init_weights(cfg, key=key, scale=sizes(
        config, rehearse).get("init_scale", {}))
    model = nemotron_h.NemotronHForCausalLM(cfg, weights=weights)
    model.eval()
    return model


def n_params(model):
    return sum(int(a.size) for a in model.raw_params().values())


def reference_row_logits(config, params, ids, width, rehearse=False):
    return reference_nemotron_h.row_logits(params, ids, width,
                                           sizes(config, rehearse))
