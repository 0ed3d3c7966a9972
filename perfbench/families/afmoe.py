"""The ``afmoe`` family: from a configuration file (the keys of the published
``config.json`` of arcee-ai's Trinity-Mini) to the program's model, and to the
plain reference beside it. Serving only: what this configuration brings (window
layers whose keys live in a ring of pages a slot, beside full layers that keep
the whole context) exists only while serving, and at 16 bytes a parameter a
chip cannot hold one expert layer's 128 experts, so the family has no training
functions.

Weights are seeded, not trained: N(0, ``INIT_STD``) matrices, RMSNorm gains 1,
and a NON-zero ``expert_bias`` from the same draw (so that choosing and
weighing experts differ), every leaf drawn in ONE jitted call in the type it is
served in; the model adopts those arrays without a copy. Leaves named under the
configuration's ``assumed.init_scale`` are drawn at that multiple of the range;
``assumed.init_scale_why`` and PERF.md section 6 say why."""
import jax

from perfbench import reference_afmoe

INIT_STD = 0.02
# the keys of the configuration file the program's config takes
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers", "layer_types",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "num_experts", "num_experts_per_tok", "num_shared_experts",
             "num_dense_layers", "sliding_window", "rope_theta",
             "rms_norm_eps", "route_norm", "route_scale", "score_func",
             "mup_enabled", "tie_word_embeddings", "max_position_embeddings")
# the most positions whose logits a check reads: the answers of the mixes
# this configuration runs are capped at 1,024 tokens
HEAD_ROWS = 2048


def sizes(config, rehearse=False):
    """The sizes the run uses: the file's own, or its ``rehearse`` block
    (tiny widths, CPU tests only) over them. ``n_layer`` and ``n_embd`` are
    what ``serving.build_server`` prints a pool size from: the layers that
    have a page pool (the FULL layers; a window layer holds a ring) and the
    K or V row's width."""
    c = dict(config)
    c.update(config.get("assumed", {}))
    if rehearse:
        c.update(config["rehearse"])
    types = c["layer_types"][:c["num_hidden_layers"]]
    c["n_layer"] = sum(t == "full_attention" for t in types)
    c["n_embd"] = c["head_dim"] * c["num_key_value_heads"]
    return c


def vocab(config, rehearse=False):
    return sizes(config, rehearse)["vocab_size"]


def program_config(config, rehearse=False):
    from paddle_tpu.models.afmoe import AfmoeConfig
    c = sizes(config, rehearse)
    return AfmoeConfig(dtype=c["dtype"], initializer_range=INIT_STD,
                       **{k: c[k] for k in PUBLISHED})


def build_model(config, seed, rehearse=False, train=False):
    """The program's own ``AfmoeForCausalLM`` over weights from ``seed``:
    every leaf drawn in one jitted call on the device in the type it is
    served in, stacked a kind of sublayer and held once."""
    from paddle_tpu.models import afmoe
    if train:
        raise NotImplementedError("the afmoe family serves; it has no "
                                  "train step")
    cfg = program_config(config, rehearse)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                             seed // 2 ** 31)
    weights = afmoe.init_weights(cfg, key=key, scale=sizes(
        config, rehearse).get("init_scale", {}))
    model = afmoe.AfmoeForCausalLM(cfg, weights=weights)
    model.eval()
    return model


def n_params(model):
    return sum(int(a.size) for a in model.raw_params().values())


def reference_row_logits(config, params, ids, width, rehearse=False):
    return reference_afmoe.row_logits(params, ids, width,
                                      sizes(config, rehearse),
                                      head_rows=HEAD_ROWS)
