"""The ``gpt2`` family: from a configuration file (the keys of the published
``config.json``) to the program's model, and to the plain reference beside
it. A configuration names its family under ``"family"``; a new family is a
new file here with the same functions."""
import jax
import jax.numpy as jnp

from perfbench import reference_gpt2, stats

INIT_STD = 0.02            # GPT-2's published initializer_range


def sizes(config, rehearse=False):
    """The sizes the run uses: the file's own, or its ``rehearse`` block
    (tiny widths, CPU tests only) over them."""
    c = dict(config)
    c.update(config.get("assumed", {}))
    if rehearse:
        c.update(config["rehearse"])
    return c


def vocab(config, rehearse=False):
    return sizes(config, rehearse)["padded_vocab_size"]


def program_config(config, rehearse=False):
    from paddle_tpu.models.gpt import GPTConfig
    c = sizes(config, rehearse)
    return GPTConfig(
        vocab_size=c["padded_vocab_size"], hidden_size=c["n_embd"],
        num_layers=c["n_layer"], num_heads=c["n_head"],
        max_seq_len=c["n_positions"],
        intermediate_size=c.get("n_inner") or 4 * c["n_embd"],
        dropout=c["dropout"], layer_norm_eps=c["layer_norm_epsilon"])


def init_params(shapes, seed, dtype):
    """Every weight in ONE jitted call, on the device, in the type it is
    served in: N(0, 0.02) matrices, LayerNorm gains 1, biases 0."""
    names = sorted(shapes)

    @jax.jit
    def make(key):
        out = {}
        for k, name in zip(jax.random.split(key, len(names)), names):
            shape = shapes[name]
            if len(shape) >= 2:
                out[name] = INIT_STD * jax.random.normal(k, shape, dtype)
            elif name.endswith("weight"):
                out[name] = jnp.ones(shape, dtype)
            else:
                out[name] = jnp.zeros(shape, dtype)
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                             seed // 2 ** 31)
    return make(key)


def build_model(config, seed, rehearse=False, train=False):
    """The program's own ``GPTForCausalLM`` in bfloat16 with weights from
    ``seed``. The constructor's own initialisers are replaced by constants
    (they would draw every leaf in float32, one eager call each)."""
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.nn import initializer as init
    init.set_global_initializer(init.Constant(0.0), init.Constant(0.0))
    try:
        model = GPTForCausalLM(program_config(config, rehearse))
    finally:
        init.set_global_initializer(None, None)
    model.astype("bfloat16")
    if not train:
        model.eval()
    shapes = {n: tuple(a.shape) for n, a in model.raw_params().items()}
    model.load_raw_params(init_params(shapes, seed, jnp.bfloat16))
    return model


def n_params(model):
    return sum(int(a.size) for a in model.raw_params().values())


def train_flops_per_token(config, model, seq, rehearse=False):
    c = sizes(config, rehearse)
    return stats.transformer_train_flops_per_token(
        n_params(model), c["n_layer"], c["n_embd"], seq)


def ce_loss(logits, labels):
    """Mean next-token cross-entropy, in float32 (chip_smoke.py's)."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
    return -jnp.take_along_axis(logp, labels[:, 1:, None], -1).mean()


def reference_row_logits(config, params, ids, width, rehearse=False):
    c = sizes(config, rehearse)
    return reference_gpt2.row_logits(params, ids, width, c["n_head"],
                                     c["layer_norm_epsilon"])


def reference_loss(config, params, ids, rehearse=False):
    c = sizes(config, rehearse)
    return reference_gpt2.loss(params, ids, c["n_head"],
                               c["layer_norm_epsilon"])
