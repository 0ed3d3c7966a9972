"""The plain reference against the model's own float32 forward at a tiny
width, so that a wrong reference is found here and not on the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, reference_gpt2
from perfbench.families import gpt2


@pytest.fixture(scope="module")
def tiny():
    config = harness.load_config(harness.load_manifest(), "gpt2-medium")
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTForCausalLM
    pt.seed(3)
    model = GPTForCausalLM(gpt2.program_config(config, rehearse=True))
    model.eval()
    # biases and LayerNorm parameters off their defaults, or a reference
    # that forgot one would still agree
    rng = np.random.default_rng(0)
    model.load_raw_params({
        n: jnp.asarray(rng.normal(0.0, 0.1, a.shape) + (a.ndim == 1
                       and n.endswith("ln1.weight")), jnp.float32)
        if a.ndim == 1 else a for n, a in model.raw_params().items()})
    return config, model


def test_logits_agree_with_the_models_own_f32_forward(tiny):
    from paddle_tpu.jit import functional_call
    config, model = tiny
    c = gpt2.sizes(config, rehearse=True)
    params = model.raw_params()
    ids = np.random.default_rng(1).integers(
        0, c["padded_vocab_size"], (2, 33)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(functional_call(model, params, jnp.asarray(ids)))
    got = np.asarray(reference_gpt2.logits(params, ids, c["n_head"],
                                           c["layer_norm_epsilon"]))
    assert got.dtype == np.float32 and got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    # a wrong head count or a missing causal mask must NOT pass
    wrong = np.asarray(reference_gpt2.logits(params, ids, c["n_head"] // 2))
    assert np.abs(wrong - want).max() > 1e-2 * np.abs(want).max()


def test_right_padding_does_not_reach_earlier_positions(tiny):
    config, model = tiny
    c = gpt2.sizes(config, rehearse=True)
    ids = np.arange(1, 20, dtype=np.int32)
    a = gpt2.reference_row_logits(config, model.raw_params(), ids, 32,
                                  rehearse=True)
    b = np.asarray(reference_gpt2.logits(model.raw_params(), ids[None],
                                         c["n_head"]))[0]
    assert a.shape == (19, c["padded_vocab_size"])
    assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max()


def test_reference_loss_is_the_models_loss(tiny):
    from paddle_tpu.jit import functional_call
    config, model = tiny
    ids = np.random.default_rng(2).integers(0, 256, (3, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = functional_call(model, model.raw_params(), jnp.asarray(ids))
        want = float(gpt2.ce_loss(logits, jnp.asarray(ids)))
    got = gpt2.reference_loss(config, model.raw_params(), ids, rehearse=True)
    assert got == pytest.approx(want, rel=1e-5)


def test_bf16_weights_are_read_exactly(tiny):
    config, model = tiny
    c = gpt2.sizes(config, rehearse=True)
    half = {n: a.astype(jnp.bfloat16) for n, a in model.raw_params().items()}
    back = {n: a.astype(jnp.float32) for n, a in half.items()}
    ids = np.arange(2, 18, dtype=np.int32)[None]
    a = np.asarray(reference_gpt2.logits(half, ids, c["n_head"]))
    b = np.asarray(reference_gpt2.logits(back, ids, c["n_head"]))
    assert a.dtype == np.float32 and np.array_equal(a, b)


def test_weights_from_the_seed_in_one_call():
    shapes = {"lm_head_weight": (256, 64), "gpt.ln_f.weight": (64,),
              "gpt.ln_f.bias": (64,)}
    a = gpt2.init_params(shapes, 2 ** 31 + 7, jnp.bfloat16)
    b = gpt2.init_params(shapes, 2 ** 31 + 7, jnp.bfloat16)
    c = gpt2.init_params(shapes, 7, jnp.bfloat16)
    assert all(v.dtype == jnp.bfloat16 for v in a.values())
    assert all(np.array_equal(a[n], b[n]) for n in shapes)
    assert not np.array_equal(a["lm_head_weight"], c["lm_head_weight"])
    assert float(jnp.std(a["lm_head_weight"].astype(jnp.float32))
                 ) == pytest.approx(gpt2.INIT_STD, rel=0.1)
    assert np.all(np.asarray(a["gpt.ln_f.weight"], np.float32) == 1.0)
    assert np.all(np.asarray(a["gpt.ln_f.bias"], np.float32) == 0.0)


def test_built_model_has_the_published_shape():
    config = harness.load_config(harness.load_manifest(), "gpt2-medium")
    cfg = gpt2.program_config(config)
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads) == (1024, 24, 16)
    assert cfg.intermediate_size == 4096 and cfg.vocab_size == 50304
    assert cfg.max_seq_len == 1024 and cfg.dropout == 0.0
    model = gpt2.build_model(config, 5, rehearse=True)
    assert all(a.dtype == jnp.bfloat16 for a in model.raw_params().values())
    assert gpt2.n_params(model) == sum(
        int(np.prod(a.shape)) for a in model.raw_params().values())
