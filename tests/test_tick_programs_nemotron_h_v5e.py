"""Nemotron-3-Super-120B-A12B's tick programs at the geometry of its cell
(``perfbench/traffic/reason-steady.json``), compiled for a DESCRIBED TPU
v5e (no chip; the helpers and fixtures are ``test_tick_programs_v5e``'s).

The first 11 of the 88 published layers (``MEMEMEM*EME``: 5 Mamba-2 layers,
5 latent expert layers holding 128 of the router's 512 experts, 1 attention
layer of 2 K/V heads of 128), a quarter of the vocabulary, 64 slots, page
16, 4,096 positions, 16,385 pages. The Mamba-2 layers (chunked scan in a
launch, one recurrence step in a decode tick) and the expert layers are XLA
compositions; the one attention layer goes through the ONE paged decode
kernel and the ONE ragged-prefill kernel. What is held: the chip's compiler
takes the decode tick and the widest and a narrow prefill launch (1,024
rows each) at the real size; they fit beside the 9.30 GB of weights; the
pool has 1 layer and stays where it is; both leaves of the slot state
(1.36 GB of float32 recurrent state, the convolution windows) are aliased:
donated and carried like the pool, never copied."""
import types

import jax
import jax.numpy as jnp
import numpy as np
from test_tick_programs_v5e import (_INSTR, _compile,  # noqa: F401
                                    _prefill_kernels, as_on_chip, one_chip,
                                    topo)

from paddle_tpu.models import generation

CELL = dict(slots=64, page=16, cache_len=4096, num_pages=16385)


def _cfg():
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    return NemotronHConfig(num_hidden_layers=11,
                           hybrid_override_pattern="MEMEMEM*EME",
                           n_routed_experts=128, router_experts=512,
                           vocab_size=32768)


def _weight_shapes(cfg):
    from paddle_tpu.models import nemotron_h as nh
    raw = {n: jax.ShapeDtypeStruct(s, nh.param_dtype(cfg, n))
           for n, s in nh.param_shapes(cfg).items()}
    tree = {"table": raw["model.embed_tokens.weight"],
            "norm": raw["model.norm_f.weight"],
            "head": raw["lm_head.weight"]}
    tree.update({leaf: raw[name]
                 for leaf, name in nh._BUNDLE_LEAVES.items()})
    return tree


def _bundle(cfg, weights):
    model = types.SimpleNamespace(
        cfg=cfg, _pt_stacked_weights={(None, None): weights})
    return generation._make_llama_decode_fns(
        model, CELL["cache_len"], cache_backend="paged",
        page_size=CELL["page"], num_pages=CELL["num_pages"])


def _caches(cfg, shapes):
    return jax.eval_shape(lambda: _bundle(cfg, shapes)[0](CELL["slots"]))


def _nbytes(tree):
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


def _assert_fits(exe, caches, temp):
    pool = caches["pool"]
    assert set(pool) == {"k", "v"} and pool["k"].shape == (1, 16385, 16, 256)
    carried = _nbytes(pool) + _nbytes(caches["state"])
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= carried
    # weights 9.30 GB + state 1.36 GB + pool 0.27 GB + temp inside 16 GB
    assert _nbytes(caches["state"]) > 1.3e9
    assert mem.temp_size_in_bytes < temp
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 14.5e9
    # neither the pool nor the recurrent state is copied whole
    whole = [",".join(map(str, a.shape))
             for a in (pool["k"], caches["state"]["ssm"])]
    bad = []
    for line in exe.as_text().splitlines():
        m = _INSTR.search(line)
        if m and m.group(4) == "copy" and m.group(2) in whole:
            bad.append(line.strip()[:160])
    assert not bad, "\n".join(bad[:8])
    return mem


def test_cache_tree_has_one_pool_layer_and_a_state_tree():
    cfg = _cfg()
    shapes = _weight_shapes(cfg)
    assert 9.2e9 < _nbytes(shapes) < 9.4e9             # the cut: 9.30 GB
    caches = _caches(cfg, shapes)
    assert caches["pool"]["k"].shape == caches["pool"]["v"].shape \
        == (1, 16385, 16, 2 * 128)
    state = caches["state"]
    assert (state["conv"].shape, state["conv"].dtype) == (
        (5, 64, 3, 10240), jnp.bfloat16)
    assert (state["ssm"].shape, state["ssm"].dtype) == (
        (5, 64, 128, 64, 128), jnp.float32)
    assert caches["route"].shape == (5, 64, 22)          # expert layers
    assert caches["bt"].shape == (64, 256)


def test_nemotron_h_decode_tick_compiles_and_fits(one_chip, as_on_chip):
    from paddle_tpu.inference.continuous_batching import (
        ContinuousBatchingServer)
    cfg = _cfg()
    shapes = _weight_shapes(cfg)
    caches = _caches(cfg, shapes)

    def decode_tick(weights, tok, caches, t, keys):
        b = _bundle(cfg, weights)
        srv = types.SimpleNamespace(
            _embed_fn=b[1], _step_fn=b[2], _head_fn=b[3], do_sample=False,
            _temperature=1.0, _top_k=0, _top_p=1.0, tick_block=1,
            max_cache_len=CELL["cache_len"])
        return ContinuousBatchingServer._build_decode_step(srv)._fn(
            tok, caches, t, keys)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    specs = (shapes, i32(64), caches, i32(64),
             jax.ShapeDtypeStruct((64, 2), jnp.uint32))
    exe = _compile(decode_tick, (2,), one_chip, *specs)
    _assert_fits(exe, caches, temp=0.6e9)
    calls = [line for line in exe.as_text().splitlines()
             if "custom-call(" in line and "paged_attention_decode"
             in line.split("custom-call(")[0]]
    assert len(calls) == 1                  # the ONE attention layer
    # the read-back: tokens, then the experts of the 5 EXPERT layers
    out = jax.eval_shape(decode_tick, *specs)
    assert out[4].shape == (64, 1 + 5 * 22)


def test_nemotron_h_prefill_launches_compile_and_fit(one_chip, as_on_chip):
    """The widest launch (1 chunk x 1,024 rows, what a budget of 1,024
    tokens can fill: 8 chunks of the scan, the state passed between
    them), and the narrow one with a row a slot (64 x 16: one chunk of 16
    rows, the most state a launch views)."""
    cfg = _cfg()
    shapes = _weight_shapes(cfg)
    caches = _caches(cfg, shapes)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)

    def launch(weights, tokens, t0, caches, out_idx, take, slots):
        return _bundle(cfg, weights)[4](tokens, t0, caches, out_idx, take,
                                        slots)

    exe = _compile(launch, (3,), one_chip, shapes, i32(1, 1024), i32(1),
                   caches, i32(1), i32(1), i32(1))
    assert len(_prefill_kernels(exe)) == 1
    _assert_fits(exe, caches, temp=3.0e9)
    exe = _compile(launch, (3,), one_chip, shapes, i32(64, 16), i32(64),
                   caches, i32(64), i32(64), i32(64))
    assert len(_prefill_kernels(exe)) == 1
    _assert_fits(exe, caches, temp=3.0e9)
