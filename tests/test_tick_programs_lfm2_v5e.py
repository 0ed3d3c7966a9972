"""LFM2-24B-A2B's tick programs at the geometry of its cell
(``perfbench/traffic/assist-steady.json``), compiled for a DESCRIBED TPU
v5e (no chip; the helpers and fixtures are ``test_tick_programs_v5e``'s).

10 of the 40 published layers (2 dense conv layers, then two periods
``attn conv conv conv`` of 64 experts), 64 slots, page 16, 4,096
positions, 16,385 pages. The conv layers are XLA compositions and the
two attention layers go through the ONE paged decode kernel and the ONE
ragged-prefill kernel, each handed a POOL index. What is held: the chip's
compiler takes the decode tick and the widest and a narrow prefill launch
(1,024 rows each) at the real size; they fit beside the 10.53 GB of
weights; the pool has 2 layers and stays where it is; the slot state is aliased
(donated and carried like the pool)."""
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
    from paddle_tpu.models.lfm2 import _PUBLISHED_TYPES, Lfm2MoeConfig
    return Lfm2MoeConfig(num_hidden_layers=10,
                         layer_types=_PUBLISHED_TYPES[:10])


def _weight_shapes(cfg):
    from paddle_tpu.models import lfm2
    raw = {n: jax.ShapeDtypeStruct(s, lfm2.param_dtype(cfg, n))
           for n, s in lfm2.param_shapes(cfg).items()}
    tree = {"table": raw["model.embed_tokens.weight"],
            "norm": raw["model.embedding_norm.weight"]}
    tree.update({leaf: raw[name]
                 for leaf, name in lfm2._BUNDLE_LEAVES.items()})
    return tree


def _bundle(cfg, weights):
    model = types.SimpleNamespace(
        cfg=cfg, _pt_stacked_weights={(None, None): weights})
    return generation._make_llama_decode_fns(
        model, CELL["cache_len"], cache_backend="paged",
        page_size=CELL["page"], num_pages=CELL["num_pages"])


def _caches(cfg, shapes):
    return jax.eval_shape(lambda: _bundle(cfg, shapes)[0](CELL["slots"]))


def _assert_fits(exe, caches):
    pool = caches["pool"]
    assert set(pool) == {"k", "v"} and pool["k"].shape == (2, 16385, 16, 512)
    carried = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in list(pool.values()) + [caches["state"]])
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= carried
    # weights 10.53 GB + pool 1.07 GB + temp inside 16 GB, with room
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 14.5e9
    whole = ",".join(map(str, pool["k"].shape))
    layer = ",".join(map(str, pool["k"].shape[1:]))
    bad = []
    for line in exe.as_text().splitlines():
        m = _INSTR.search(line)
        if m and m.group(4) in ("copy", "dynamic-slice",
                                "dynamic-update-slice") \
                and m.group(2) in (whole, "1," + layer, layer):
            bad.append(line.strip()[:160])
    assert not bad, "\n".join(bad[:8])
    return mem


def test_cache_tree_has_a_pool_layer_an_attention_layer():
    cfg = _cfg()
    caches = _caches(cfg, _weight_shapes(cfg))
    assert caches["pool"]["k"].shape == caches["pool"]["v"].shape \
        == (2, 16385, 16, 8 * 64)
    assert caches["state"].shape == (8, 64, 2, 2048)    # conv layers, slots
    assert caches["route"].shape == (8, 64, 4)          # expert layers
    assert caches["bt"].shape == (64, 256)


def test_lfm2_decode_tick_compiles_and_fits(one_chip, as_on_chip):
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
    mem = _assert_fits(exe, caches)
    assert mem.temp_size_in_bytes < 0.5e9
    # ONE decode kernel call a program would need a loop; the unrolled
    # spec has one call an ATTENTION layer, each over a pool index
    calls = [line for line in exe.as_text().splitlines()
             if "custom-call(" in line and "paged_attention_decode"
             in line.split("custom-call(")[0]]
    assert len(calls) == 2
    # the read-back: tokens, then the experts of the 8 EXPERT layers
    out = jax.eval_shape(decode_tick, *specs)
    assert out[4].shape == (64, 1 + 8 * 4)


def test_lfm2_prefill_launches_compile_and_fit(one_chip, as_on_chip):
    """The widest launch (1 chunk x 1,024 rows, its slot and real row
    count given: the rows a budget of 1,024 tokens can fill), and the
    narrow one with a row a slot (64 x 16)."""
    cfg = _cfg()
    shapes = _weight_shapes(cfg)
    caches = _caches(cfg, shapes)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)

    def launch(weights, tokens, t0, caches, out_idx, take, slots):
        return _bundle(cfg, weights)[4](tokens, t0, caches, out_idx, take,
                                        slots)

    exe = _compile(launch, (3,), one_chip, shapes, i32(1, 1024), i32(1),
                   caches, i32(1), i32(1), i32(1))
    assert "ragged_prefill_attention" in exe.as_text()
    # one call an ATTENTION layer of the unrolled spec, each over a
    # dynamic grid
    assert len(_prefill_kernels(exe)) == 2
    _assert_fits(exe, caches)
    exe = _compile(launch, (3,), one_chip, shapes, i32(64, 16), i32(64),
                   caches, i32(64), i32(64), i32(64))
    assert len(_prefill_kernels(exe)) == 2
    _assert_fits(exe, caches)
