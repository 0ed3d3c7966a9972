"""Trinity-Mini's tick programs at the geometry of its cell
(``perfbench/traffic/mixed-steady.json``), compiled for a DESCRIBED TPU
v5e (no chip; the helpers and fixtures are ``test_tick_programs_v5e``'s).

6 of the 32 published layers (2 dense window layers, then one period
``window full window window`` of 128 experts and a shared one), 32
slots, page 16, 32,768 positions, 65,537 pages for the FULL layer, and a
ring of ``ring_pages(2048, 1024, 16)`` = 193 pages a slot for each of the
5 WINDOW layers. Both kinds go through the ONE paged decode kernel and
the ONE ragged-prefill kernel, a window layer's call with its lower page
bound. What is held: the chip's compiler takes the decode tick and the
widest and a narrow prefill launch at the real size; they fit beside the
8.61 GB of weights; both pools are aliased (donated and carried) and
stay where they are."""
import types

import jax
import jax.numpy as jnp
import numpy as np
from test_tick_programs_v5e import (_INSTR, _compile,  # noqa: F401
                                    _prefill_kernels, as_on_chip, one_chip,
                                    topo)

from paddle_tpu.models import generation

CELL = dict(slots=32, page=16, cache_len=32768, num_pages=65537,
            rows=1024)
RING = 193


def _cfg():
    from paddle_tpu.models.afmoe import _PUBLISHED_TYPES, AfmoeConfig
    return AfmoeConfig(num_hidden_layers=6,
                       layer_types=(_PUBLISHED_TYPES * 2)[:6])


def _weight_shapes(cfg):
    from paddle_tpu.models import afmoe
    raw = {n: jax.ShapeDtypeStruct(s, afmoe.param_dtype(cfg, n))
           for n, s in afmoe.param_shapes(cfg).items()}
    tree = {"table": raw["model.embed_tokens.weight"],
            "norm": raw["model.norm.weight"],
            "head": raw["lm_head.weight"]}
    tree.update({leaf: raw[name]
                 for leaf, name in afmoe._BUNDLE_LEAVES.items()})
    return tree


def _bundle(cfg, weights):
    model = types.SimpleNamespace(
        cfg=cfg, _pt_stacked_weights={(None, None): weights})
    return generation._make_llama_decode_fns(
        model, CELL["cache_len"], cache_backend="paged",
        page_size=CELL["page"], num_pages=CELL["num_pages"],
        window_rows=CELL["rows"])


def _caches(cfg, shapes):
    return jax.eval_shape(lambda: _bundle(cfg, shapes)[0](CELL["slots"]))


def _assert_fits(exe, caches):
    pool, ring = caches["pool"], caches["ring"]
    carried = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in list(pool.values()) + list(ring.values()))
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= carried
    # weights 8.61 GB + pools 3.17 GB + temp inside 16 GB, with room
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 14.5e9
    shapes = set()
    for leaf in (pool["k"], ring["k"]):
        shapes |= {",".join(map(str, leaf.shape)),
                   "1," + ",".join(map(str, leaf.shape[1:])),
                   ",".join(map(str, leaf.shape[1:]))}
    bad = []
    for line in exe.as_text().splitlines():
        m = _INSTR.search(line)
        if m and m.group(4) in ("copy", "dynamic-slice",
                                "dynamic-update-slice") \
                and m.group(2) in shapes:
            bad.append(line.strip()[:160])
    assert not bad, "\n".join(bad[:8])
    return mem


def test_cache_tree_has_a_pool_for_the_full_layer_and_a_ring():
    cfg = _cfg()
    caches = _caches(cfg, _weight_shapes(cfg))
    assert caches["pool"]["k"].shape == (1, 65537, 16, 4 * 128)
    assert generation.ring_pages(2048, 1024, 16) == RING
    assert caches["ring"]["k"].shape == caches["ring"]["v"].shape \
        == (5, 32 * RING + 1, 16, 4 * 128)
    assert caches["rbt"].shape == caches["bt"].shape == (32, 2048)
    assert caches["route"].shape == (4, 32, 8)          # expert layers
    gb = lambda a: 2 * int(np.prod(a.shape)) * a.dtype.itemsize / 1e9
    assert round(gb(caches["pool"]["k"]), 2) == 2.15
    assert round(gb(caches["ring"]["k"]), 2) == 1.01


def test_afmoe_decode_tick_compiles_and_fits(one_chip, as_on_chip):
    from paddle_tpu.inference.continuous_batching import (
        _PUSH_COLS, ContinuousBatchingServer)
    cfg = _cfg()
    shapes = _weight_shapes(cfg)
    caches = _caches(cfg, shapes)

    def decode_tick(weights, tok, caches, t, keys, push):
        b = _bundle(cfg, weights)
        srv = types.SimpleNamespace(
            _embed_fn=b[1], _step_fn=b[2], _head_fn=b[3], do_sample=False,
            _temperature=1.0, _top_k=0, _top_p=1.0, tick_block=1,
            max_cache_len=CELL["cache_len"])
        return ContinuousBatchingServer._build_decode_step(srv)._fn(
            tok, caches, t, keys, push)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    n = CELL["slots"]
    specs = (shapes, i32(n), caches, i32(n),
             jax.ShapeDtypeStruct((n, 2), jnp.uint32),
             jax.ShapeDtypeStruct((n, _PUSH_COLS), jnp.uint32))
    exe = _compile(decode_tick, (2,), one_chip, *specs)
    mem = _assert_fits(exe, caches)
    assert mem.temp_size_in_bytes < 1.0e9
    # one decode kernel call an attention layer of the unrolled spec: the
    # full layer's and the 5 window layers'
    calls = [line for line in exe.as_text().splitlines()
             if "custom-call(" in line and "paged_attention_decode"
             in line.split("custom-call(")[0]]
    assert len(calls) == 6
    out = jax.eval_shape(decode_tick, *specs)
    assert out[4].shape == (n, 1 + 4 * 8)


def test_afmoe_prefill_launches_compile_and_fit(one_chip, as_on_chip):
    """The widest launch (1 chunk x 1,024 rows), and the narrow one with a
    row a slot (32 x 32)."""
    cfg = _cfg()
    shapes = _weight_shapes(cfg)
    caches = _caches(cfg, shapes)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)

    def launch(weights, tokens, t0, caches, out_idx, take, slots):
        return _bundle(cfg, weights)[4](tokens, t0, caches, out_idx, take,
                                        slots)

    for rows, width in ((1, 1024), (32, 32)):
        exe = _compile(launch, (3,), one_chip, shapes, i32(rows, width),
                       i32(rows), caches, i32(rows), i32(rows), i32(rows))
        assert len(_prefill_kernels(exe)) == 6
        _assert_fits(exe, caches)
