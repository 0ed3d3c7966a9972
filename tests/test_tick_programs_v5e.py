"""The serving tick programs, compiled for a DESCRIBED TPU v5e (no chip).

The sandbox's libtpu compiles for a chip that is described and not
attached, so these tests see what the chip's compiler does to the paged
tick programs at the benchmark cell's real geometry (GPT-2 medium, 32
slots, page 16, 2,049 pages: a 3.0 GiB pool) without running anything:
layouts, aliasing and bytes of temporaries — never a time.

What they hold (ISSUE 26): inside a tick the page pool is never copied,
sliced out or relaid out. The pool is stored lane-dense
(``[layers, pages, page_size, kv_heads * head_dim]``), carried through
the layer loop and read by the kernels through a layer index, so the
donated argument's buffer IS the result's:

- ``temp_size_in_bytes`` under a quarter of the pool's bytes (the parent
  held a second copy of the pool: 3.8 GiB),
- ``alias_size_in_bytes`` at least the pool's,
- the pool row-major wherever the optimised HLO names its shape,
- no ``copy``, ``dynamic-slice`` or ``dynamic-update-slice`` whose
  result has the pool's whole or per-layer shape.

Everything is built from shapes: the stacked weight tree is a tree of
``ShapeDtypeStruct`` handed to the bundle builder inside the traced
function (no 0.7 GB of weights, no 3 GiB of zeros on the CPU). The
topology is described in a module-scoped fixture that skips — never at
import (one process holds libtpu at a time; see the
``on-chip-measurement`` guide, section 2) — and the persistent compile
cache is off around the compiles (an entry written for a described chip
cannot be read back without one).
"""
import re
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.models import generation
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM, gpt2_tiny

# the cell's server (perfbench/traffic/chat-steady.json)
SLOTS, PAGE, CACHE_LEN = 32, 16, 1024
MEDIUM = dict(cfg=GPTConfig(vocab_size=50304, hidden_size=1024,
                            num_layers=24, num_heads=16, max_seq_len=1024),
              slots=SLOTS, num_pages=SLOTS * CACHE_LEN // PAGE + 1)
# GPT-2 XL as PR 24 ran it on one chip: 25 heads x 64 = 1,600 lanes,
# which is no multiple of 128
XL = dict(cfg=GPTConfig(vocab_size=50304, hidden_size=1600, num_layers=48,
                        num_heads=25, max_seq_len=1024),
          slots=8, num_pages=8 * CACHE_LEN // PAGE + 1)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def as_on_chip():
    """The kernels ask ``on_tpu()`` whether to run Mosaic or their XLA
    reference: answer yes for this module's compiles (and forget the
    answer afterwards), with the persistent compile cache off and the
    chip's own matmul precision (conftest sets "highest" for CPU
    parity; nothing sets it on the chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.pallas import on_tpu
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    on_tpu.cache_clear()
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        assert on_tpu()
    try:
        with jax.default_matmul_precision("default"):
            yield
    finally:
        on_tpu.cache_clear()
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def _weight_shapes(cfg, dtype=jnp.bfloat16):
    """Shapes of ``_make_gpt_decode_fns``'s stacked weight tree."""
    L, H, F, V = (cfg.num_layers, cfg.hidden_size, cfg.intermediate_size,
                  cfg.vocab_size)
    shapes = {
        "table": (V, H), "wpe": (cfg.max_seq_len, H),
        "lnf_w": (H,), "lnf_b": (H,),
        "ln1.weight": (L, H), "ln1.bias": (L, H),
        "ln2.weight": (L, H), "ln2.bias": (L, H),
        "attn.qkv.weight": (L, H, 3 * H), "attn.qkv.bias": (L, 3 * H),
        "attn.proj.weight": (L, H, H), "attn.proj.bias": (L, H),
        "mlp.fc1.weight": (L, H, F), "mlp.fc1.bias": (L, F),
        "mlp.fc2.weight": (L, F, H), "mlp.fc2.bias": (L, H),
    }
    return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in shapes.items()}


def _paged_bundle(cfg, weights, num_pages, mesh=None):
    """The paged GPT decode bundle over ``weights`` (arrays or tracers):
    the bundle builder finds its stacked tree already made, so nothing
    is materialised."""
    model = types.SimpleNamespace(
        cfg=cfg, _pt_stacked_weights={
            (None, None if mesh is None else id(mesh)): weights})
    return generation._make_gpt_decode_fns(
        model, CACHE_LEN, mesh=mesh, cache_backend="paged", page_size=PAGE,
        num_pages=num_pages)


def _decode_tick(cfg, num_pages, mesh=None):
    """The server's own ``decode_tick`` (greedy, one step a tick) over
    a bundle built from the traced weights."""
    from paddle_tpu.inference.continuous_batching import (
        ContinuousBatchingServer)

    def decode_tick(weights, tok, caches, t, keys):
        b = _paged_bundle(cfg, weights, num_pages, mesh)
        srv = types.SimpleNamespace(
            _embed_fn=b[1], _step_fn=b[2], _head_fn=b[3], do_sample=False,
            _temperature=1.0, _top_k=0, _top_p=1.0, tick_block=1,
            max_cache_len=CACHE_LEN)
        tick = ContinuousBatchingServer._build_decode_step(srv)
        return tick._fn(tok, caches, t, keys)

    return decode_tick


def _prefill_tick(cfg, num_pages, mesh=None):
    def prefill_tick(weights, tokens, t0, caches, out_idx, take, slots):
        return _paged_bundle(cfg, weights, num_pages, mesh)[4](
            tokens, t0, caches, out_idx, take, slots)

    return prefill_tick


def _compile(fn, donate, one_chip, *specs):
    specs = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        specs)
    return (jax.jit(fn, donate_argnums=donate).trace(*specs)
            .lower(lowering_platforms=("tpu",)).compile())


def _cache_shapes(cfg, slots, num_pages):
    init = _paged_bundle(cfg, _weight_shapes(cfg), num_pages)[0]
    return jax.eval_shape(lambda: init(slots))


_INSTR = re.compile(r"=\s*(\w+)\[([\d,]*)\](?:\{([\d,]*)[^}]*\})?\s+"
                    r"([\w\-]+)\(")


def _assert_pool_stays(exe, caches):
    """Every way the compiled program moves the pool, in one message."""
    pool = caches["pool"]["k"]
    pool_bytes = 2 * int(np.prod(pool.shape)) * pool.dtype.itemsize
    mem = exe.memory_analysis()
    gib = 2.0 ** 30
    bad = []
    if mem.temp_size_in_bytes >= pool_bytes / 4:
        bad.append(f"temp {mem.temp_size_in_bytes / gib:.2f} GiB against "
                   f"a pool of {pool_bytes / gib:.2f} GiB: the program "
                   f"holds a copy of it")
    if mem.alias_size_in_bytes < pool_bytes:
        bad.append(f"alias {mem.alias_size_in_bytes / gib:.2f} GiB: the "
                   f"donated pool ({pool_bytes / gib:.2f} GiB) is not the "
                   f"result's buffer")
    whole = ",".join(map(str, pool.shape))
    layer = ",".join(map(str, pool.shape[1:]))
    row_major = ",".join(str(i) for i in reversed(range(pool.ndim)))
    seen = 0
    for line in exe.as_text().splitlines():
        m = _INSTR.search(line)
        if m is None:
            continue
        _, dims, layout, opcode = m.groups()
        if dims == whole:
            seen += 1
            if layout is not None and layout != row_major:
                bad.append(f"pool laid out {{{layout}}}: "
                           f"{line.strip()[:160]}")
        if (opcode in ("copy", "dynamic-slice", "dynamic-update-slice")
                and dims in (whole, "1," + layer, layer)):
            bad.append(f"{opcode} of the pool: {line.strip()[:160]}")
    assert seen, "the optimised HLO never names the pool's shape"
    assert not bad, "\n".join(bad[:12])


def _decode_specs(cfg, slots, num_pages):
    caches = _cache_shapes(cfg, slots, num_pages)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    return caches, (_weight_shapes(cfg), i32(slots), caches, i32(slots),
                    jax.ShapeDtypeStruct((slots, 2), jnp.uint32))


def _kernel_calls(exe, name):
    """The custom calls of one paged kernel in a program, each with its
    grid's bound a runtime operand: the call's first operand is a
    scalar ``s32[]``, the step count its schedule made."""
    calls = [line for line in exe.as_text().splitlines()
             if "custom-call(" in line and name in
             line.split("custom-call(")[0]]
    for call in calls:
        assert "operand_layout_constraints={s32[]," in call
    return calls


def _one_decode_kernel(exe):
    """ONE decode-attention custom call a program (the layer loop's)."""
    assert len(_kernel_calls(exe, "paged_attention_decode")) == 1


def _prefill_kernels(exe):
    return _kernel_calls(exe, "ragged_prefill_attention")


@pytest.mark.parametrize("geometry", [MEDIUM, XL], ids=["medium", "xl"])
def test_decode_tick_leaves_the_pool_in_place(geometry, one_chip,
                                              as_on_chip):
    cfg, slots, num_pages = (geometry[k] for k in
                             ("cfg", "slots", "num_pages"))
    caches, specs = _decode_specs(cfg, slots, num_pages)
    exe = _compile(_decode_tick(cfg, num_pages), (2,), one_chip, *specs)
    _one_decode_kernel(exe)
    _assert_pool_stays(exe, caches)
    if geometry is MEDIUM:
        # the cell's program: its temporaries round to 0.00 GiB
        assert exe.memory_analysis().temp_size_in_bytes < 2 ** 30 / 200


def _on_mesh(topo, cfg, slots, num_pages):
    """The cell's geometry on ``v5e:2x2``: ``(mesh, on, weights, caches,
    shard)`` — ``on(spec, *axes)`` places a shape on the mesh, the
    weights split on ``mp`` as the bundle splits them, the pool on its
    lanes, and ``shard`` is each device's quarter of the pool."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = Mesh(np.array(topo.devices), ("mp",))
    dims = {"attn.qkv.weight": 2, "attn.proj.weight": 1,
            "mlp.fc1.weight": 2, "mlp.fc2.weight": 1}

    def on(spec, *axes):
        return jax.ShapeDtypeStruct(spec.shape, spec.dtype,
                                    sharding=NamedSharding(mesh, P(*axes)))

    weights = {k: on(v, *[("mp" if i == dims.get(k) else None)
                          for i in range(len(v.shape))])
               for k, v in _weight_shapes(cfg).items()}
    caches = _cache_shapes(cfg, slots, num_pages)
    cache_specs = {"bt": on(caches["bt"]),
                   "pool": {n: on(a, None, None, None, "mp")
                            for n, a in caches["pool"].items()}}
    # per device: a quarter of the lanes, still row-major and in place
    shard = {n: jax.ShapeDtypeStruct(a.shape[:-1] + (a.shape[-1] // 4,),
                                     a.dtype)
             for n, a in caches["pool"].items()}
    return mesh, on, weights, cache_specs, shard


def test_decode_tick_compiles_for_the_four_chip_mesh(topo, as_on_chip):
    """The mesh path at the cell's geometry on ``v5e:2x2``: one launch a
    kv-head shard under ``shard_map``, the grid's schedule replicated,
    each device's quarter of the pool aliased and left in place."""
    cfg, slots, num_pages = (MEDIUM[k] for k in
                             ("cfg", "slots", "num_pages"))
    mesh, on, weights, cache_specs, shard = _on_mesh(topo, cfg, slots,
                                                     num_pages)
    i32 = lambda *s: on(jax.ShapeDtypeStruct(s, jnp.int32))
    exe = (jax.jit(_decode_tick(cfg, num_pages, mesh), donate_argnums=(2,))
           .trace(weights, i32(slots), cache_specs, i32(slots),
                  on(jax.ShapeDtypeStruct((slots, 2), jnp.uint32)))
           .lower(lowering_platforms=("tpu",)).compile())
    _one_decode_kernel(exe)
    _assert_pool_stays(exe, {"pool": shard})


def test_prefill_tick_compiles_for_the_four_chip_mesh(topo, as_on_chip):
    """The same for a packed launch (8 rows x 128): one prefill call a
    kv-head shard under ``shard_map``, its two-level schedule and step
    count replicated, the pool's quarters left in place."""
    cfg, slots, num_pages = (MEDIUM[k] for k in
                             ("cfg", "slots", "num_pages"))
    mesh, on, weights, cache_specs, shard = _on_mesh(topo, cfg, slots,
                                                     num_pages)
    i32 = lambda *s: on(jax.ShapeDtypeStruct(s, jnp.int32))
    rows = _prefill_specs(cfg, slots, num_pages, 128)[1][1].shape[0]
    exe = (jax.jit(_prefill_tick(cfg, num_pages, mesh), donate_argnums=(3,))
           .trace(weights, i32(rows, 128), i32(rows), cache_specs,
                  i32(rows), i32(rows), i32(rows))
           .lower(lowering_platforms=("tpu",)).compile())
    assert len(_prefill_kernels(exe)) == 1
    _assert_pool_stays(exe, {"pool": shard})


def test_grid_schedule_is_made_outside_the_layer_loop(as_on_chip):
    """Every layer of a tick attends the same lengths: the cumulative
    sum that makes the kernel's schedule sits in the decode program's
    body ONCE, before the layer loop, and the loop's body (the scan's
    ``while``) holds the kernel and no cumulative sum."""
    cfg, slots, num_pages = (MEDIUM[k] for k in
                             ("cfg", "slots", "num_pages"))
    _, specs = _decode_specs(cfg, slots, num_pages)
    text = (jax.jit(_decode_tick(cfg, num_pages), donate_argnums=(2,))
            .trace(*specs).lower(lowering_platforms=("tpu",)).as_text())
    funcs = text.split("func.func ")
    made = [f for f in funcs if "call @cumsum(" in f]
    assert len(made) == 1 and made[0].count("call @cumsum(") == 1
    # ... in the step's function, ahead of its layer loop
    assert -1 < made[0].index("call @cumsum(") < made[0].index(
        "stablehlo.while")
    (body,) = [f for f in funcs if "tpu_custom_call" in f]
    assert body.count("tpu_custom_call") == 1
    assert "cumsum" not in body and "stablehlo.while" not in body


def _prefill_specs(cfg, slots, num_pages, width):
    """A packed launch of the server's: the rows its budget (the cell's
    default, ``max_cache_len``) can fill at this width, at most one a
    slot."""
    from paddle_tpu.inference.continuous_batching import _launch_row_limit
    caches = _cache_shapes(cfg, slots, num_pages)
    rows = min(slots, _launch_row_limit(CACHE_LEN) // width)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    return caches, (_weight_shapes(cfg), i32(rows, width), i32(rows), caches,
                    i32(rows), i32(rows), i32(rows))


def test_prefill_tick_leaves_the_pool_in_place(one_chip, as_on_chip):
    """One ragged-prefill width of the cell's ladder (C = 64, its most
    frequent: 13 of 46 launches in PR 25's window)."""
    cfg, slots, num_pages = (MEDIUM[k] for k in
                             ("cfg", "slots", "num_pages"))
    caches, specs = _prefill_specs(cfg, slots, num_pages, 64)
    exe = _compile(_prefill_tick(cfg, num_pages), (3,), one_chip, *specs)
    assert "ragged_prefill_attention" in exe.as_text()
    assert len(_prefill_kernels(exe)) == 1
    _assert_pool_stays(exe, caches)


@pytest.mark.parametrize("geometry,width", [(MEDIUM, 512), (XL, 128)],
                         ids=["medium-512", "xl-128"])
def test_prefill_kernel_is_one_call_over_a_dynamic_grid(geometry, width,
                                                        one_chip,
                                                        as_on_chip):
    """ONE prefill-attention call a program (the layer loop's), whatever
    the launch's width — its query tiles are grid steps, not a loop of
    launches — with the step count a runtime scalar: the cell's widest
    launch (2 rows x 512) and the 1,600-lane width."""
    cfg, slots, num_pages = (geometry[k] for k in
                             ("cfg", "slots", "num_pages"))
    _, specs = _prefill_specs(cfg, slots, num_pages, width)
    exe = _compile(_prefill_tick(cfg, num_pages), (3,), one_chip, *specs)
    assert len(_prefill_kernels(exe)) == 1


def test_prefill_schedule_is_made_outside_the_layer_loop(as_on_chip):
    """Every layer of a launch attends the same chunks: the cumulative
    sums that make the kernel's schedule sit in the prefill program's
    body ONCE, before the layer loop, and the loop's body holds the
    kernel, no cumulative sum and no loop over query tiles."""
    cfg, slots, num_pages = (MEDIUM[k] for k in
                             ("cfg", "slots", "num_pages"))
    _, specs = _prefill_specs(cfg, slots, num_pages, 128)
    text = (jax.jit(_prefill_tick(cfg, num_pages), donate_argnums=(3,))
            .trace(*specs).lower(lowering_platforms=("tpu",)).as_text())
    funcs = text.split("func.func ")
    made = [f for f in funcs if "call @cumsum" in f
            and not f.startswith("private @cumsum")]
    assert len(made) == 1
    # ... in the step's function, ahead of its layer loop
    assert -1 < made[0].rindex("call @cumsum") < made[0].index(
        "stablehlo.while")
    (body,) = [f for f in funcs if "tpu_custom_call" in f]
    assert body.count("tpu_custom_call") == 1
    assert "cumsum" not in body and "stablehlo.while" not in body


def test_weight_shapes_are_the_bundles_own():
    """The shapes spelled above are those a real (tiny) model stacks:
    the compiles would otherwise judge a program nobody runs."""
    cfg = gpt2_tiny()
    model = GPTForCausalLM(cfg)
    model._decode_bundle(32)
    (tree,) = model._pt_stacked_weights.values()
    want = _weight_shapes(cfg)
    assert {k: tuple(v.shape) for k, v in tree.items()} == \
        {k: v.shape for k, v in want.items()}


# ----------------------------------------------------------------------
# Keye-VL-2.0-30B-A3B's language model at the geometry of its cell
# (perfbench/traffic/longctx-steady.json): 6 layers of 128 experts, 8
# slots, page 16, 16,384 positions, 8,193 pages. The llama-family
# builder's routed FFN and key selection are XLA compositions, so what is
# held here is that the chip's compiler takes both tick programs at the
# real size, that they fit beside the 8.75 GB of weights, and that the K
# and V pools stay where they are (the 64-lane indexer pool is relaid
# out, PERF.md section 7).
KEYE = dict(slots=8, page=16, cache_len=16384, num_pages=8193)


def _keye_cfg():
    from paddle_tpu.models.keye_vl import KeyeVL2Config
    return KeyeVL2Config(num_hidden_layers=6)


def _keye_weight_shapes(cfg):
    from paddle_tpu.models import keye_vl
    raw = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16)
           for n, s in keye_vl.param_shapes(cfg).items()}
    tree = {"table": raw["model.embed_tokens.weight"],
            "norm": raw["model.norm.weight"], "head": raw["lm_head.weight"]}
    tree.update({leaf: raw["model.layers." + name]
                 for leaf, name in keye_vl._BUNDLE_LEAVES.items()})
    return tree


def _keye_bundle(cfg, weights):
    model = types.SimpleNamespace(
        cfg=cfg, _pt_stacked_weights={(None, None): weights})
    return generation._make_llama_decode_fns(
        model, KEYE["cache_len"], cache_backend="paged",
        page_size=KEYE["page"], num_pages=KEYE["num_pages"])


def _assert_keye_fits(exe, caches):
    pool = caches["pool"]
    assert set(pool) == {"k", "v", "ki"}
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in pool.values())
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # weights 8.75 GB + pool 1.71 GB + temp inside 16 GB, with room
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 13.5e9
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


def test_keye_decode_tick_compiles_and_fits(one_chip, as_on_chip):
    from paddle_tpu.inference.continuous_batching import (
        ContinuousBatchingServer)
    cfg = _keye_cfg()
    shapes = _keye_weight_shapes(cfg)
    caches = jax.eval_shape(
        lambda: _keye_bundle(cfg, shapes)[0](KEYE["slots"]))
    assert caches["route"].shape == (6, 8, 8)
    assert caches["kept"].shape == (6, 8)

    def decode_tick(weights, tok, caches, t, keys):
        b = _keye_bundle(cfg, weights)
        srv = types.SimpleNamespace(
            _embed_fn=b[1], _step_fn=b[2], _head_fn=b[3], do_sample=False,
            _temperature=1.0, _top_k=0, _top_p=1.0, tick_block=1,
            max_cache_len=KEYE["cache_len"])
        return ContinuousBatchingServer._build_decode_step(srv)._fn(
            tok, caches, t, keys)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    exe = _compile(decode_tick, (2,), one_chip, shapes, i32(8), caches,
                   i32(8), jax.ShapeDtypeStruct((8, 2), jnp.uint32))
    mem = _assert_keye_fits(exe, caches)
    assert mem.temp_size_in_bytes < 0.5e9
    # no Pallas kernel on this path, and the tokens' read-back carries
    # the experts chosen and the keys kept: [slots, 1 + layers * top_k + 1]
    assert "tpu_custom_call" not in exe.as_text()
    assert exe.output_shardings is not None
    out = jax.eval_shape(decode_tick, shapes, i32(8), caches, i32(8),
                         jax.ShapeDtypeStruct((8, 2), jnp.uint32))
    assert out[4].shape == (8, 1 + 6 * 8 + 1)


def test_keye_prefill_tick_compiles_and_fits(one_chip, as_on_chip):
    """One width of the ladder (128: a row tile of the selection)."""
    cfg = _keye_cfg()
    shapes = _keye_weight_shapes(cfg)
    caches = jax.eval_shape(
        lambda: _keye_bundle(cfg, shapes)[0](KEYE["slots"]))

    def prefill_tick(weights, tokens, t0, caches, out_idx, take, slots):
        return _keye_bundle(cfg, weights)[4](tokens, t0, caches, out_idx,
                                             take, slots)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    exe = _compile(prefill_tick, (3,), one_chip, shapes, i32(8, 128),
                   i32(8), caches, i32(8), i32(8), i32(8))
    _assert_keye_fits(exe, caches)
