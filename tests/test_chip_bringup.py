"""Chip bring-up contracts that a CPU can check (ISSUE 21).

- ``chip_smoke.py`` refuses to run without a TPU (non-zero exit, the
  platform named, no result line); ``--rehearse`` passes end to end
  (slow: ~2.5 min of Pallas interpreter).
- Weights are ARGUMENTS of every compiled serving program: the lowered
  decode and ragged-prefill programs of a ``gpt2_tiny`` paged server
  and ``generate()``'s loops hold no constant of a weight's shape, and
  the dense and paged bundles share one stacked tree.
- No fallback that hides the device: ``on_tpu()`` raises when the
  backend cannot initialise, ``set_device`` refuses a platform JAX does
  not have, a kernel that refuses the platform fails the smoke's
  ``kernels`` phase, DataLoader workers refuse device arrays.
- The refusals the ROADMAP documents still refuse, and name it by
  letter; no string under ``paddle_tpu/`` cites a roadmap NUMBER.
- The compile cache honours ``JAX_COMPILATION_CACHE_DIR`` and otherwise
  sits at the fixed ``<checkout>/.jax_cache``.
"""
import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.jit.hoist import hoisted_jit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


# ------------------------------------------------------------ chip_smoke.py
def _run_smoke(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_without_a_chip_fails_and_names_the_platform():
    r = _run_smoke(timeout=120)
    assert r.returncode not in (0, 2, 3)   # 2/3 are the chip tool's own
    assert "'cpu'" in r.stderr and "not a TPU" in r.stderr
    # no result: nothing on stdout parses as the contract's JSON line
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert "phase" not in r.stdout          # nothing was run


def test_chip_smoke_alone_without_the_repo_fails(tmp_path):
    """The script in a directory that holds nothing else of the repo must
    fail too (here on the CPU it stops at the platform check already)."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(SMOKE).read())
    r = subprocess.run([sys.executable, str(alone), "--rehearse"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120, env=dict(os.environ, PYTHONPATH=""))
    assert r.returncode != 0
    assert "paddle_tpu" in r.stderr         # ModuleNotFoundError
    assert not [ln for ln in r.stdout.splitlines() if "{" in ln]


@pytest.mark.slow
def test_chip_smoke_rehearsal_passes():
    r = _run_smoke("--rehearse", timeout=900)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    lines = r.stdout.splitlines()
    assert lines and all(ln.startswith("REHEARSAL") for ln in lines)
    assert json.loads(lines[-1].split(" ", 1)[1])["ok"] is True
    for phase in ("kernels", "serve-split", "hybrid-serve", "ssm-serve",
                  "train",
                  "mesh4-serve-split", "mesh4-train"):
        assert re.search(rf"phase {phase}: ok", r.stdout), phase


# ------------------------------------------- weights out of the executables
def _tiny_gpt():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
    pt.seed(5)
    m = GPTForCausalLM(gpt2_tiny())
    m.eval()
    return m


def _weight_shapes(model):
    """Shapes only a weight has: >= 2-D stacked leaves of the tree (a
    bias row could collide with an activation shape)."""
    (tree,) = model._pt_stacked_weights.values()
    return {tuple(a.shape) for a in jax.tree_util.tree_leaves(tree)
            if a.ndim >= 2}


def _assert_no_weight_constants(text, shapes, what):
    # a captured array lowers to `stablehlo.constant dense<...> :
    # tensor<AxBxf32>`; arguments appear as %argN, never as constants
    for m in re.finditer(r"stablehlo\.constant[^\n]*tensor<([0-9x]+)x[a-z]",
                         text):
        dims = tuple(int(d) for d in m.group(1).split("x"))
        assert dims not in shapes, f"{what}: weight {dims} is a constant"
    # and the text is small: gpt2_tiny's weights alone are ~0.5 MB of f32,
    # twice that as text; a program that carries none stays well under
    assert len(text) < 400_000, f"{what}: {len(text)} chars of StableHLO"


def test_serving_programs_take_the_weights_as_arguments():
    model = _tiny_gpt()
    srv = ContinuousBatchingServer(model, cache_backend="paged", max_slots=2,
                                   max_cache_len=64, page_size=8)
    shapes = _weight_shapes(model)
    assert (2, 64, 192) in shapes          # stacked attn.qkv.weight
    S = 2

    decode = srv._build_decode_step()
    text = decode.lower(srv._tok, srv._caches, srv._t, srv._keys).as_text()
    _assert_no_weight_constants(text, shapes, "decode tick")

    toks = jnp.zeros((S, 8), jnp.int32)
    z = jnp.zeros((S,), jnp.int32)
    text = srv._ragged_fn.lower(toks, z, srv._caches, z, z, z).as_text()
    _assert_no_weight_constants(text, shapes, "ragged prefill")

    # the control: plain jax.jit over the same closure DOES bake them in
    baked = jax.jit(srv._step_fn).lower(
        jnp.zeros((S, 1, 64)), srv._caches, z).as_text()
    with pytest.raises(AssertionError, match="is a constant"):
        _assert_no_weight_constants(baked, shapes, "control")


def test_dense_and_paged_bundles_share_one_stacked_tree():
    model = _tiny_gpt()
    srv = ContinuousBatchingServer(model, cache_backend="paged", max_slots=2,
                                   max_cache_len=64, page_size=8)
    (tree,) = model._pt_stacked_weights.values()
    for bundle in (srv._bundle, srv._paged_bundle):
        embed_fn = bundle[1]
        captured = {id(c.cell_contents["table"]) for c in embed_fn.__closure__
                    if isinstance(c.cell_contents, dict)
                    and "table" in c.cell_contents}
        assert captured == {id(tree["table"])}
    # hoisted programs hand the SAME arrays to XLA, by reference
    prog = srv._prefill_jit._program(
        (jnp.zeros((1, 4, 64)), srv._init_caches(1), jnp.int32(0)))
    weight_ids = {id(a) for a in jax.tree_util.tree_leaves(tree)}
    assert weight_ids & {id(c) for c in prog.consts}
    model.reset_generate_cache()
    assert model._pt_stacked_weights is None


def test_generate_loops_take_the_weights_as_arguments():
    from paddle_tpu.inference import decode_loop
    model = _tiny_gpt()
    ids = np.arange(6, dtype=np.int32)[None]
    model.generate(ids, max_new_tokens=4, max_cache_len=32)
    model.generate(ids, max_new_tokens=4, max_cache_len=32, do_sample=True,
                   seed=3)
    shapes = _weight_shapes(model)
    bundle = model._decode_bundle(32)
    caches = bundle[0](1)
    loops = decode_loop._JIT_CACHE[bundle[2]]
    assert {k[1] for k in loops} == {"greedy_generate", "sample_generate"}
    for key, loop in loops.items():
        first = (jnp.zeros((1,), jnp.int32) if key[1] == "greedy_generate"
                 else jnp.zeros((1, 256)))
        extra = () if key[1] == "greedy_generate" else (jax.random.PRNGKey(0),)
        text = loop.lower(first, caches, 6, *extra).as_text()
        _assert_no_weight_constants(text, shapes, key[1])
    text = bundle[4].lower(jnp.zeros((1, 6, 64)), caches,
                           jnp.int32(0)).as_text()
    _assert_no_weight_constants(text, shapes, "prefill")


def test_hoisted_jit_matches_jit_and_donates():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 64))

    def step(x, caches, t):
        return x @ w, {"k": caches["k"].at[t].set(x[0])}

    x, caches = jnp.ones((2, 64)), {"k": jnp.zeros((8, 64))}
    want, _ = jax.jit(step)(x, caches, 3)
    h = hoisted_jit(step, donate_argnums=(1,))
    out, new = h(x, caches, 3)
    np.testing.assert_array_equal(out, want)
    assert caches["k"].is_deleted() and float(new["k"][3, 0]) == 1.0
    h(x, new, 4)
    assert len(h._programs) == 1            # same signature: no retrace
    # the AOT stages keep fn's own signature (what CostCatalog relies on)
    fresh = {"k": jnp.zeros((8, 64))}
    compiled = h.lower(x, fresh, 3).compile()
    assert compiled.cost_analysis()["flops"] > 0
    out2, _ = compiled(x, fresh, jnp.int32(3))
    np.testing.assert_array_equal(out2, want)
    assert len(h.lower(x, {"k": jnp.zeros((8, 64))}, 3).as_text()) \
        < len(jax.jit(step).lower(x, fresh, 3).as_text()) / 4


def test_hoisted_jit_returns_donated_mesh_buffers_in_place():
    """Captured weights ride as SHARDED arguments, and GSPMD lays an
    unpinned output out after them: a donated, replicated cache must come
    back replicated (its next program was compiled for that)."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    rep = NamedSharding(mesh, P())
    w = jax.device_put(jax.random.normal(jax.random.PRNGKey(1), (16, 64)),
                       NamedSharding(mesh, P(None, "mp")))

    def step(x, caches):
        y = x @ w
        return y.sum(), {"k": caches["k"] + y, "n": caches["n"] + 1}

    def fresh():
        # "n" is a plain single-device leaf (the server's block table is
        # one): pinning is per leaf, it must not switch the rest off
        return {"k": jax.device_put(jnp.zeros((4, 64)), rep),
                "n": jnp.zeros((), jnp.int32)}

    x = jnp.ones((4, 16))
    drift = jax.jit(lambda w_, x_, c: {"k": c["k"] + x_ @ w_})(
        w, x, fresh())["k"].sharding
    assert not drift.is_fully_replicated   # what the pin is there against
    _, new = hoisted_jit(step, donate_argnums=(1,))(x, fresh())
    assert new["k"].sharding.is_fully_replicated
    assert int(new["n"]) == 1


# --------------------------------------------- no fallback hides the device
def test_on_tpu_raises_when_the_backend_cannot_initialise():
    from paddle_tpu.ops import pallas as pallas_pack
    pallas_pack.on_tpu.cache_clear()
    try:
        with mock.patch.object(jax, "default_backend",
                               side_effect=RuntimeError("backend init")):
            with pytest.raises(RuntimeError, match="backend init"):
                pallas_pack.on_tpu()
        assert pallas_pack.on_tpu() is False     # CPU; failure not cached
    finally:
        pallas_pack.on_tpu.cache_clear()


def test_set_device_refuses_a_platform_jax_does_not_have():
    assert pt.set_device("cpu") == "cpu"
    assert pt.device.set_device("cpu:0") == "cpu:0"
    for bad in ("tpu", "gpu:0"):
        with pytest.raises(ValueError, match="no '(tpu|gpu)' device"):
            pt.set_device(bad)


def test_a_kernel_that_refuses_fails_the_phase(monkeypatch):
    """No kernel of the main path refuses a platform, so a refusal in
    the smoke's ``kernels`` phase is a failure, not a line of output."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    def refuses(case):
        raise NotImplementedError("not on this platform")

    monkeypatch.setattr(smoke, "kernel_paged", refuses)
    phases = smoke.Phases(SimpleNamespace(mark=lambda: (0, 0.0),
                                          since=lambda mark: (0, 0.0)))
    with phases.run("kernels"):
        smoke.phase_kernels(smoke.presets(True), True)
    assert phases.failed == ["kernels"]


# ------------------------------------------------- the documented refusals
def _refuse_int8_paged_pool():
    ContinuousBatchingServer(_tiny_gpt(), cache_backend="paged",
                             max_cache_len=64, page_size=8,
                             cache_dtype="int8")


def _refuse_optimistic_on_dense():
    ContinuousBatchingServer(_tiny_gpt(), max_cache_len=64,
                             admission="optimistic")


def _refuse_cross_datacenter():
    from paddle_tpu.inference.placement import normalize_placement
    normalize_placement("cross-datacenter")


def _slot_state_server(family="lfm2", **kw):
    """A paged server over a model with per-slot recurrent state beside
    the page pool: the ``lfm2`` family's short-convolution layers (one
    leaf), or the ``nemotron_h`` family's Mamba-2 layers (a tree of two
    leaves of two dtypes)."""
    if family not in _SLOT_STATE_MODELS:
        if family == "lfm2":
            from paddle_tpu.models.lfm2 import Lfm2MoeForCausalLM, lfm2_tiny
            model = Lfm2MoeForCausalLM(lfm2_tiny(), seed=0)
        else:
            from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                                      nemotron_h_tiny)
            model = NemotronHForCausalLM(nemotron_h_tiny(), seed=0)
        _SLOT_STATE_MODELS[family] = model
    return ContinuousBatchingServer(_SLOT_STATE_MODELS[family],
                                    cache_backend="paged", max_cache_len=64,
                                    page_size=8, max_slots=2, **kw)


_SLOT_STATE_MODELS = {}


def _refuse_prefix_hit_with_slot_state(family="lfm2"):
    _slot_state_server(family, auto_prefix_cache=True)


def _refuse_preemption_replay_with_slot_state(family="lfm2"):
    _slot_state_server(family, admission="optimistic")


def _refuse_host_tier_with_slot_state(family="lfm2"):
    _slot_state_server(family, host_tier=True)


def _refuse_dense_prefill_with_slot_state(family="lfm2"):
    _slot_state_server(family, prefill_mode="dense")


def _refuse_migration_with_slot_state(family="lfm2"):
    srv = _slot_state_server(family)
    rid = srv.submit(np.arange(9, dtype=np.int32), max_new_tokens=4)
    srv.step()
    srv.migrate_out(rid)


def _for_state_tree(refuse):
    """The same refusal over the model whose slot state is a TREE."""
    return lambda: refuse("nemotron_h")


_SLOT_STATE = ("per-slot recurrent state", "ROADMAP B5")


@pytest.mark.parametrize("refuse,names", [
    (_refuse_int8_paged_pool, ("ROADMAP A7",)),
    (_refuse_optimistic_on_dense, ("ROADMAP A7",)),
    (_refuse_cross_datacenter, ("ROADMAP", "Still dropped", "B7")),
    (_refuse_prefix_hit_with_slot_state,
     _SLOT_STATE + ("auto_prefix_cache=True",)),
    (_refuse_preemption_replay_with_slot_state,
     _SLOT_STATE + ("admission='optimistic'",)),
    (_refuse_host_tier_with_slot_state, _SLOT_STATE + ("host_tier",)),
    (_refuse_migration_with_slot_state, _SLOT_STATE + ("migration",)),
    (_for_state_tree(_refuse_prefix_hit_with_slot_state),
     _SLOT_STATE + ("auto_prefix_cache=True",)),
    (_for_state_tree(_refuse_preemption_replay_with_slot_state),
     _SLOT_STATE + ("admission='optimistic'",)),
    (_for_state_tree(_refuse_host_tier_with_slot_state),
     _SLOT_STATE + ("host_tier",)),
    (_for_state_tree(_refuse_migration_with_slot_state),
     _SLOT_STATE + ("migration",)),
    (_for_state_tree(_refuse_dense_prefill_with_slot_state),
     _SLOT_STATE + ("prefill_mode='dense'",)),
], ids=["int8-paged-pool", "optimistic-on-dense", "cross-datacenter",
        "slot-state-prefix-hit", "slot-state-preemption-replay",
        "slot-state-host-tier", "slot-state-migration",
        "state-tree-prefix-hit", "state-tree-preemption-replay",
        "state-tree-host-tier", "state-tree-migration",
        "state-tree-dense-prefill"])
def test_documented_refusals(refuse, names):
    """Each combination ROADMAP.md lists under "Refusals standing in the
    code" raises, and its message names the item that would lift it."""
    with pytest.raises(NotImplementedError) as e:
        refuse()
    for name in names:
        assert name in str(e.value)


def test_no_string_cites_a_dead_roadmap_number():
    """ROADMAP.md's items have letters (A7, B6, C1); "ROADMAP item 3"
    names a list that no longer exists."""
    hits = []
    for dirpath, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                path = os.path.join(dirpath, fn)
                text = re.sub(r"\s+", " ", open(path).read())
                hits += [(os.path.relpath(path, REPO), m.group(0))
                         for m in re.finditer(
                             r"ROADMAP[^.;:]{0,12}item[- ]\d", text)]
    assert hits == []


def test_flash_attention_partitions_itself_under_a_mesh():
    """GSPMD refuses Mosaic calls, so under ``with mesh:`` the Pallas
    flash path shard_maps itself: batch over the data axes that divide
    it, heads over mp (the launch itself rehearses in chip_smoke's
    mesh4-train phase)."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.parallel.mesh import AXES
    q = jnp.zeros((8, 4, 128, 16))
    assert fa._mesh_partition(q) is None                 # no mesh
    devs = np.array(jax.devices()[:8])
    with Mesh(devs.reshape(2, 1, 2, 1, 2), AXES) as mesh:
        assert fa._mesh_partition(q) == \
            (mesh, P(("dp", "sharding"), "mp", None, None))
        # batch 2: only dp divides it; 3 heads: mp does not
        assert fa._mesh_partition(jnp.zeros((2, 3, 128, 16)))[1] == \
            P(("dp",), None, None, None)
        inner = jax.shard_map(lambda x: x * (fa._mesh_partition(q) is None),
                              mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        assert float(inner(jnp.ones((2,)))[0]) == 1.0    # axes bound: None
    with Mesh(devs[:4], ("mp",)):                        # the serving mesh
        assert fa._mesh_partition(q)[1] == P(None, "mp", None, None)


def test_dataloader_workers_refuse_device_arrays():
    from paddle_tpu.io import DataLoader, TensorDataset
    ds = TensorDataset([pt.to_tensor(np.arange(8, dtype=np.float32))])
    assert len(list(DataLoader(ds, batch_size=4, num_workers=0))) == 2
    with pytest.raises(Exception, match="must stay off JAX"):
        list(DataLoader(ds, batch_size=4, num_workers=1))


def test_spawned_replica_host_is_not_steered_to_the_cpu():
    import inspect

    from paddle_tpu.inference import remote
    assert "JAX_PLATFORMS" not in inspect.getsource(remote._host_main)


# ------------------------------------------------------------ compile cache
def test_compile_cache_honours_the_variable_else_the_fixed_path():
    from paddle_tpu.device import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        with mock.patch.dict(os.environ,
                             {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}):
            jax.config.update("jax_compilation_cache_dir", "untouched")
            assert enable_compile_cache() == "/elsewhere"
            # set nowhere in code: JAX reads the variable itself
            assert jax.config.jax_compilation_cache_dir == "untouched"
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        with mock.patch.dict(os.environ, env, clear=True):
            fixed = os.path.join(REPO, ".jax_cache")
            assert enable_compile_cache() == fixed
            assert enable_compile_cache() == fixed      # never a temp/pid
            assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
