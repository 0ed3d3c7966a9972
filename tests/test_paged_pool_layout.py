"""The page pool's storage layout and the seams around it (ISSUE 26).

The pool is stored lane-dense, ``[layers, pages, page_size, kv_heads *
head_dim]`` (``generation.paged_pool_shape``, its one owner), carried
through the layer loop, and read by the kernels through a layer index.
Pinned here, on the CPU:

- the shape and its per-head view round-trip, and the cache tree every
  model family builds has that shape;
- both kernels (interpret mode) and both XLA references, handed a
  many-layer pool and a layer index, equal the one-layer call on that
  layer bit for bit — on one device and shard_mapped over the 4-device
  CPU mesh;
- each eager page mover (fill, seed, spill, restore/migration scatter)
  moves the bytes it moved before: payloads stay ``[L, pg, kvh, hd]``
  rows of the dense cache, on one device and on a sharded pool;
- the pool buffer handed to ``decode_tick`` and to ``prefill_tick`` is
  the one returned (donation aliases: nothing pool-sized is rebuilt).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.models import generation as gen
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import ragged_prefill as rp

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 forced host devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("mp",))


# ------------------------------------------------------------ the shape


class TestPoolShape:
    def test_shape_is_lane_dense(self):
        assert gen.paged_pool_shape(24, 2049, 16, 16, 64) == \
            (24, 2049, 16, 1024)
        assert gen.paged_pool_shape(48, 513, 16, 25, 64) == \
            (48, 513, 16, 1600)

    def test_views_round_trip(self):
        rows = np.arange(3 * 5 * 4 * 8, dtype=np.float32).reshape(3, 5, 4, 8)
        flat = gen.pool_lanes(rows)
        assert flat.shape == (3, 5, 32)
        # head g is lanes [g * hd, (g + 1) * hd)
        np.testing.assert_array_equal(flat[..., 8:16], rows[..., 1, :])
        np.testing.assert_array_equal(gen.pool_heads(flat, 4), rows)
        np.testing.assert_array_equal(
            gen.pool_lanes(gen.pool_heads(jnp.asarray(flat), 4)), flat)

    def test_kv_heads_come_from_the_config(self):
        from paddle_tpu.models.gpt import gpt2_tiny
        from paddle_tpu.models.llama import llama_tiny
        assert gen.paged_kv_heads(gpt2_tiny()) == gpt2_tiny().num_heads
        assert gen.paged_kv_heads(llama_tiny()) == llama_tiny().num_kv_heads

    @pytest.mark.parametrize("family", ["gpt", "llama", "mixtral"])
    def test_every_family_builds_the_owners_shape(self, family):
        model = _tiny(family)
        cfg = model.cfg
        kvh = gen.paged_kv_heads(cfg)
        hd = cfg.hidden_size // cfg.num_heads
        init = model._decode_bundle(32, cache_backend="paged",
                                    page_size=8, num_pages=9)[0]
        tree = jax.eval_shape(lambda: init(2))
        want = gen.paged_pool_shape(cfg.num_layers, 9, 8, kvh, hd)
        assert tree["pool"]["k"].shape == tree["pool"]["v"].shape == want
        assert tree["bt"].shape == (2, 4)


def _tiny(family):
    pt.seed(5)
    if family == "gpt":
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        model = GPTForCausalLM(gpt2_tiny())
    elif family == "llama":
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        model = LlamaForCausalLM(llama_tiny())
    else:
        from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                               mixtral_tiny)
        model = MixtralForCausalLM(mixtral_tiny())
    model.eval()
    return model


# ---------------------------------------------- kernels: the layer index


L, S, NH, KVH, HD, NP, PG, MAXP = 3, 4, 8, 4, 32, 12, 8, 4


def _layered_pool(seed):
    """A 3-layer lane-dense pool, its per-layer per-head views, and
    block tables of distinct live pages."""
    rng = np.random.RandomState(seed)
    r = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32) * .5)
    k, v = r(L, NP, PG, KVH * HD), r(L, NP, PG, KVH * HD)
    bt = jnp.asarray(np.stack([
        rng.choice(np.arange(1, NP), MAXP, replace=False)
        for _ in range(S)]).astype(np.int32))
    return r, k, v, bt


def _shard(a, mesh):
    return jax.device_put(a, NamedSharding(mesh, P(None, None, None, "mp")))


def _decode_calls(mesh):
    r, k, v, bt = _layered_pool(41)
    q = r(S, NH, HD)
    lengths = jnp.asarray(np.array([PG, 13, 1, MAXP * PG], np.int32))
    if mesh is not None:
        k, v = _shard(k, mesh), _shard(v, mesh)

    def layered(l, **kw):
        return pa.paged_attention(q, k, v, bt, lengths, layer=l,
                                  mesh=mesh, **kw)

    def alone(l, **kw):
        return pa.paged_attention(q, gen.pool_heads(k[l], KVH),
                                  gen.pool_heads(v[l], KVH), bt, lengths,
                                  mesh=mesh, **kw)

    return layered, alone


def _prefill_calls(mesh):
    r, k, v, bt = _layered_pool(42)
    C = 2 * rp.QUERY_TILE            # two query tiles a launch
    q = r(S, C, NH, HD)
    t0 = jnp.asarray(np.array([0, 5, 16, 3], np.int32))
    take = jnp.asarray(np.array([16, 5, 0, 16], np.int32))   # an idle slot
    if mesh is not None:
        k, v = _shard(k, mesh), _shard(v, mesh)

    def layered(l, **kw):
        return rp.ragged_prefill_attention(q, k, v, bt, t0, take, layer=l,
                                           mesh=mesh, **kw)

    def alone(l, **kw):
        return rp.ragged_prefill_attention(
            q, gen.pool_heads(k[l], KVH), gen.pool_heads(v[l], KVH), bt,
            t0, take, mesh=mesh, **kw)

    return layered, alone


@pytest.mark.parametrize("calls", [_decode_calls, _prefill_calls],
                         ids=["decode", "prefill"])
@pytest.mark.parametrize("layer", range(L))
class TestLayerIndex:
    def test_kernel_reads_its_layer(self, calls, layer):
        layered, alone = calls(None)
        np.testing.assert_array_equal(
            np.asarray(layered(layer, interpret=True)),
            np.asarray(alone(layer, interpret=True)))

    def test_traced_layer_index(self, calls, layer):
        """The loop's index is a tracer: the kernel takes it through
        the scalar prefetch, the reference through its gather."""
        layered, alone = calls(None)
        for kw in (dict(interpret=True), {}):
            got = jax.jit(lambda l: layered(l, **kw))(jnp.int32(layer))
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(alone(layer, **kw)))

    @needs_mesh
    @pytest.mark.mesh
    def test_sharded_kernel_reads_its_layer(self, calls, layer):
        layered, alone = calls(_mesh())
        np.testing.assert_array_equal(
            np.asarray(layered(layer, interpret=True)),
            np.asarray(alone(layer, interpret=True)))


def test_prefill_tiles_are_one_looped_launch():
    """A chunk wider than the query tile is ONE kernel launch whose
    grid steps through the tiles (traced and lowered once a program),
    whatever the width; a width that is no multiple of the tile pads its
    last tile and reads the same rows."""
    r, k, v, bt = _layered_pool(43)
    C = 4 * rp.QUERY_TILE
    q = r(S, C, NH, HD)
    t0 = jnp.asarray(np.array([0, 5, 16, 3], np.int32))
    take = jnp.asarray(np.array([C, 11, 0, 20], np.int32))

    def call(q, take):
        return rp.ragged_prefill_attention(q, k, v, bt, t0, take, layer=1,
                                           interpret=True)

    jaxpr = str(jax.make_jaxpr(call)(q, take))
    assert jaxpr.count("name=ragged_prefill_attention") == 1
    whole = np.asarray(call(q, take))
    odd = C - 5                            # 27 rows: a padded last tile
    np.testing.assert_array_equal(
        np.asarray(call(q[:, :odd], jnp.minimum(take, odd))),
        whole[:, :odd])


# ------------------------------------------------------- the page movers


def _gpt_server(mesh=None, **kw):
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
    pt.seed(9)
    model = GPTForCausalLM(gpt2_tiny())      # 2 layers, 4 heads x 16
    model.eval()
    kw.setdefault("prefill_mode", "dense")   # the movers' home path
    return ContinuousBatchingServer(
        model, cache_backend="paged", max_slots=2, max_cache_len=32,
        page_size=8, num_pages=9, mesh=mesh, **kw)


def _dense_rows(srv, seed):
    """A dense batch-1 cache tree of seeded rows, in the pool's dtype."""
    base = srv._init_caches(1)
    rng = np.random.RandomState(seed)
    return {n: jnp.asarray(rng.randn(*base[n].shape), base[n].dtype)
            for n in ("k", "v")}


@pytest.mark.parametrize("mp", [None, pytest.param(4, marks=[
    needs_mesh, pytest.mark.mesh])], ids=["one-device", "mp4"])
class TestPageMovers:
    def test_fill_spill_write_seed_move_the_same_rows(self, mp):
        srv = _gpt_server(mesh=None if mp is None else _mesh(mp))
        assert srv._pool_shards == (mp or 1)
        rows = _dense_rows(srv, 3)
        want = {n: np.asarray(rows[n]) for n in rows}   # [L, 1, T, h, hd]
        # fill: dense rows [8, 24) land in pages 5 and 2, position order
        srv._fill_pages(rows, [5, 2], 8)
        pay5, pay2 = srv._spill_payload(5), srv._spill_payload(2)
        for j, n in enumerate(("k", "v")):
            assert pay5[j].shape == (2, 8, 4, 16)       # [L, pg, kvh, hd]
            np.testing.assert_array_equal(pay5[j], want[n][:, 0, 8:16])
            np.testing.assert_array_equal(pay2[j], want[n][:, 0, 16:24])
        # write (host-tier restore, migration, handoff): the payloads
        # land in fresh pages and read back identical
        srv._write_pages([7, 1], [pay5, pay2])
        for got, sent in ((srv._spill_payload(7), pay5),
                          (srv._spill_payload(1), pay2)):
            for j in range(2):
                np.testing.assert_array_equal(got[j], sent[j])
        # seed: the pages gathered back into a dense batch-1 cache
        dense = srv._seed_from_pages([7, 1])
        for n in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(dense[n])[:, 0, :16], want[n][:, 0, 8:24])
        # untouched pages stayed zero
        assert not np.asarray(srv._caches["pool"]["k"])[:, 3].any()

    def test_pool_is_placed_by_whole_heads(self, mp):
        srv = _gpt_server(mesh=None if mp is None else _mesh(mp))
        pool = srv._caches["pool"]["k"]
        assert pool.shape == gen.paged_pool_shape(2, 9, 8, 4, 16)
        shard = pool.addressable_shards[0].data
        assert shard.shape == (2, 9, 8, 64 // (mp or 1))
        assert srv._caches["bt"].sharding.is_fully_replicated


# ------------------------------------------------------------- donation


class TestPoolStaysInPlace:
    def _ptrs(self, caches):
        return [caches["pool"][n].unsafe_buffer_pointer()
                for n in ("k", "v")]

    def test_decode_tick_returns_the_buffer_it_was_handed(self):
        srv = _gpt_server(prefill_mode="ragged")
        before = self._ptrs(srv._caches)
        tok, caches, t, keys, toks = srv._build_decode_step()(
            srv._tok, srv._caches, srv._t, srv._keys)
        jax.block_until_ready(toks)
        assert self._ptrs(caches) == before

    def test_prefill_tick_returns_the_buffer_it_was_handed(self):
        srv = _gpt_server(prefill_mode="ragged")
        before = self._ptrs(srv._caches)
        z = jnp.zeros((2,), jnp.int32)
        logits, caches = srv._ragged_fn(jnp.zeros((2, 8), jnp.int32), z,
                                        srv._caches, z, z, jnp.arange(2))
        jax.block_until_ready(logits)
        assert self._ptrs(caches) == before
