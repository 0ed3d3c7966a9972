"""The ``lfm2_moe`` decoder (LFM2-24B-A2B) at the ``rehearse`` sizes of
``perfbench/configs/lfm2-24b-a2b.json`` (6 layers: 2 dense conv layers, then
``attn conv attn conv`` with 8 experts top-2; hidden 64, 4 q / 2 kv heads of
16, vocabulary 256), seeded weights, float32, against the plain reference
``perfbench/reference_lfm2_moe.py``:

(a) the model's forward equals the reference; the reference without the
    selection bias, and with the convolution not carried over a block's
    edge, differ from it (the negative controls);
(b) ragged prefill in chunks, then decode, through the PAGED cache and its
    per-slot convolution state equals the reference's full forward ON
    LOGITS: a prompt split over three launches while another slot decodes
    between them, in a slot another sequence just left (stale state), with
    a row a slot and with rows for the launch's chunks only;
(c) the same through ``ContinuousBatchingServer``: every launch's logits
    against the reference, every emitted token the reference's argmax,
    slots reused, prompts spanning launches, the counters;
(d) the router (the bias chooses and does not weigh; the 1e-6; the
    scaling) against ten lines of numpy;
(e) the page pool has a layer an ATTENTION layer (2 for the cell's 10);
(f) what assumes that pages are the whole state refuses by name.

TOLERANCE of (a)-(c): 2e-4 absolute on logits whose spread (std) is about
0.3. Both sides are float32 under ``highest`` matmul precision
(conftest.py) on the same weights: what is left is the order of float32
sums, measured 1e-7 to 4e-6. A bfloat16 run of this same tiny model
differs from the reference by 6e-3 (``test_bfloat16_would_fail``),
thirty times the limit.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.models import lfm2
from paddle_tpu.models.generation import _layer_spec
from paddle_tpu.ops.routed_ffn import route_topk
from perfbench import reference_lfm2_moe as ref
from perfbench.families import lfm2_moe as family

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
WIDTH, PAGE = 64, 8


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "lfm2-24b-a2b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model(config):
    return family.build_model(config, seed=5, rehearse=True)


@pytest.fixture(scope="module")
def sizes(config):
    return family.sizes(config, rehearse=True)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def _want(model, sizes, ids, **kw):
    return ref.row_logits(model.raw_params(), ids, WIDTH, sizes, rows=16,
                          **kw)


# ------------------------------------------------------------ (a) forward
def test_forward_equals_reference(model, sizes):
    ids = _ids(40, seed=1)
    got = np.asarray(model(ids[None]).numpy())[0]
    want = _want(model, sizes, ids)
    assert want.std() > 0.1
    assert np.abs(got - want).max() < TOL
    # the negative controls are not the model: choosing without the bias,
    # and a convolution that forgets its inputs at a block's edge
    assert np.abs(_want(model, sizes, ids, bias=False) - want).max() > 50 * TOL
    assert np.abs(_want(model, sizes, ids, carry=False) - want).max() > 5 * TOL


def test_bfloat16_would_fail(model, sizes):
    """The stated tolerance is one a bfloat16 run of this tiny model fails."""
    ids = _ids(40, seed=1)
    cfg = dataclasses.replace(model.cfg, dtype="bfloat16")
    half = lfm2.Lfm2MoeForCausalLM(cfg, weights={
        n: a.astype(lfm2.param_dtype(cfg, n))
        for n, a in model.raw_params().items()})
    got = np.asarray(half(ids[None]).numpy())[0]
    assert np.abs(got - _want(model, sizes, ids)).max() > 20 * TOL


def test_rehearse_sizes_are_the_tiny_preset(model):
    assert dataclasses.asdict(model.cfg) == dataclasses.asdict(
        lfm2.lfm2_tiny())


def test_generate_dense_cache_matches_reference(model, sizes):
    """``generate()`` runs the dense cache, whose conv state rides the
    cache tree a layer a conv layer."""
    prompt = _ids(11, seed=3)
    out = np.asarray(model.generate(prompt[None], max_new_tokens=6).numpy())[0]
    lg = _want(model, sizes, out)
    assert [int(lg[t].argmax()) for t in range(10, 16)] == list(out[11:])
    with pytest.raises(NotImplementedError, match="ROADMAP B5"):
        model.generate(prompt[None], max_new_tokens=2, prefill_chunk=4)


# ------------------------------------------- (b) paged prefill and decode
def _bundle(model, slots):
    pages_per_slot = WIDTH // PAGE
    bundle = model._decode_bundle(WIDTH, cache_backend="paged",
                                  page_size=PAGE,
                                  num_pages=slots * pages_per_slot + 1)
    caches = bundle[0](slots)
    bt = (1 + np.arange(slots * pages_per_slot, dtype=np.int32)
          ).reshape(slots, pages_per_slot)
    return bundle, dict(caches, bt=jnp.asarray(bt))


def _launch(bundle, caches, slots, chunks, width, tight=False):
    """One ragged launch: ``chunks`` = {slot: (tokens, start)}, row j the
    j-th of them, with a row a slot or (``tight``) a row a chunk. Returns
    ({slot: logits of the chunk's last real row}, caches)."""
    prefill = bundle[5]
    rows = len(chunks) if tight else slots
    toks = np.zeros((rows, width), np.int32)
    t0 = np.full((rows,), WIDTH, np.int32)
    last = np.zeros((rows,), np.int32)
    take = np.zeros((rows,), np.int32)
    which = np.full((rows,), slots, np.int32)
    row_of = {}
    for j, (slot, (tokens, start)) in enumerate(chunks.items()):
        row = row_of[slot] = j
        toks[row, :len(tokens)] = tokens
        t0[row], last[row] = start, len(tokens) - 1
        take[row], which[row] = len(tokens), slot
    logits, caches = prefill(jnp.asarray(toks), jnp.asarray(t0), caches,
                             jnp.asarray(last), jnp.asarray(take),
                             jnp.asarray(which))
    return {s: np.asarray(logits[r]) for s, r in row_of.items()}, caches


def _decode(bundle, caches, slots, rows):
    """One decode step: ``rows`` = {slot: (token, position)}; every other
    slot rides parked on the sentinel. Returns ({slot: logits}, caches)."""
    _, embed_fn, _, head_fn, decode_step, _ = bundle
    tok = np.zeros((slots,), np.int32)
    t = np.full((slots,), WIDTH, np.int32)
    for slot, (token, pos) in rows.items():
        tok[slot], t[slot] = token, pos
    tt = jnp.asarray(t)
    hidden, caches = decode_step(embed_fn(jnp.asarray(tok), tt), caches, tt)
    lg = np.asarray(head_fn(hidden)[:, -1])
    return {s: lg[s] for s in rows}, caches


@pytest.mark.parametrize("tight", [False, True], ids=["a-row-a-slot",
                                                      "a-row-a-chunk"])
def test_paged_prefill_in_chunks_then_decode_equals_reference(model, sizes,
                                                              tight):
    slots = 3
    bundle, caches = _bundle(model, slots)
    assert caches["pool"]["k"].shape[0] == 2           # attention layers
    assert caches["state"].shape == (4, slots, 2, 64)  # conv layers
    other, stale, ids = _ids(34, seed=7), _ids(9, seed=8), _ids(26, seed=9)
    want, want_other = _want(model, sizes, ids), _want(model, sizes, other)
    worst = 0.0

    def see(got, table, pos):
        nonlocal worst
        worst = max(worst, np.abs(got - table[pos]).max())

    # slot 1 prefills ``other``'s first 24 tokens and will decode the rest;
    # slot 2 holds another sequence first, which leaves its state behind
    got, caches = _launch(bundle, caches, slots,
                          {1: (other[:24], 0), 2: (stale, 0)}, 32, tight)
    see(got[1], want_other, 23)
    assert float(jnp.abs(caches["state"][:, 2]).max()) > 0
    assert float(jnp.abs(caches["state"][:, 0]).max()) == 0   # never used
    # ``ids``: a prompt of 20 in three launches (8, 8, 4 real rows of 8),
    # in the slot the stale sequence left, slot 1 decoding between them
    pos = 24
    for start in (0, 8, 16):
        chunk = ids[start:min(start + 8, 20)]
        got, caches = _launch(bundle, caches, slots, {2: (chunk, start)}, 8,
                              tight)
        see(got[2], want, start + len(chunk) - 1)
        got, caches = _decode(bundle, caches, slots, {1: (other[pos], pos)})
        see(got[1], want_other, pos)
        pos += 1
    for t in range(20, 26):                             # both slots decode
        got, caches = _decode(bundle, caches, slots,
                              {2: (ids[t], t), 1: (other[pos], pos)})
        see(got[2], want, t)
        see(got[1], want_other, pos)
        pos += 1
    assert worst < TOL, worst
    assert float(jnp.abs(caches["state"][:, 0]).max()) == 0   # still idle


def test_a_parked_slot_keeps_its_state(model):
    """A decoding slot rides a prefill launch, and a prefilling slot a
    decode tick, parked on the sentinel: neither program touches its
    state."""
    slots = 2
    bundle, caches = _bundle(model, slots)
    _, caches = _launch(bundle, caches, slots, {0: (_ids(5), 0)}, 8)
    held = np.asarray(caches["state"][:, 0])
    _, caches = _launch(bundle, caches, slots, {1: (_ids(7, 1), 0)}, 8)
    _, caches = _decode(bundle, caches, slots, {1: (3, 7)})
    assert np.array_equal(np.asarray(caches["state"][:, 0]), held)
    assert np.abs(held).max() > 0


# ------------------------------------------------- (c) through the server
def _server(model, **kw):
    kw.setdefault("max_slots", 2)
    return ContinuousBatchingServer(
        model, cache_backend="paged", page_size=PAGE, max_cache_len=WIDTH,
        prefill_tokens_per_tick=8, **kw)


@pytest.mark.parametrize("row_limit", [4096, 8], ids=["a-row-a-slot",
                                                      "one-row"])
def test_server_prefill_and_decode_equal_reference(model, sizes, row_limit):
    """Four prompts through two slots, 8 prefill tokens a tick: prompts of
    20 and 13 span three and two launches with the other slot decoding
    between them, and the third and fourth request land in slots the first
    two left. Every launch's logits are held to the reference and every
    emitted token is its argmax, with rows for both slots and, at a row
    limit of 8, with one row a launch of width 8."""
    srv = _server(model, telemetry=True)
    # the server's own limit is the power of two over its budget (8): hold
    # a row a slot too
    srv._launch_rows = row_limit
    seen = []
    launch = srv._ragged_fn

    def spy(tokens, t0, caches, out_idx, take, slots):
        logits, caches = launch(tokens, t0, caches, out_idx, take, slots)
        seen.append((np.asarray(tokens), np.asarray(t0), np.asarray(out_idx),
                     np.asarray(logits), np.asarray(take), np.asarray(slots)))
        return logits, caches

    srv._ragged_fn = spy
    prompts = [_ids(n, seed=20 + n) for n in (20, 13, 6, 17)]
    rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
    outs = srv.run()
    tables = [_want(model, sizes, np.concatenate([p, outs[r]]))
              for p, r in zip(prompts, rids)]
    for p, r, table in zip(prompts, rids, tables):
        assert [int(table[len(p) - 1 + j].argmax()) for j in range(5)] \
            == list(outs[r])
    checked = 0
    for tokens, t0, out_idx, logits, takes, slots in seen:
        # as many rows as the limit allows at this width, a slot at most
        assert tokens.shape[0] == min(2, max(1, row_limit // tokens.shape[1]))
        assert np.array_equal(slots < 2, t0 < WIDTH)   # the rest: no slot's
        for row in np.flatnonzero(t0 < WIDTH):
            take = int(takes[row])
            start, chunk = int(t0[row]), tokens[row, :take]
            for p, table in zip(prompts, tables):
                if start + take == len(p) and np.array_equal(
                        p[start:], chunk):        # the chunk ends a prompt
                    assert np.abs(logits[row] - table[len(p) - 1]
                                  ).max() < TOL
                    checked += 1
    assert checked == 4
    assert any(tokens.shape[0] == 1 for tokens, *_ in seen) \
        == (row_limit == 8)
    s = srv.stats
    # 20 = 8 + 8 + 4, 13 = 8 + 5, 6, 17 = 8 + 8 + 1 under a budget shared
    # FIFO: more slot-chunks than prompts, some continuing a prompt
    assert s["prefill_chunks"] >= 9 and s["prefill_chunks_carried"] >= 5
    assert s["prefill_chunks_carried"] < s["prefill_chunks"]
    # the route read-back covers the 4 EXPERT layers: every live decode
    # row chose 2 experts in each
    assert srv._caches["route"].shape == (4, 2, 2)
    assert 4 * 2 <= s["moe_experts_touched"] <= s["decode_ticks"] * 4 * 8
    # the decode kernel's grid counts the 2 attention layers
    assert s["decode_grid_steps"] == s["decode_live_pages"] > 0
    assert s["decode_grid_steps"] % 2 == 0
    snap = srv.telemetry.registry.snapshot()

    def total(name, kind):
        return sum(v["value"] if isinstance(v, dict) else v
                   for k, v in snap[name]["samples"].items() if kind in k)

    assert total("serving_prefill_chunks_total", "launched") \
        == s["prefill_chunks"]
    assert total("serving_prefill_chunks_total", "carried") \
        == s["prefill_chunks_carried"]


def test_dense_backend_server_carries_the_state_with_the_slot(model, sizes):
    srv = ContinuousBatchingServer(model, max_slots=2, max_cache_len=WIDTH)
    prompts = [_ids(n, seed=60 + n) for n in (9, 14, 5)]
    rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
    outs = srv.run()
    for p, r in zip(prompts, rids):
        table = _want(model, sizes, np.concatenate([p, outs[r]]))
        assert [int(table[len(p) - 1 + j].argmax()) for j in range(4)] \
            == list(outs[r])


# ------------------------------------------------------------- (d) router
def test_router_against_numpy():
    """The bias chooses and does not weigh; the 1e-6; the scaling."""
    rng = np.random.default_rng(0)
    h = rng.normal(size=(32, 16)).astype(np.float32)
    w = rng.normal(size=(16, 8)).astype(np.float32)
    bias = rng.normal(scale=0.3, size=8).astype(np.float32)
    k, scaling = 3, 2.5
    idx, gate = route_topk(jnp.asarray(h), jnp.asarray(w), k,
                           score="sigmoid", bias=jnp.asarray(bias),
                           scale=scaling)
    s = 1.0 / (1.0 + np.exp(-(h.astype(np.float64) @ w)))
    sel = np.argsort(-(s + bias), axis=1, kind="stable")[:, :k]
    g = np.take_along_axis(s, sel, 1)
    g = g / (g.sum(1, keepdims=True) + 1e-6) * scaling
    assert np.array_equal(np.asarray(idx), sel)
    assert np.abs(np.asarray(gate) - g).max() < 1e-6
    # the bias changed who was chosen somewhere, and nobody's weight
    plain = np.argsort(-s, axis=1, kind="stable")[:, :k]
    assert (np.sort(plain, 1) != np.sort(sel, 1)).any()
    # the 1e-6 is in the sum: the gates add up to a hair under the scale
    assert (np.asarray(gate).sum(1) < scaling).all()
    # no bias, no scaling, no normalisation: the scores themselves
    idx0, gate0 = route_topk(jnp.asarray(h), jnp.asarray(w), k,
                             normalize=False, score="sigmoid")
    assert np.array_equal(np.asarray(idx0), plain)
    assert np.abs(np.asarray(gate0) - np.take_along_axis(s, plain, 1)
                  ).max() < 1e-6
    with pytest.raises(ValueError, match="router score"):
        route_topk(jnp.asarray(h), jnp.asarray(w), k, score="tanh")


# ---------------------------------------------------- (e) the layer spec
def test_pool_has_a_layer_an_attention_layer(config):
    """The cell's 10 layers at tiny widths: 2 attention layers, so the pool
    has 2; 8 conv layers of state; 8 expert layers of route read-back."""
    cfg = lfm2.lfm2_tiny(num_hidden_layers=10,
                         layer_types=config["layer_types"])
    assert cfg.layer_types == lfm2._PUBLISHED_TYPES[:10] == (
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv")
    spec = _layer_spec(cfg)
    assert [sorted(at) for at in spec[:3]] == [
        ["conv", "dense", "layer"], ["conv", "dense", "layer"],
        ["attn", "layer", "moe"]]
    assert spec[6] == {"layer": 6, "attn": 1, "moe": 4}
    assert spec[9] == {"layer": 9, "conv": 7, "moe": 7}
    model = lfm2.Lfm2MoeForCausalLM(cfg, seed=0)
    srv = ContinuousBatchingServer(model, max_slots=3, max_cache_len=32,
                                   cache_backend="paged", page_size=8)
    assert srv._caches["pool"]["k"].shape == (2, 3 * 4 + 1, 8, 2 * 16)
    assert srv._caches["state"].shape == (8, 3, 2, 64)
    assert srv._caches["route"].shape == (8, 3, 2)
    assert srv._n_layers == 2 and srv._row_nbytes() == 2 * 2 * 32 * 4
    # a model of identical layers has no spec
    from paddle_tpu.models.keye_vl import keye_vl2_tiny
    assert _layer_spec(keye_vl2_tiny()) is None


def test_published_config_defaults():
    cfg = lfm2.Lfm2MoeConfig()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim) == (40, 2048,
                                                                    64)
    assert lfm2.layer_counts(cfg) == (10, 30, 2, 38)
    assert cfg.layer_types[:6] == ("conv", "conv", "full_attention", "conv",
                                   "conv", "conv")
    shapes = lfm2.param_shapes(cfg)
    assert shapes["model.moe_layers.experts_w1"] == (38, 64, 2048, 1536)
    assert shapes["model.conv_layers.in_proj"] == (30, 2048, 6144)
    with pytest.raises(ValueError, match="layer_types names every layer"):
        lfm2.Lfm2MoeConfig(num_hidden_layers=10)


# ------------------------------------------------------- (f) the refusals
@pytest.mark.parametrize("kw", [
    dict(auto_prefix_cache=True), dict(admission="optimistic"),
    dict(host_tier=True), dict(host_tier_bytes=1 << 20),
    dict(prefill_mode="dense"),
], ids=["prefix-cache", "preemption-replay", "host-tier", "host-tier-bytes",
        "dense-prefill"])
def test_what_assumes_pages_are_the_whole_state_refuses(model, kw):
    with pytest.raises(NotImplementedError, match="ROADMAP B5") as e:
        _server(model, **kw)
    assert "per-slot recurrent state" in str(e.value)


def test_prefix_registration_and_migration_refuse(model):
    srv = _server(model)
    assert srv._auto_prefix is False          # None reads as off here
    with pytest.raises(NotImplementedError, match="register_prefix"):
        srv.register_prefix(_ids(16))
    rid = srv.submit(_ids(9), max_new_tokens=4)
    srv.step()
    with pytest.raises(NotImplementedError, match="migration"):
        srv.migrate_out(rid)
    with pytest.raises(NotImplementedError, match="migration"):
        srv.migrate_in({}, [])
    with pytest.raises(NotImplementedError, match="ROADMAP B5"):
        srv.migrate_in_begin({})
    assert len(srv.run()[rid]) == 4           # and it serves on
