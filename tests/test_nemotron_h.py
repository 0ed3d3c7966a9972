"""The ``nemotron_h`` decoder (Nemotron-3-Super-120B-A12B) at the ``rehearse``
sizes of ``perfbench/configs/nemotron3-super-120b-a12b.json`` (``MEM*E``: two
Mamba-2 layers, two latent expert layers holding 8 of 16 experts top-3, one
attention layer; hidden 64, vocabulary 256), seeded weights, float32, against
the plain reference ``perfbench/reference_nemotron_h.py``:

(a) the model's forward equals the reference; the reference without the
    selection bias, with the recurrent state dropped at a block's edge, and
    with the state kept in bfloat16, differ from it (the negative controls);
(b) ragged prefill in chunks, then decode, through the PAGED cache and its
    per-slot state TREE equals the reference's full forward ON LOGITS: a
    prompt split over three launches while another slot decodes between them,
    a chunk whose ``take`` is under its width, in a slot another sequence
    just left (stale ``S``), with a row a slot and with rows for the launch's
    chunks only;
(c) the same through ``ContinuousBatchingServer``: every launch's logits
    against the reference, every emitted token the reference's argmax, slots
    reused, prompts spanning launches, the counters of the expert share;
(d) the chunked scan against the plain recurrence at widths that are and are
    not a multiple of the chunk, and one step against both;
(e) the router (top-k of a biased sigmoid, the bias not weighing, the 1e-20,
    the scaling) against ten lines of numpy;
(f) THE SHARE TEST: the routed parts that the four shares of 4 x ``count``
    experts give, with the shared expert counted once, add up to the uncut
    reference's layer output;
(g) the pool has 1 layer and the state tree two leaves of two dtypes for the
    cell's 11-layer pattern;
(h) what assumes that pages are the whole state refuses by name.

TOLERANCE of (a)-(c): 2e-4 absolute on logits whose spread (std) is about
0.16. Both sides are float32 under ``highest`` matmul precision (conftest.py)
on the same weights: what is left is the order of float32 sums (the chunked
scan sums a chunk's rows in another order than the plain recurrence),
measured 2e-7 to 2e-6. A bfloat16 run of this same tiny model differs from the
reference by far more (``test_bfloat16_would_fail``), and so does the state
dropped at a chunk's edge.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.models.generation import _layer_spec
from paddle_tpu.ops.routed_ffn import route_topk, routed_ffn
from paddle_tpu.ops.ssm_scan import ssm_scan, ssm_step
from perfbench import reference_nemotron_h as ref
from perfbench.families import nemotron_h as family
# one ragged launch and one decode step over a bundle's cache tree, at the
# same width and page as here
from test_lfm2 import PAGE, WIDTH, _bundle, _decode, _launch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "nemotron3-super-120b-a12b.json")) as f:
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
    # the negative controls are not the model
    for control in (dict(bias=False), dict(carry=False),
                    dict(carry="window"), dict(state_dtype=jnp.bfloat16)):
        assert np.abs(_want(model, sizes, ids, **control) - want).max() \
            > 10 * TOL, control


def test_bfloat16_would_fail(model, sizes):
    """The stated tolerance is one a bfloat16 run of this tiny model fails."""
    ids = _ids(40, seed=1)
    cfg = dataclasses.replace(model.cfg, dtype="bfloat16")
    half = nh.NemotronHForCausalLM(cfg, weights={
        n: a.astype(nh.param_dtype(cfg, n))
        for n, a in model.raw_params().items()})
    got = np.asarray(half(ids[None]).numpy())[0]
    assert np.abs(got - _want(model, sizes, ids)).max() > 20 * TOL


def test_rehearse_sizes_are_the_tiny_preset(model):
    assert dataclasses.asdict(model.cfg) == dataclasses.asdict(
        nh.nemotron_h_tiny())


def test_generate_dense_cache_matches_reference(model, sizes):
    """``generate()`` runs the dense cache, whose state tree rides the
    cache tree; a decode row is one step of the recurrence the prefill ran
    in chunks."""
    prompt = _ids(11, seed=3)
    out = np.asarray(model.generate(prompt[None], max_new_tokens=6).numpy())[0]
    lg = _want(model, sizes, out)
    assert [int(lg[t].argmax()) for t in range(10, 16)] == list(out[11:])
    with pytest.raises(NotImplementedError, match="ROADMAP B5"):
        model.generate(prompt[None], max_new_tokens=2, prefill_chunk=4)


# ------------------------------------------- (b) paged prefill and decode
def _state_max(caches, slot):
    return max(float(jnp.abs(leaf[:, slot]).max())
               for leaf in caches["state"].values())


@pytest.mark.parametrize("tight", [False, True], ids=["a-row-a-slot",
                                                      "a-row-a-chunk"])
def test_paged_prefill_in_chunks_then_decode_equals_reference(model, sizes,
                                                              tight):
    slots = 3
    bundle, caches = _bundle(model, slots)
    assert caches["pool"]["k"].shape[0] == 1           # attention layers
    assert {n: (a.shape, a.dtype) for n, a in caches["state"].items()} == {
        "conv": ((2, slots, 3, 128 + 2 * 2 * 16), jnp.float32),
        "ssm": ((2, slots, 8, 16, 16), jnp.float32)}
    other, stale, ids = _ids(34, seed=7), _ids(9, seed=8), _ids(26, seed=9)
    want, want_other = _want(model, sizes, ids), _want(model, sizes, other)
    worst = 0.0

    def see(got, table, pos):
        nonlocal worst
        worst = max(worst, np.abs(got - table[pos]).max())

    # slot 1 prefills ``other``'s first 24 tokens (three chunks of the scan,
    # chunk_size 8) and will decode the rest; slot 2 holds another sequence
    # first, which leaves its S and its window behind
    got, caches = _launch(bundle, caches, slots,
                          {1: (other[:24], 0), 2: (stale, 0)}, 32, tight)
    see(got[1], want_other, 23)
    assert _state_max(caches, 2) > 0
    assert _state_max(caches, 0) == 0                   # never used
    # ``ids``: a prompt of 20 in three launches (8, 8, 4 real rows of 8: the
    # last chunk's take is under its width), in the slot the stale sequence
    # left, slot 1 decoding between them
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
    assert _state_max(caches, 0) == 0                   # still idle


def test_a_parked_slot_keeps_its_state(model):
    """A decoding slot rides a prefill launch, and a prefilling slot a
    decode tick, parked on the sentinel: neither program touches either
    leaf of its state."""
    slots = 2
    bundle, caches = _bundle(model, slots)
    _, caches = _launch(bundle, caches, slots, {0: (_ids(5), 0)}, 8)
    held = {n: np.asarray(a[:, 0]) for n, a in caches["state"].items()}
    _, caches = _launch(bundle, caches, slots, {1: (_ids(7, 1), 0)}, 8)
    _, caches = _decode(bundle, caches, slots, {1: (3, 7)})
    for n, a in held.items():
        assert np.array_equal(np.asarray(caches["state"][n][:, 0]), a)
        assert np.abs(a).max() > 0


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
    two left (stale S). Every launch's logits are held to the reference and
    every emitted token is its argmax."""
    srv = _server(model, telemetry=True)
    # the server's own limit is the power of two over its budget (8): hold
    # a row a slot too
    srv._launch_rows = row_limit
    seen = []
    launch = srv._ragged_fn

    def spy(tokens, t0, caches, out_idx, take, slots):
        logits, caches = launch(tokens, t0, caches, out_idx, take, slots)
        seen.append((np.asarray(tokens), np.asarray(t0), np.asarray(logits),
                     np.asarray(take)))
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
    for tokens, t0, logits, takes in seen:
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
    s = srv.stats
    assert s["prefill_chunks"] >= 9 and s["prefill_chunks_carried"] >= 5
    # the route read-back covers the 2 EXPERT layers: every live decode row
    # chose 3 of the router's 16 experts in each, of which 8 are held
    assert srv._caches["route"].shape == (2, 2, 3)
    assert s["moe_pairs_routed"] == s["decode_live_rows"] * 2 * 3
    assert 0 < s["moe_pairs_held"] < s["moe_pairs_routed"]
    assert 0 < s["moe_experts_touched"] <= min(
        s["moe_pairs_held"], s["decode_ticks"] * 2 * 8)
    # the decode kernel's grid counts the 1 attention layer
    assert s["decode_grid_steps"] == s["decode_live_pages"] > 0
    snap = srv.telemetry.registry.snapshot()

    def total(name, kind):
        return sum(v["value"] if isinstance(v, dict) else v
                   for k, v in snap[name]["samples"].items() if kind in k)

    assert total("serving_moe_pairs_total", "routed") == s["moe_pairs_routed"]
    assert total("serving_moe_pairs_total", "held") == s["moe_pairs_held"]


# ------------------------------------------------ (d) the two scan forms
def _plain_recurrence(x, d, a, bm, cm, state):
    """The recurrence a row at a time, in float64 numpy."""
    x, d, a, bm, cm = (np.asarray(v, np.float64) for v in (x, d, a, bm, cm))
    s = np.asarray(state, np.float64).copy()
    b, t, heads, p = x.shape
    rep = heads // bm.shape[2]
    ys = np.zeros((b, t, heads, p))
    for i in range(t):
        bh, ch = np.repeat(bm[:, i], rep, 1), np.repeat(cm[:, i], rep, 1)
        s = np.exp(d[:, i] * a)[..., None, None] * s \
            + (d[:, i][..., None] * x[:, i])[..., None] * bh[:, :, None, :]
        ys[:, i] = (s * ch[:, :, None, :]).sum(-1)
    return ys, s


@pytest.mark.parametrize("rows,chunk", [(32, 8), (27, 8), (5, 8), (16, 16),
                                        (2, 128)])
def test_chunked_scan_equals_the_plain_recurrence(rows, chunk):
    rng = np.random.default_rng(rows)
    b, heads, p, groups, n = 2, 4, 8, 2, 16
    x = rng.normal(size=(b, rows, heads, p)).astype(np.float32)
    d = rng.uniform(0.001, 0.5, (b, rows, heads)).astype(np.float32)
    d[1, rows - 2:] = 0.0            # padding rows: no step at all
    a = -rng.uniform(1, 16, heads).astype(np.float32)
    bm = rng.normal(size=(b, rows, groups, n)).astype(np.float32)
    cm = rng.normal(size=(b, rows, groups, n)).astype(np.float32)
    s0 = rng.normal(size=(b, heads, p, n)).astype(np.float32)
    want_y, want_s = _plain_recurrence(x, d, a, bm, cm, s0)
    y, s = ssm_scan(*(jnp.asarray(v) for v in (x, d, a, bm, cm, s0)), chunk)
    assert np.abs(np.asarray(y) - want_y).max() < 1e-4 * np.abs(want_y).max()
    assert np.abs(np.asarray(s) - want_s).max() < 1e-4 * np.abs(want_s).max()
    # the state after padding rows is the last real row's
    _, before = _plain_recurrence(x[1:, :rows - 2], d[1:, :rows - 2], a,
                                  bm[1:, :rows - 2], cm[1:, :rows - 2], s0[1:])
    assert np.abs(np.asarray(s)[1] - before[0]).max() \
        < 1e-4 * np.abs(before).max()
    # one step is the same recurrence
    y1, s1 = ssm_step(*(jnp.asarray(v) for v in (
        x[:, 0], d[:, 0], a, bm[:, 0], cm[:, 0], s0)))
    w_y, w_s = _plain_recurrence(x[:, :1], d[:, :1], a, bm[:, :1], cm[:, :1],
                                 s0)
    assert np.abs(np.asarray(y1) - w_y[:, 0]).max() < 1e-4
    assert np.abs(np.asarray(s1) - w_s).max() < 1e-4


# ------------------------------------------------------------- (e) router
def test_router_against_numpy():
    rng = np.random.default_rng(4)
    h = rng.normal(size=(64, 16)).astype(np.float32)
    w = rng.normal(size=(16, 32)).astype(np.float32)
    bias = rng.normal(scale=0.3, size=32).astype(np.float32)
    k, scaling = 5, 5.0
    idx, gate = route_topk(jnp.asarray(h), jnp.asarray(w), k,
                           score="sigmoid", bias=jnp.asarray(bias),
                           scale=scaling, eps=1e-20)
    s = 1.0 / (1.0 + np.exp(-(h.astype(np.float64) @ w)))
    sel = np.argsort(-(s + bias), axis=1, kind="stable")[:, :k]
    g = np.take_along_axis(s, sel, 1)
    g = g / (g.sum(1, keepdims=True) + 1e-20) * scaling
    assert np.array_equal(np.asarray(idx), sel)
    assert np.abs(np.asarray(gate) - g).max() < 1e-5
    # the bias changed who was chosen somewhere, and nobody's weight
    plain = np.argsort(-s, axis=1, kind="stable")[:, :k]
    assert (np.sort(plain, 1) != np.sort(sel, 1)).any()
    # with 1e-20 in the sum the gates add up to the scale
    assert np.abs(np.asarray(gate).sum(1) - scaling).max() < 1e-5


# ------------------------------------------------------ (f) the share test
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(config):
    """Four models each hold 4 of the router's 16 experts, with the SAME
    router, latent projections and shared expert. The routed parts the four
    give, plus the shared expert once, are the uncut layer (all 16 held),
    through the program's ``routed_ffn(held=)`` and against the reference
    given all 16."""
    c = dict(family.sizes(config, rehearse=True), n_routed_experts=16,
             router_experts=16, held_first=0)
    cfg = nh.nemotron_h_tiny(n_routed_experts=16, router_experts=16)
    w = nh.init_weights(cfg, seed=11, scale=c["init_scale"])
    x = np.random.default_rng(2).normal(size=(24, 64)).astype(np.float32)
    whole_routed, shared = ref.expert_layer_shares(w, x, c)
    tol = 1e-4 * np.abs(whole_routed).max()     # float32 sums, reordered
    moe = lambda n: w["model.moe_layers." + n]
    u = x * (1.0 / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)) \
        * np.asarray(w["model.layers.norm"][1])
    idx, gate = route_topk(jnp.asarray(u), moe("router")[0], 3,
                           score="sigmoid",
                           bias=moe("e_score_correction_bias")[0], scale=5.0,
                           eps=1e-20)
    v = jnp.asarray(u) @ moe("latent_down")[0]
    parts = []
    for first in (0, 4, 8, 12):
        part = routed_ffn(v, idx, gate, None,
                          moe("experts_w1")[:, first:first + 4],
                          moe("experts_w2")[:, first:first + 4], layer=0,
                          held=(first, 4))
        parts.append(np.asarray(part @ moe("latent_up")[0]))
        # the reference given the same share agrees with the program's part
        share = {n: (a[:, first:first + 4] if "experts_w" in n else a)
                 for n, a in w.items()}
        want, _ = ref.expert_layer_shares(share, x, dict(c, held_first=first))
        assert np.abs(parts[-1] - want).max() < 1e-5
    assert all(np.abs(p).max() > 1000 * tol for p in parts)  # each share adds
    assert np.abs(sum(parts) - whole_routed).max() < tol
    whole = ref._expert_rows(
        {n: a for n, a in w.items() if "moe_layers" in n or "layers.norm" in n},
        jnp.asarray(x), jnp.int32(1), jnp.int32(0), key=ref.sizes_key(c),
        bias=True) - x
    assert np.abs(sum(parts) + shared - np.asarray(whole)).max() < tol
    assert np.abs(shared).max() > 10 * tol


def test_a_pair_not_held_reads_no_weight():
    """``routed_ffn(held=)``: the loop runs the tiles of the held pairs
    only: with NaN in every weight of an expert nobody held chose, and rows
    that chose only experts that are not held, the result is finite and
    zero."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(6, 8)).astype(np.float32))
    wu = rng.normal(size=(1, 4, 8, 16)).astype(np.float32)
    wd = rng.normal(size=(1, 4, 16, 8)).astype(np.float32)
    wu[0, 3] = np.nan                                # held expert 11: unchosen
    idx = jnp.asarray([[8, 2], [9, 20], [10, 8], [0, 1], [30, 31], [9, 9]],
                      jnp.int32)
    gate = jnp.ones((6, 2), jnp.float32)
    y = np.asarray(routed_ffn(h, idx, gate, None, jnp.asarray(wu),
                              jnp.asarray(wd), layer=0, held=(8, 4)))
    assert np.isfinite(y).all()
    assert np.abs(y[3]).max() == 0 and np.abs(y[4]).max() == 0
    want0 = np.square(np.maximum(np.asarray(h)[0] @ wu[0, 0], 0)) @ wd[0, 0]
    assert np.abs(y[0] - want0).max() < 1e-4


# ---------------------------------------------------- (g) the layer spec
def test_pool_has_one_layer_and_the_state_two_leaves(config):
    """The cell's 11 layers ``MEMEMEM*EME`` at tiny widths: 1 attention
    layer, so the pool has 1; 5 Mamba layers of state in two leaves of two
    dtypes; 5 expert layers of route read-back."""
    assert config["hybrid_override_pattern"] == "MEMEMEM*EME" \
        == nh._PUBLISHED_PATTERN[:11]
    cfg = nh.nemotron_h_tiny(num_hidden_layers=11, dtype="bfloat16",
                             hybrid_override_pattern="MEMEMEM*EME")
    spec = _layer_spec(cfg)
    assert spec[0] == {"layer": 0, "ssm": 0}
    assert spec[1] == {"layer": 1, "moe": 0}
    assert spec[7] == {"layer": 7, "attn": 0}
    assert spec[10] == {"layer": 10, "moe": 4}
    model = nh.NemotronHForCausalLM(cfg, seed=0)
    srv = ContinuousBatchingServer(model, max_slots=3, max_cache_len=32,
                                   cache_backend="paged", page_size=8)
    assert srv._caches["pool"]["k"].shape == (1, 3 * 4 + 1, 8, 2 * 16)
    state = srv._caches["state"]
    assert sorted(state) == ["conv", "ssm"]
    assert (state["conv"].shape, state["conv"].dtype) == (
        (5, 3, 3, 192), jnp.bfloat16)
    assert (state["ssm"].shape, state["ssm"].dtype) == (
        (5, 3, 8, 16, 16), jnp.float32)
    assert srv._caches["route"].shape == (5, 3, 3)
    assert srv._n_layers == 1 and srv._slot_state is True


def test_published_config_defaults():
    cfg = nh.NemotronHConfig()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim) == (
        88, 4096, 128)
    assert nh.layer_counts(cfg) == (40, 8, 40)
    assert cfg.experts_held == (0, 512) and cfg.router_experts == 512
    assert cfg.ssm_dims == (128, 64, 8, 128, 128) and cfg.conv_dim == 10240
    shapes = nh.param_shapes(cfg)
    assert shapes["model.mamba_layers.in_proj"] == (40, 4096, 18560)
    assert shapes["model.moe_layers.experts_w1"] == (40, 512, 1024, 2688)
    assert shapes["model.moe_layers.shared_w1"] == (40, 4096, 5376)
    with pytest.raises(ValueError, match="names every layer"):
        nh.NemotronHConfig(num_hidden_layers=11)
    with pytest.raises(ValueError, match="outside the router's"):
        nh.NemotronHConfig(n_routed_experts=128, router_experts=512,
                           held_first=400)


def test_seeded_step_sizes_lie_where_the_config_says(config):
    """``dt_bias`` is the inverse softplus of a step in [time_step_min,
    time_step_max]; ``A_log`` = log U[1, 16]; ``D`` 1."""
    cfg = nh.nemotron_h_tiny()
    w = nh.init_weights(cfg, seed=1)
    step = np.log1p(np.exp(np.asarray(w["model.mamba_layers.dt_bias"])))
    assert 0.001 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    a = np.exp(np.asarray(w["model.mamba_layers.A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert np.all(np.asarray(w["model.mamba_layers.D"]) == 1.0)
    assert w["model.mamba_layers.dt_bias"].dtype == jnp.float32
    assert np.abs(np.asarray(
        w["model.moe_layers.e_score_correction_bias"])).max() > 0


# ------------------------------------------------------- (h) the refusals
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
    assert len(srv.run()[rid]) == 4           # and it serves on
