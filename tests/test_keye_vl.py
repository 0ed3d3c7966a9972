"""Keye-VL-2.0's language model at the ``rehearse`` sizes of
``perfbench/configs/keye-vl2-30b-a3b.json`` (2 layers, hidden 64, 4 q / 2
kv heads of 16, 8 experts top-2 of width 32, indexer 2 heads of 8 keeping
8 keys, vocabulary 256), seeded weights, float32, against the plain
reference ``perfbench/reference_keye_vl2.py``:

(a) the model's forward equals the reference, with unequal (time, height,
    width) positions too;
(b) ragged prefill in chunks, then decode, through the PAGED cache equals
    the reference's full forward ON LOGITS, for contexts below, at and
    above ``topk`` with a chunk boundary inside and past ``topk``;
(c) a prefix-cache hit, a preemption replay and a host-tier round trip
    each reproduce the cold tokens: the indexer's keys travel with their
    pages;
(d) the negative control: the reference with the selection left out
    differs from the reference by far more than (b)'s tolerance;
(e) the router's top-k for k in {1, 2, 8} against a sort, and the routed
    FFN against every chosen expert applied one by one; rows masked dead
    join no expert's group and leave the live rows bit for bit.

TOLERANCE of (a) and (b): 2e-4 absolute on logits whose spread (std) is
about 0.17. Both sides are float32 under ``highest`` matmul precision
(conftest.py) on the same weights, so what is left is the order of
float32 sums (row tiles, the gated sum over experts, the paged path's
gathers): measured 2e-7 to 5e-6. The limit sits two orders above that
and three below (d)'s difference (0.1 and more), so a selected set that
differs by ONE key, or one expert chosen otherwise, fails it.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.tensor import unwrap
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.kv_tier import HostTier
from paddle_tpu.models.keye_vl import (KeyeVL2Config, KeyeVL2ForCausalLM,
                                       keye_vl2_tiny)
from paddle_tpu.ops.key_selection import topk_mask
from paddle_tpu.ops.routed_ffn import _layout, route_topk, routed_ffn
from perfbench import reference_keye_vl2 as ref
from perfbench.families import keye_vl2 as family

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
WIDTH, PAGE = 64, 8
TOPK = 8


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "keye-vl2-30b-a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model(config):
    """The family's own build at the rehearse sizes (float32), every
    matrix N(0, 0.02): without the cell's ``init_scale`` attention is a
    large share of the residual, so a wrong key or expert moves the
    logits most (the scaled draw goes through ``--rehearse``,
    tests/perfbench/test_perfbench_keye_vl2.py)."""
    plain = dict(config, assumed={k: v for k, v in config["assumed"].items()
                                  if k != "init_scale"})
    return family.build_model(plain, seed=2147483659, rehearse=True)


@pytest.fixture(scope="module")
def sizes(config):
    return family.sizes(config, rehearse=True)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


# ------------------------------------------------------------ (a) forward
@pytest.mark.parametrize("positions", ["text", "unequal"])
def test_forward_equals_reference(model, sizes, positions):
    ids = _ids(40)
    pos = None
    if positions == "unequal":
        rng = np.random.default_rng(1)
        pos = np.stack([np.arange(40), rng.integers(0, 30, 40),
                        rng.integers(0, 30, 40)]).astype(np.int32)
    got = np.asarray(unwrap(model(
        ids[None], None if pos is None else jnp.asarray(pos[:, None]))))[0]
    want = ref.row_logits(model.raw_params(), ids, WIDTH, sizes,
                          position_ids=pos, rows=16)
    assert want.std() > 0.05
    assert np.abs(got - want).max() < TOL
    if pos is not None:      # and the 3-part positions do matter
        text = ref.row_logits(model.raw_params(), ids, WIDTH, sizes, rows=16)
        assert np.abs(want - text).max() > 100 * TOL


def test_rehearse_sizes_are_the_tiny_preset(model):
    """The configuration file's ``rehearse`` block and ``keye_vl2_tiny``
    are the same model."""
    tiny = dataclasses.asdict(keye_vl2_tiny())
    mine = dataclasses.asdict(model.cfg)
    for cfg in (tiny, mine):
        cfg["rope_scaling"].pop("type", None)
    assert mine == tiny
    assert model.cfg.indexer == (2, 8, TOPK)


# ------------------------------------------- (b) paged prefill and decode
def _paged_logits(model, ids, prompt_len, chunk):
    """Logits of positions ``chunk boundaries - 1`` and of every decoded
    position, through the paged bundle: the prompt in ragged chunks of
    ``chunk`` into pool pages, then one decode step a token. Slot 1 of 2
    carries the sequence; slot 0 idles (parked past its table)."""
    slots, pages_per_slot = 2, WIDTH // PAGE
    bundle = model._decode_bundle(WIDTH, cache_backend="paged",
                                  page_size=PAGE,
                                  num_pages=slots * pages_per_slot + 1)
    init, embed_fn, _, head_fn, decode_step, prefill = bundle
    caches = init(slots)
    bt = np.zeros((slots, pages_per_slot), np.int32)
    bt[1] = 1 + np.arange(pages_per_slot)
    caches = dict(caches, bt=jnp.asarray(bt))
    out = {}
    for start in range(0, prompt_len, chunk):
        take = min(chunk, prompt_len - start)
        toks = np.zeros((slots, chunk), np.int32)
        toks[1, :take] = ids[start:start + take]
        t0 = np.asarray([WIDTH, start], np.int32)       # slot 0 idle
        logits, caches = prefill(jnp.asarray(toks), jnp.asarray(t0), caches,
                                 jnp.asarray([0, take - 1], np.int32),
                                 jnp.asarray([0, take], np.int32),
                                 jnp.arange(slots, dtype=jnp.int32))
        out[start + take - 1] = np.asarray(logits[1])
    for t in range(prompt_len, len(ids)):
        tt = jnp.asarray([WIDTH, t], jnp.int32)
        x = embed_fn(jnp.asarray([0, ids[t]], jnp.int32), tt)
        hidden, caches = decode_step(x, caches, tt)
        out[t] = np.asarray(head_fn(hidden)[1, -1])
    assert set(caches["pool"]) == {"k", "v", "ki"}
    assert caches["pool"]["ki"].shape == (2, slots * pages_per_slot + 1,
                                          PAGE, 8)
    return out


@pytest.mark.parametrize("prompt_len,chunk", [
    (5, 4),      # context below topk, a boundary inside it
    (8, 4),      # context exactly topk at the prompt's end
    (8, 8),      # one chunk that ends at topk
    (20, 4),     # above topk, boundaries inside and past it
    (20, 16),    # above topk, the first boundary past it
    (40, 16),    # far above, two boundaries past it
])
def test_paged_prefill_then_decode_equals_reference(model, sizes,
                                                    prompt_len, chunk):
    ids = _ids(prompt_len + 6, seed=prompt_len)
    want = ref.row_logits(model.raw_params(), ids, WIDTH, sizes, rows=16)
    got = _paged_logits(model, ids, prompt_len, chunk)
    assert len(got) >= 6 + 1
    worst = max(np.abs(row - want[t]).max() for t, row in got.items())
    assert worst < TOL, (worst, sorted(got))


# ----------------------------------------------------- (c) pages that move
def _server(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_pages", 17)
    return ContinuousBatchingServer(
        model, cache_backend="paged", page_size=PAGE, max_cache_len=WIDTH,
        prefill_tokens_per_tick=16, **kw)


@pytest.fixture(scope="module")
def cold(model):
    """Tokens of a prompt served alone on an empty server."""
    memo = {}

    def tokens(prompt, new):
        key = (prompt.tobytes(), new)
        if key not in memo:
            srv = _server(model, auto_prefix_cache=False)
            rid = srv.submit(prompt, max_new_tokens=new)
            memo[key] = np.asarray(srv.run()[rid])
        return memo[key]

    return tokens


def _check_reference(model, sizes, prompt, out):
    """Every emitted token is the reference's own argmax."""
    lg = ref.row_logits(model.raw_params(), np.concatenate([prompt, out]),
                        WIDTH, sizes, rows=16)
    for j, tok in enumerate(out):
        assert lg[len(prompt) - 1 + j].argmax() == tok


def test_prefix_cache_hit_reproduces_cold_tokens(model, sizes, cold):
    srv = _server(model)
    first = _ids(28, seed=7)
    rid = srv.submit(first, max_new_tokens=4)
    srv.run()
    # the same 24 leading tokens (three whole pages), another tail
    second = np.concatenate([first[:24], _ids(9, seed=8)])
    rid = srv.submit(second, max_new_tokens=6)
    out = np.asarray(srv.run()[rid])
    assert srv.stats["prefix_auto_hits"] >= 1
    assert srv.stats["prefix_auto_hit_tokens"] >= 24
    np.testing.assert_array_equal(out, cold(second, 6))
    _check_reference(model, sizes, second, out)
    assert srv.pool_balance()[1] == 0


def test_preemption_replay_reproduces_cold_tokens(model, cold):
    # 3 slots growing page by page over 8 usable pages: someone is parked
    srv = _server(model, max_slots=3, num_pages=9, admission="optimistic",
                  auto_prefix_cache=False)
    prompts = [_ids(n, seed=20 + n) for n in (17, 18, 19)]
    rids = [srv.submit(p, max_new_tokens=22) for p in prompts]
    res = srv.run()
    assert srv.stats["preemptions"] >= 1
    assert srv.stats["preempt_resumed"] >= 1
    for p, rid in zip(prompts, rids):
        np.testing.assert_array_equal(np.asarray(res[rid]), cold(p, 22))
    bal = srv.pool_balance()
    assert bal[1] == 0 and bal.preempted == 0


def test_host_tier_round_trip_reproduces_cold_tokens(model, cold):
    tier = HostTier()
    srv = _server(model, max_slots=1, num_pages=9, host_tier=tier)
    first = _ids(24, seed=31)
    srv.submit(first, max_new_tokens=4)
    srv.run()
    for seed in (32, 33, 34):             # fill the pool: first's pages spill
        srv.submit(_ids(24, seed=seed), max_new_tokens=4)
        srv.run()
    assert tier.spilled_pages_total >= 1
    # a spilled page is all three leaves: K and V (2 kv heads of 16) and
    # the indexer's keys (1 head of 8), 2 layers x 8 positions, float32
    assert tier.bytes_used == tier.entries * 2 * PAGE * (2 * 32 + 8) * 4
    payload = srv._spill_payload(1)
    assert [a.shape for a in payload] == [(2, PAGE, 2, 16), (2, PAGE, 1, 8),
                                          (2, PAGE, 2, 16)]     # k, ki, v
    # the returning prompt's pages come back from the host
    again = np.concatenate([first, _ids(5, seed=35)])
    rid = srv.submit(again, max_new_tokens=6)
    out = np.asarray(srv.run()[rid])
    assert tier.restored_pages_total >= 1
    np.testing.assert_array_equal(out, cold(again, 6))


def test_counters_in_stats_and_registry(model):
    srv = _server(model, telemetry=True, auto_prefix_cache=False)
    prompts = [_ids(n, seed=40 + n) for n in (12, 30)]
    for p in prompts:
        srv.submit(p, max_new_tokens=5)
    srv.run()
    s = srv.stats
    assert s["decode_ticks"] > 0
    assert 0 < s["moe_live_rows"] < s["moe_rows"]
    # every live decode row chose 2 experts in each of 2 layers
    assert s["moe_experts_touched"] >= 2 * 2
    # 4 decode rows a request (the first token is the prefill's), at
    # contexts of prompt + 1 .. prompt + 4 keys, in each of 2 layers;
    # what was KEPT is counted on the device where the mask is made, and
    # an exact selection keeps min(context, topk) = 8 of each
    assert s["attn_keys_context"] == 2 * sum(
        len(p) + j for p in prompts for j in range(1, 5))
    assert s["attn_keys_selected"] == 2 * 8 * TOPK
    # key selection attends through XLA: neither paged kernel's grid ran
    assert s["prefill_chunks"] > 0 and s["decode_ticks"] > 0
    assert (s["prefill_grid_steps"], s["prefill_live_steps"],
            s["decode_grid_steps"]) == (0, 0, 0)
    snap = srv.telemetry.registry.snapshot()

    def total(name, **labels):
        return sum(v["value"] if isinstance(v, dict) else v
                   for k, v in snap[name]["samples"].items()
                   if all(x in k for x in labels.values()))

    assert total("serving_moe_rows_total", kind="launched") == s["moe_rows"]
    assert total("serving_moe_rows_total", kind="live") == s["moe_live_rows"]
    assert total("serving_moe_experts_touched_total") \
        == s["moe_experts_touched"]
    assert total("serving_attn_keys_total", kind="context") \
        == s["attn_keys_context"]
    assert total("serving_attn_keys_total", kind="selected") \
        == s["attn_keys_selected"]


def test_one_live_slot_of_four_pays_for_its_own_rows(model, cold):
    """One request on a server of four slots: the three parked slots'
    rows join no expert's group and select no key. ``moe_rows`` counts
    what the experts computed (the prompt's one chunk of 16 with its 4
    rows of padding, then a row a decode tick), ``decode_rows`` the 4
    rows every tick carried, and the keys kept are the live row's own."""
    srv = _server(model, max_slots=4, num_pages=33, auto_prefix_cache=False)
    prompt = _ids(12, seed=61)
    rid = srv.submit(prompt, max_new_tokens=5)
    out = np.asarray(srv.run()[rid])
    np.testing.assert_array_equal(out, cold(prompt, 5))
    s = srv.stats
    assert s["decode_ticks"] == 4
    assert (s["decode_rows"], s["decode_live_rows"]) == (16, 4)
    assert (s["moe_rows"], s["moe_live_rows"]) == (16 + 4, 12 + 4)
    assert s["attn_keys_context"] == 2 * sum(12 + j for j in range(1, 5))
    assert s["attn_keys_selected"] == 2 * 4 * TOPK


def test_kept_keys_are_counted_where_the_mask_is_made(model, monkeypatch):
    """A program that left the selection out would read 100: the count
    is the mask's own sum, not arithmetic on lengths."""
    from paddle_tpu.ops import key_selection
    monkeypatch.setattr(key_selection, "topk_mask",
                        lambda scores, k, valid: valid)
    model.reset_generate_cache()
    try:
        srv = _server(model, auto_prefix_cache=False)
        srv.submit(_ids(12, seed=52), max_new_tokens=5)
        srv.run()
        s = srv.stats
        assert s["attn_keys_selected"] == s["attn_keys_context"] > 0
    finally:
        model.reset_generate_cache()


def test_generate_dense_cache_matches_reference(model, sizes):
    """``generate()`` (the dense cache with its own indexer-key leaf)
    emits the reference's argmax at every step."""
    prompt = _ids(21, seed=50)
    full = np.asarray(unwrap(model.generate(prompt[None], max_new_tokens=6,
                                            max_cache_len=WIDTH)))[0]
    np.testing.assert_array_equal(full[:21], prompt)
    _check_reference(model, sizes, prompt, full[21:])


# ------------------------------------------------- (d) negative control
def test_reference_without_selection_differs(model, sizes):
    ids = _ids(40, seed=3)
    with_sel = ref.row_logits(model.raw_params(), ids, WIDTH, sizes, rows=16)
    without = ref.row_logits(model.raw_params(), ids, WIDTH, sizes, rows=16,
                             select=False)
    # every valid key is kept while t + 1 <= topk ...
    assert np.abs(with_sel[:TOPK] - without[:TOPK]).max() == 0.0
    # ... and past it the selection changes the logits by far more than
    # the tolerance of (b)
    assert np.abs(with_sel[TOPK:] - without[TOPK:]).max() > 100 * TOL


# ---------------------------------------------- (e) routing and selection
@pytest.mark.parametrize("k", [1, 2, 8])
def test_router_topk_against_a_sort(k):
    rng = np.random.default_rng(k)
    h = jnp.asarray(rng.normal(size=(33, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
    idx, gate = route_topk(h, w, k)
    probs = np.asarray(jax.nn.softmax(h @ w, -1))
    order = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
    np.testing.assert_array_equal(np.asarray(idx), order)
    picked = np.take_along_axis(probs, order, -1)
    np.testing.assert_allclose(np.asarray(gate),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(route_topk(h, w, k, False)[1]),
                               picked, rtol=1e-6)


@pytest.mark.parametrize("k", [1, 2, 8])
def test_routed_ffn_against_each_expert_applied(k):
    rng = np.random.default_rng(10 + k)
    n, hid, experts, width = 21, 16, 12, 10
    h = jnp.asarray(rng.normal(size=(n, hid)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(hid, experts)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(3, experts, hid, width)) * 0.3,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(3, experts, width, hid)) * 0.3,
                     jnp.float32)
    idx, gate = route_topk(h, router, k)
    got = jax.jit(lambda l: routed_ffn(h, idx, gate, wg, wu, wd, layer=l))(2)
    want = np.zeros((n, hid), np.float32)
    for row in range(n):
        for j in range(k):
            e = int(idx[row, j])
            a, b = h[row] @ wg[2, e], h[row] @ wu[2, e]
            want[row] += float(gate[row, j]) * np.asarray(
                (jax.nn.silu(a) * b) @ wd[2, e])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


@pytest.mark.parametrize("k", [1, 2, 8])
def test_routed_ffn_dead_rows_join_no_group(k):
    """With a liveness mask the live rows equal the unmasked call bit
    for bit, a dead row (NaN, as an idle slot's are) comes out zero, and
    the tiles that run are those of a call on the live rows alone."""
    rng = np.random.default_rng(20 + k)
    n, hid, experts, width, tile = 21, 16, 12, 10, 4
    h = rng.normal(size=(n, hid)).astype(np.float32)
    live = rng.random(n) < 0.4
    live[:2] = True, False
    router = jnp.asarray(rng.normal(size=(hid, experts)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(experts, hid, width)) * 0.3,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(experts, width, hid)) * 0.3,
                     jnp.float32)
    idx, gate = route_topk(jnp.asarray(h), router, k)
    plain = np.asarray(jax.jit(
        lambda hh: routed_ffn(hh, idx, gate, wg, wu, wd, tile=tile))(
            jnp.asarray(h)))
    h[~live] = np.nan
    dead_idx, dead_gate = route_topk(jnp.asarray(h), router, k)
    got = np.asarray(jax.jit(
        lambda hh, m: routed_ffn(hh, dead_idx, dead_gate, wg, wu, wd,
                                 tile=tile, live=m))(jnp.asarray(h),
                                                     jnp.asarray(live)))
    np.testing.assert_array_equal(got[live], plain[live])
    assert (got[~live] == 0).all()
    # the layout: the masked call's tiles against the live rows' own
    m = n * k
    n_tiles = -(-m // tile) + experts
    masked = jnp.where(jnp.repeat(jnp.asarray(live), k),
                       idx.reshape(m), experts)
    alone = idx[np.where(live)[0]].reshape(-1)
    *_, te_masked, used_masked = _layout(masked, experts, tile, n_tiles)
    *_, te_alone, used_alone = _layout(
        alone, experts, tile, -(-alone.shape[0] // tile) + experts)
    *_, used_all = _layout(idx.reshape(m), experts, tile, n_tiles)
    assert int(used_masked) == int(used_alone) < int(used_all)
    np.testing.assert_array_equal(np.asarray(te_masked)[:int(used_masked)],
                                  np.asarray(te_alone)[:int(used_alone)])


@pytest.mark.parametrize("k", [1, 8, 64])
def test_topk_mask_is_exact_with_ties_to_the_lower_position(k):
    rng = np.random.default_rng(k)
    rows, width = 9, 50
    scores = np.round(rng.normal(size=(rows, width)) * 2) / 2   # many ties
    scores = scores.astype(np.float32)
    valid = np.arange(width)[None] <= rng.integers(0, width, (rows, 1))
    got = np.asarray(topk_mask(jnp.asarray(scores), k, jnp.asarray(valid)))
    for r in range(rows):
        s = np.where(valid[r], scores[r], -np.inf)
        keep = np.argsort(-s, kind="stable")[:min(k, valid[r].sum())]
        want = np.zeros(width, bool)
        want[keep] = True
        np.testing.assert_array_equal(got[r], want)


def test_published_config_defaults():
    """``KeyeVL2Config()`` is the published 30B-A3B language model."""
    c = KeyeVL2Config()
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim) == (2048, 32, 4, 128)
    assert (c.num_experts, c.num_experts_per_tok,
            c.moe_intermediate_size) == (128, 8, 768)
    assert c.indexer == (16, 64, 2048) and c.vocab_size == 151936
    assert c.num_hidden_layers == 48


def test_dense_prefill_mode_moves_all_three_leaves(model, cold):
    """``prefill_mode="dense"`` prefills on a dense batch-1 cache and
    scatters its rows into pages; an auto-prefix hit gathers them back:
    both walk the pool's leaves, the indexer's keys among them."""
    srv = _server(model, prefill_mode="dense")
    first = _ids(26, seed=61)
    rid = srv.submit(first, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(srv.run()[rid]),
                                  cold(first, 5))
    second = np.concatenate([first[:24], _ids(7, seed=62)])
    rid = srv.submit(second, max_new_tokens=5)
    out = np.asarray(srv.run()[rid])
    assert srv.stats["prefix_auto_hits"] >= 1
    np.testing.assert_array_equal(out, cold(second, 5))
