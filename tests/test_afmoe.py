"""The ``afmoe`` decoder (Trinity-Mini) at the ``rehearse`` sizes of
``perfbench/configs/trinity-mini-26b-a3b.json`` (6 layers: 2 dense window
layers, then ``window full window window`` with 8 experts top-2 and a
shared one; hidden 64, 4 q / 2 kv heads of 16, a window of 16 keys,
vocabulary 256), seeded weights, float32, against the plain reference
``perfbench/reference_afmoe.py``:

(a) the model's forward and ``generate()`` (a dense cache, the window a
    mask over it) equal the reference; the reference's negative controls
    (window layers attending the whole context, rope on the full layer, no
    output gate, every matrix at 4 significant bits) differ from it;
(b) ragged prefill in chunks, then decode, through the PAGED cache equals
    the reference ON LOGITS: a launch whose chunk straddles the window's
    edge, decode past the window, two slots whose rings wrap several
    times; the four controls FAIL the same comparison;
(c) the same through ``ContinuousBatchingServer``, with the window
    layers' counters and the pool's pages by layer type;
(d) a window layer holds at most its ring's pages a slot, whatever the
    context;
(e) both paged kernels with a lower page bound equal their references
    with the window mask, through a ring's table;
(f) a model with no window layer hands the kernels what it did before:
    the same operands, and grids sized with no window;
(g) what assumes that a slot's pages hold its whole past refuses by name.

TOLERANCE of (a)-(c): 2e-4 absolute on logits whose spread (std) is about
0.2. Both sides are float32 under ``highest`` matmul precision
(conftest.py) on the same weights: what is left is the order of float32
sums, measured under 1e-6; every control misses by over a hundred times
the limit.
"""
import dataclasses
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.models import afmoe, generation
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import ragged_prefill as rp
from perfbench import reference_afmoe as ref
from perfbench.families import afmoe as family

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
WIDTH, PAGE, ROWS = 128, 4, 8
WINDOW = 16
RING = generation.ring_pages(WINDOW, ROWS, PAGE)      # 7 pages, 28 keys


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "trinity-mini-26b-a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model(config):
    return family.build_model(config, seed=5, rehearse=True)


@pytest.fixture(scope="module")
def sizes(config):
    return family.sizes(config, rehearse=True)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def _want(params, sizes, ids, **kw):
    return ref.row_logits(params, ids, WIDTH, sizes, rows=16, **kw)


def _q4(params):
    """Every floating leaf of two or more dims at 4 significant bits."""
    def q(a):
        m, e = np.frexp(np.asarray(a, np.float32))
        return jnp.asarray(np.ldexp(np.round(m * 16.0) / 16.0, e), a.dtype)
    return {n: q(a) if a.ndim >= 2 else a for n, a in params.items()}


CONTROLS = [("window_whole_context", dict(window=False)),
            ("rope_on_full_layer", dict(rope_full=True)),
            ("no_output_gate", dict(gate=False)),
            ("leaves_4_bits", None),
            ("experts_by_score_alone", dict(route="score")),
            ("bias_weighs", dict(route="bias_weighs"))]


def _control(model, sizes, ids, kw):
    if kw is None:
        return _want(_q4(model.raw_params()), sizes, ids)
    return _want(model.raw_params(), sizes, ids, **kw)


# ------------------------------------------------------------ (a) forward
def test_rehearse_sizes_are_the_tiny_preset(model):
    assert dataclasses.asdict(model.cfg) == dataclasses.asdict(
        afmoe.afmoe_tiny())
    assert model.cfg.sliding_window == WINDOW
    assert generation.attention_window(model.cfg) == WINDOW
    spec = generation._layer_spec(model.cfg)
    # both kinds read the attention stacks; each its own pool
    assert [("ring" in at, at.get("ring", at.get("kv"))) for at in spec] \
        == [(True, 0), (True, 1), (True, 2), (False, 0), (True, 3),
            (True, 4)]
    assert [at.get("dense", at.get("moe")) for at in spec] \
        == [0, 1, 0, 1, 2, 3]


def test_forward_equals_reference_and_the_controls_do_not(model, sizes):
    ids = _ids(60, seed=1)
    got = np.asarray(model(ids[None]).numpy())[0]
    want = _want(model.raw_params(), sizes, ids)
    assert want.std() > 0.1
    assert np.abs(got - want).max() < TOL
    for _, kw in CONTROLS:
        assert np.abs(_control(model, sizes, ids, kw) - want).max() \
            > 100 * TOL


def test_generate_dense_cache_matches_reference(model, sizes):
    """``generate()`` runs the dense cache, where a window layer's window
    is a mask over the whole cache."""
    prompt = _ids(21, seed=3)
    out = np.asarray(model.generate(prompt[None],
                                    max_new_tokens=12).numpy())[0]
    lg = _want(model.raw_params(), sizes, out)
    assert [int(lg[t].argmax()) for t in range(20, 32)] == list(out[21:])


def test_head_rows_leaves_the_rest_at_zero(model, sizes):
    ids = _ids(40, seed=4)
    full = _want(model.raw_params(), sizes, ids)
    tail = ref.row_logits(model.raw_params(), ids, WIDTH, sizes, rows=16,
                          head_rows=10)
    assert np.array_equal(tail[-10:], full[-10:])
    assert not tail[:16].any()          # the blocks the head skipped


# ------------------------------------------- (b) paged prefill and decode
def _bundle(model, slots):
    pages_per_slot = WIDTH // PAGE
    bundle = model._decode_bundle(WIDTH, cache_backend="paged",
                                  page_size=PAGE,
                                  num_pages=slots * pages_per_slot + 1,
                                  window_rows=ROWS)
    caches = bundle[0](slots)
    bt = (1 + np.arange(slots * pages_per_slot, dtype=np.int32)
          ).reshape(slots, pages_per_slot)
    return bundle, dict(caches, bt=jnp.asarray(bt))


def _launch(bundle, caches, slots, chunks, width):
    """One ragged launch, a row a slot: ``chunks`` = {slot: (tokens,
    start)}. Returns ({slot: logits of the chunk's last real row},
    caches)."""
    toks = np.zeros((slots, width), np.int32)
    t0 = np.full((slots,), WIDTH, np.int32)
    last = np.zeros((slots,), np.int32)
    take = np.zeros((slots,), np.int32)
    which = np.full((slots,), slots, np.int32)
    for row, (slot, (tokens, start)) in enumerate(chunks.items()):
        toks[row, :len(tokens)] = tokens
        t0[row], last[row] = start, len(tokens) - 1
        take[row], which[row] = len(tokens), slot
    logits, caches = bundle[5](jnp.asarray(toks), jnp.asarray(t0), caches,
                               jnp.asarray(last), jnp.asarray(take),
                               jnp.asarray(which))
    return {s: np.asarray(logits[r]) for r, s in enumerate(chunks)}, caches


def _decode(bundle, caches, slots, rows):
    """One decode step: ``rows`` = {slot: (token, position)}; every other
    slot rides parked on the sentinel."""
    _, embed_fn, _, head_fn, decode_step, _ = bundle
    tok = np.zeros((slots,), np.int32)
    t = np.full((slots,), WIDTH, np.int32)
    for slot, (token, pos) in rows.items():
        tok[slot], t[slot] = token, pos
    tt = jnp.asarray(t)
    hidden, caches = decode_step(embed_fn(jnp.asarray(tok), tt), caches, tt)
    lg = np.asarray(head_fn(hidden)[:, -1])
    return {s: lg[s] for s in rows}, caches


def _serve_two(model):
    """Slot 0: a prompt of 37 in launches of 8 (the third straddles the
    window's edge), then 40 decode steps (past the window and round the
    ring four times); slot 1: a prompt of 12, prefilled between, then
    decoding beside it. Returns [(slot, position, logits)], caches."""
    slots = 2
    bundle, caches = _bundle(model, slots)
    a, b = _ids(77, seed=7), _ids(52, seed=8)
    got = []
    for start in range(0, 37, 8):
        chunk = a[start:min(start + 8, 37)]
        chunks = {0: (chunk, start)}
        if start == 8:
            chunks[1] = (b[:8], 0)
        if start == 16:
            chunks[1] = (b[8:12], 8)
        out, caches = _launch(bundle, caches, slots, chunks, 8)
        got.append((0, start + len(chunk) - 1, out[0]))
        if 1 in out:
            got.append((1, chunks[1][1] + len(chunks[1][0]) - 1, out[1]))
    for j in range(40):
        rows = {0: (a[37 + j], 37 + j), 1: (b[12 + j], 12 + j)}
        out, caches = _decode(bundle, caches, slots, rows)
        got += [(0, 37 + j, out[0]), (1, 12 + j, out[1])]
    return got, caches, (a, b)


def test_paged_prefill_then_decode_equals_reference(model, sizes):
    got, caches, seqs = _serve_two(model)
    assert caches["pool"]["k"].shape[0] == 1           # the full layer
    assert caches["ring"]["k"].shape == (5, 2 * RING + 1, PAGE, 2 * 16)
    want = [_want(model.raw_params(), sizes, s) for s in seqs]
    worst = max(np.abs(lg - want[slot][pos]).max()
                for slot, pos, lg in got)
    assert worst < TOL, worst
    # decode went past the window, and the ring wrapped
    assert max(pos for slot, pos, _ in got if slot == 0) + 1 \
        > 2 * RING * PAGE


@pytest.mark.parametrize("name,kw", CONTROLS, ids=[c[0] for c in CONTROLS])
def test_each_control_fails_the_comparison(model, sizes, name, kw):
    """The same comparison, against each negative control: every one
    misses the tolerance by far, on the rows past the window most of
    all where it is the window that was dropped."""
    got, _, seqs = _serve_two(model)
    want = [_control(model, sizes, s, kw) for s in seqs]
    worst = max(np.abs(lg - want[slot][pos]).max()
                for slot, pos, lg in got)
    assert worst > 100 * TOL, (name, worst)


# ------------------------------------------------- (c) through the server
def _server(model, **kw):
    kw.setdefault("max_slots", 2)
    return ContinuousBatchingServer(
        model, cache_backend="paged", page_size=PAGE, max_cache_len=WIDTH,
        prefill_tokens_per_tick=ROWS, **kw)


def test_server_prefill_and_decode_equal_reference(model, sizes):
    """Four prompts of 10 to 60 tokens through two slots, 8 prefill
    tokens a tick, 24 tokens each: every launch's logits are held to the
    reference and every emitted token is its argmax; decode passes the
    window, slots are reused, rings wrap."""
    srv = _server(model, telemetry=True)
    assert srv._auto_prefix is False
    assert srv._caches["ring"]["k"].shape[1] == 2 * RING + 1
    seen = []
    launch = srv._ragged_fn

    def spy(tokens, t0, caches, out_idx, take, slots):
        logits, caches = launch(tokens, t0, caches, out_idx, take, slots)
        seen.append((np.asarray(tokens), np.asarray(t0), np.asarray(logits),
                     np.asarray(take)))
        return logits, caches

    srv._ragged_fn = spy
    prompts = [_ids(n, seed=20 + n) for n in (60, 13, 10, 37)]
    rids = [srv.submit(p, max_new_tokens=24) for p in prompts]
    pages = []                  # the pool's pages by layer type a turn
    while srv._turn(idle=True) is not None:
        pages.append(srv._kv.occupancy()["pages_by_layer_type"])
    outs = srv.run()            # the idle server hands its results over
    tables = [_want(model.raw_params(), sizes, np.concatenate([p, outs[r]]))
              for p, r in zip(prompts, rids)]
    for p, r, table in zip(prompts, rids, tables):
        assert [int(table[len(p) - 1 + j].argmax()) for j in range(24)] \
            == list(outs[r])
    checked = 0
    for tokens, t0, logits, takes in seen:
        for row in np.flatnonzero(t0 < WIDTH):
            start, take = int(t0[row]), int(takes[row])
            for p, table in zip(prompts, tables):
                if start + take == len(p) and np.array_equal(
                        p[start:], tokens[row, :take]):
                    assert np.abs(logits[row] - table[len(p) - 1]
                                  ).max() < TOL
                    checked += 1
    assert checked == 4
    # (d) the window layers held at most their rings, however long the
    # contexts grew (84 tokens: 21 pages of the full layer a slot)
    assert max(p["full_attention"] for p in pages) > 2 * RING
    assert all(p["sliding_attention"] <= 2 * RING
               and p["ring_pages_per_slot"] == RING for p in pages)
    s = srv.stats
    # the window layers' keys: the live decode rows' contexts there, and
    # what their windows attended (a quarter or so at 84 tokens)
    assert 0 < s["attn_keys_window_attended"] < s["attn_keys_window_context"]
    assert s["attn_keys_window_attended"] \
        <= 5 * WINDOW * s["decode_live_rows"]
    assert s["decode_grid_steps"] == s["decode_live_pages"] > 0
    snap = srv.telemetry.registry.snapshot()
    samples = snap["serving_attn_keys_total"]["samples"]
    total = lambda kind: sum(v for k, v in samples.items() if kind in k)
    assert total("window_context") == s["attn_keys_window_context"]
    assert total("window_attended") == s["attn_keys_window_attended"]
    assert "pages_by_layer_type" in srv._kv.telemetry_stats()


# ------------------------------------------------------- (d) ring bound
@pytest.mark.parametrize("width", [128, 1024, 4096])
def test_a_window_layer_holds_its_ring_at_any_context(model, width):
    """The ring pool's pages depend on the window, the launch's rows and
    the slots, never on the context a slot may reach; a slot's table
    names only its own ring."""
    bundle = model._decode_bundle(width, cache_backend="paged",
                                  page_size=PAGE, num_pages=9,
                                  window_rows=ROWS)
    caches = jax.eval_shape(lambda: bundle[0](3))
    assert caches["ring"]["k"].shape[1] == 3 * RING + 1
    rbt = generation.ring_table(3, RING, width // PAGE)
    for slot in range(3):
        row = set(rbt[slot].tolist())
        assert row == set(range(1 + slot * RING, 1 + (slot + 1) * RING))
    # the pages from a launch's first key to its last write fit the ring
    assert RING * PAGE >= WINDOW - 1 + ROWS + PAGE


# ------------------------------------------------------- (e) the kernels
def _pools(seed=0, pages=40, layers=2, kvh=2, hd=16):
    rng = np.random.default_rng(seed)
    shape = (layers, pages, PAGE, kvh * hd)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


@pytest.mark.parametrize("lengths", [[5, 13, 0], [1, 16, 17], [31, 3, 28]])
def test_decode_kernel_with_a_window_equals_the_reference(lengths):
    kp, vp = _pools()
    table = jnp.asarray(generation.ring_table(3, 9, 16))   # ring of 9
    q = jnp.asarray(np.random.default_rng(1).normal(size=(3, 4, 16)),
                    jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = pa.paged_attention(q, kp, vp, table, lengths, 0.25,
                             interpret=True, layer=1, window=WINDOW)
    want = pa._ref_paged_attention(q, kp, vp, table, lengths, 0.25,
                                   layer=1, window=WINDOW)
    live = np.asarray(lengths) > 0
    assert np.abs(np.asarray(got - want))[live].max() < 1e-5
    # the grid starts at the window's first page
    pages, steps = pa.decode_grid(np.asarray(lengths), PAGE, WINDOW)
    full, _ = pa.decode_grid(np.asarray(lengths), PAGE)
    assert (pages <= full).all() and (pages <= -(-(WINDOW - 1) // PAGE) + 1
                                      ).all()


@pytest.mark.parametrize("t0,take", [([0, 5, 128], [12, 7, 0]),
                                     ([3, 20, 0], [9, 12, 1]),
                                     ([14, 30, 44], [12, 12, 12])])
def test_prefill_kernel_with_a_window_equals_the_reference(t0, take):
    kp, vp = _pools(seed=2)
    table = jnp.asarray(generation.ring_table(3, 9, 32))
    q = jnp.asarray(np.random.default_rng(3).normal(size=(3, 12, 4, 16)),
                    jnp.float32)
    t0, take = jnp.asarray(t0, jnp.int32), jnp.asarray(take, jnp.int32)
    got = rp.ragged_prefill_attention(q, kp, vp, table, t0, take=take,
                                      sm_scale=0.25, interpret=True,
                                      layer=0, window=WINDOW)
    want = rp._ref_ragged_prefill(q, kp, vp, table, t0, 0.25, layer=0,
                                  window=WINDOW)
    real = np.arange(12)[None] < np.asarray(take)[:, None]
    assert np.abs(np.asarray(got - want))[real].max() < 1e-5
    pages, _ = pa.prefill_grid(np.asarray(t0), np.asarray(take), 12,
                               rp.QUERY_TILE, PAGE, 32, WINDOW)
    full, _ = pa.prefill_grid(np.asarray(t0), np.asarray(take), 12,
                              rp.QUERY_TILE, PAGE, 32)
    assert (pages <= full).all() and ((pages > 0) == (full > 0)).all()


# ------------------------------------ (f) models without a window layer
@pytest.fixture
def kernels_on():
    """The paged kernels as on the chip (interpreted): ``on_tpu()``
    answers yes, and forgets it afterwards."""
    from paddle_tpu.ops.pallas import on_tpu
    on_tpu.cache_clear()
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        assert on_tpu()
    yield
    on_tpu.cache_clear()


def _no_window_models():
    from paddle_tpu.models import lfm2
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    yield GPTForCausalLM(GPTConfig(vocab_size=128, hidden_size=64,
                                   num_layers=2, num_heads=4,
                                   max_seq_len=64))
    yield lfm2.Lfm2MoeForCausalLM(lfm2.lfm2_tiny(), seed=0)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` in ``jaxpr`` and the jaxprs it holds."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _pallas_calls(sub)
    return out


def test_models_without_a_window_lower_the_same_kernels(kernels_on):
    """GPT-2 and lfm2: every schedule is made with no window, the decode
    kernel takes its 8 operands (the grid's dynamic bound, 4
    scalar-prefetched, q, k, v) and the prefill kernel its 10 (the bound,
    6, q, k, v), and neither kernel body is given a window."""
    made, bodies = [], []
    real = (pa.decode_schedule, pa.prefill_schedule)
    kernels = (pa._paged_attn_kernel, rp._ragged_prefill_kernel)

    def schedule(i):
        def f(*a, **kw):
            made.append(a[3:] + tuple(kw.values()) if i == 0
                        else a[6:] + tuple(kw.values()))
            return real[i](*a, **kw)
        return f

    def body(i):
        def f(*a, **kw):
            bodies.append(kw.get("window"))
            return kernels[i](*a, **kw)
        return f

    for m in _no_window_models():
        assert generation.attention_window(m.cfg) is None
        b = m._decode_bundle(64, cache_backend="paged", page_size=8,
                             num_pages=17)
        caches = b[0](2)
        assert "ring" not in caches and "rbt" not in caches
        t = jnp.asarray([5, 64], jnp.int32)
        with mock.patch.object(pa, "decode_schedule", schedule(0)), \
                mock.patch.object(pa, "prefill_schedule", schedule(1)), \
                mock.patch.object(pa, "_paged_attn_kernel", body(0)), \
                mock.patch.object(rp, "_ragged_prefill_kernel", body(1)):
            dec = jax.make_jaxpr(lambda c: b[2](
                b[1](jnp.zeros((2,), jnp.int32), t), c, t))(caches)
            pre = jax.make_jaxpr(lambda c: b[5]._fn(
                jnp.zeros((2, 16), jnp.int32), jnp.asarray([0, 64]), c,
                jnp.zeros((2,), jnp.int32), jnp.asarray([9, 0]),
                jnp.asarray([0, 2])))(caches)
        for jaxpr, operands, prefetch in ((dec, 8, 4), (pre, 10, 6)):
            calls = _pallas_calls(jaxpr.jaxpr)
            assert calls
            for eqn in calls:
                assert len(eqn.invars) == operands
                assert eqn.params["grid_mapping"].num_index_operands \
                    == prefetch
    assert made and all(w in ((), (None,)) for w in made)
    assert bodies and all(w is None for w in bodies)


# ------------------------------------------------------------ (g) refusals
def _refused(model, **kw):
    with pytest.raises(NotImplementedError) as e:
        kw.pop("_call", lambda srv: None)(_server(model, **kw))
    assert "window layers" in str(e.value) and "ROADMAP B3" in str(e.value)
    return str(e.value)


def _migrate(srv):
    rid = srv.submit(_ids(9), max_new_tokens=4)
    srv.step()
    srv.migrate_out(rid)


@pytest.mark.parametrize("kw,named", [
    (dict(auto_prefix_cache=True), "auto_prefix_cache=True"),
    (dict(admission="optimistic"), "admission='optimistic'"),
    (dict(host_tier=True), "host_tier"),
    (dict(prefill_mode="dense"), "prefill_mode='dense'"),
    (dict(_call=_migrate), "migration"),
    (dict(_call=lambda srv: srv.register_prefix(_ids(9))),
     "register_prefix"),
], ids=["prefix-hit", "preemption-replay", "host-tier", "dense-prefill",
        "migration", "register-prefix"])
def test_what_moves_pages_refuses_a_window_model(model, kw, named):
    assert named in _refused(model, **kw)
