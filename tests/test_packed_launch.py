"""Packed prefill launches (``continuous_batching._launch_row_limit``): a
ragged launch has rows for the slots in its plan, row j being slot
``slots[j]``, and as many of them as its row limit allows at its width (at
most a row a slot, at least one). The limit is the power of two over the
server's per-tick prefill budget, the rows that budget can fill, under a
ceiling of 4,096. Every family serves the same tokens whatever the limit,
and a plan that does not fit waits for the next launch."""
import numpy as np
import pytest

from paddle_tpu.inference import continuous_batching as cb
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer


def _gpt():
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
    pt.seed(3)
    m = GPTForCausalLM(gpt2_tiny())
    m.eval()
    return m


def _keye():
    from paddle_tpu.models.keye_vl import KeyeVL2ForCausalLM, keye_vl2_tiny
    m = KeyeVL2ForCausalLM(keye_vl2_tiny(), seed=3)
    m.eval()
    return m


def _serve(model, limit, lens=(20, 5, 13, 9, 17, 3), **kw):
    srv = ContinuousBatchingServer(
        model, max_slots=4, max_cache_len=64, cache_backend="paged",
        page_size=8, prefill_tokens_per_tick=16, auto_prefix_cache=False,
        **kw)
    # the budget's own limit is 16: hold other limits too
    assert srv._launch_rows == 16
    srv._launch_rows = limit
    shapes = []
    launch = srv._ragged_fn

    def spy(*args):
        shapes.append((tuple(args[0].shape), len(args)))
        return launch(*args)

    srv._ragged_fn = spy
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in lens]
    rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
    outs = srv.run()
    assert srv.pool_balance()[1] == 0
    return [list(outs[r]) for r in rids], shapes, srv


@pytest.mark.parametrize("build", [_gpt, _keye], ids=["gpt", "keye"])
def test_packed_launches_serve_the_same_tokens(build):
    model = build()
    want, wide, _ = _serve(model, 4096)
    got, narrow, srv = _serve(model, 16)
    assert got == want
    # one program: tokens, t0, caches, out_idx, take, slots
    assert all(n == 6 for _, n in wide + narrow)
    # room for every slot: a row a slot, whatever the plan holds
    assert all(rows == 4 for (rows, _), _ in wide)
    # 16 rows: 16 // C chunks a launch, so fewer rows than slots from 8 up
    assert any(rows < 4 for (rows, _), _ in narrow)
    for (rows, width), _ in narrow:
        assert rows == min(4, max(1, 16 // width))
    assert srv.stats["prefill_tokens"] == sum((20, 5, 13, 9, 17, 3))


def test_a_plan_that_does_not_fit_waits_for_the_next_launch():
    """Four prompts of 4 tokens under a budget of 16 make one launch of
    four slot-chunks; at 8 rows of width 4 only two fit a launch."""
    model = _gpt()
    _, wide, a = _serve(model, 4096, lens=(4, 4, 4, 4))
    _, narrow, b = _serve(model, 8, lens=(4, 4, 4, 4))
    assert [s for s, _ in wide] == [(4, 4)]
    assert [s for s, _ in narrow] == [(2, 4), (2, 4)]
    assert a.stats["prefill_chunks"] == b.stats["prefill_chunks"] == 4


# ------------------------------------------------- the limit is the budget's
@pytest.mark.parametrize("budget,rows", [
    (1, 1), (16, 16), (1000, 1024), (1024, 1024), (1025, 2048),
    (4096, 4096), (16384, 4096)])
def test_row_limit_is_the_power_of_two_over_the_budget(budget, rows):
    """The smallest limit that never shortens a take the budget allows,
    under the ceiling: 4,096 rows whatever a 16k budget could carry."""
    assert cb._launch_row_limit(budget) == rows
    assert rows <= cb._LAUNCH_ROWS_MAX == 4096
    assert rows >= min(budget, cb._LAUNCH_ROWS_MAX)


def _plan_only(budget, lens, slots=4, telemetry=None):
    """A server under ``budget`` whose launches are NOT run (the planner
    and its counters are what is held here): returns the launches' (rows,
    width, takes) and the server, after the prompts' first tokens."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
    span = -(-(max(lens) + 4) // 8) * 8
    pt.seed(3)
    model = GPTForCausalLM(gpt2_tiny(max_seq_len=span))
    model.eval()
    srv = ContinuousBatchingServer(
        model, max_slots=slots, max_cache_len=span, cache_backend="paged",
        page_size=8, prefill_tokens_per_tick=budget,
        auto_prefix_cache=False, telemetry=telemetry)
    launches = []

    def planned(tokens, t0, caches, out_idx, take, slots_):
        launches.append((tokens.shape[0], tokens.shape[1],
                         sorted(int(n) for n in np.asarray(take) if n)))
        return jnp.zeros((tokens.shape[0], 256), jnp.float32), caches

    srv._ragged_fn = planned
    rng = np.random.default_rng(0)
    for n in lens:
        srv.submit(rng.integers(0, 256, n).astype(np.int32),
                   max_new_tokens=1)
    srv.run()
    return launches, srv


@pytest.mark.parametrize("budget", [16, 1000, 1024, 16384])
def test_launch_shapes_follow_the_budget(budget):
    """Every launch has ``min(slots, max(1, R // C))`` rows, ``R`` the
    budget's limit, and a take as large as the budget launches whole, in
    one row (at 16,384 in a launch of one row: the ceiling packs fewer
    short chunks, it cuts no take)."""
    R = min(1 << (budget - 1).bit_length(), 4096)
    lens = (budget, max(2, budget // 3), max(2, budget // 20), 3, 2)
    launches, srv = _plan_only(budget, lens)
    assert srv._launch_rows == R
    for rows, width, _ in launches:
        assert rows == min(4, max(1, R // width))
    # the head of the FIFO is a whole budget's take: one launch carries
    # all of it (its one row where the width is the limit or beyond)
    rows, width, takes = launches[0]
    assert takes == [budget] and width == 1 << (budget - 1).bit_length()
    assert rows == 1
    assert any(rows == 4 for rows, _, _ in launches)
    s = srv.stats
    assert s["prefill_tokens"] == sum(lens)
    assert s["prefill_rows"] == sum(r * w for r, w, _ in launches)


def test_a_wave_of_short_prompts_is_one_launch():
    """64 prompts of 16 tokens under a budget of 1,024 fill one launch of
    64 rows x 16, the shape the benchmark's widest activation wave warms:
    the launch runs, and its fill is 1."""
    model = _gpt()
    srv = ContinuousBatchingServer(
        model, max_slots=64, max_cache_len=32, cache_backend="paged",
        page_size=8, prefill_tokens_per_tick=1024, auto_prefix_cache=False)
    shapes = []
    launch = srv._ragged_fn

    def spy(*args):
        shapes.append(tuple(args[0].shape))
        return launch(*args)

    srv._ragged_fn = spy
    rng = np.random.default_rng(0)
    rids = [srv.submit(rng.integers(0, 256, 16).astype(np.int32),
                       max_new_tokens=2) for _ in range(64)]
    outs = srv.run()
    assert shapes == [(64, 16)]
    assert all(len(outs[r]) == 2 for r in rids)
    assert srv.stats["prefill_rows"] == srv.stats["prefill_tokens"] == 1024


def test_prefill_rows_are_counted_and_ride_the_span():
    """``prefill_rows`` sums rows x width over the launches, in
    ``srv.stats`` and the registry alike, and each launch's
    ``serve.prefill_wait`` span carries its own as ``launch_rows`` beside
    ``width`` and ``rows`` (the plan's live chunks)."""
    launches, srv = _plan_only(16, (16, 5, 3, 9, 2), telemetry=True)
    want = [r * w for r, w, _ in launches]
    assert len(want) > 1 and srv.stats["prefill_rows"] == sum(want)
    assert srv.stats["prefill_tokens"] == 35 < sum(want)
    spans = [ev["args"] for ev in srv.telemetry.tracer.events()
             if ev["name"] == "serve.prefill_wait"]
    assert [a["launch_rows"] for a in spans] == want
    assert [(a["width"], a["rows"]) for a in spans] == \
        [(w, len(takes)) for _, w, takes in launches]
    snap = srv.telemetry.registry.snapshot()
    (rows,) = snap["serving_prefill_rows_total"]["samples"].values()
    assert (rows["value"] if isinstance(rows, dict) else rows) == sum(want)
