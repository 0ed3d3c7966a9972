"""Packed prefill launches (``continuous_batching._LAUNCH_ROWS``): a ragged
launch has rows for the slots in its plan, row j being slot ``slots[j]``,
and as many of them as its row limit allows at its width (at most a row a
slot). Every family serves the same tokens whatever the limit, and a plan
that does not fit waits for the next launch."""
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


def _serve(model, limit, monkeypatch, lens=(20, 5, 13, 9, 17, 3), **kw):
    monkeypatch.setattr(cb, "_LAUNCH_ROWS", limit)
    srv = ContinuousBatchingServer(
        model, max_slots=4, max_cache_len=64, cache_backend="paged",
        page_size=8, prefill_tokens_per_tick=16, auto_prefix_cache=False,
        **kw)
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
def test_packed_launches_serve_the_same_tokens(build, monkeypatch):
    model = build()
    want, wide, _ = _serve(model, 4096, monkeypatch)
    got, narrow, srv = _serve(model, 16, monkeypatch)
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


def test_a_plan_that_does_not_fit_waits_for_the_next_launch(monkeypatch):
    """Four prompts of 4 tokens under a budget of 16 make one launch of
    four slot-chunks; at 8 rows of width 4 only two fit a launch."""
    model = _gpt()
    _, wide, a = _serve(model, 4096, monkeypatch, lens=(4, 4, 4, 4))
    _, narrow, b = _serve(model, 8, monkeypatch, lens=(4, 4, 4, 4))
    assert [s for s, _ in wide] == [(4, 4)]
    assert [s for s, _ in narrow] == [(2, 4), (2, 4)]
    assert a.stats["prefill_chunks"] == b.stats["prefill_chunks"] == 4
