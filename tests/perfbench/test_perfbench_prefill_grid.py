"""``prefill_grid_live_share``: the per-layer metric that reads the ragged
prefill kernel's grid counters from ``srv.stats``. The reader on hand-made
observations gives the share, and ``None`` where the counters are missing (a
training run, a tree without them, a window without a prefill launch); the
rehearsed chat cell prints it, because the counters are the host's."""
import pytest

from perfbench import harness
from perfbench.layer_metrics import prefill_grid_live_share
from test_perfbench_rehearse import ROOT, rehearse

CELLS = ["gpt2-medium.chat-steady", "lfm2-24b-a2b.assist-steady"]
NAME = "prefill_grid_live_share"


def _grid(steps, live, **more):
    return dict(more, prefill_grid_steps=steps, prefill_live_steps=live)


@pytest.mark.parametrize("obs,want", [
    ({}, None),                                          # a training run
    ({"server_stats": None}, None),
    # a tree before the counters: server stats without the two of them
    ({"server_stats": {"start": {"prefill_chunks": 3},
                       "end": {"prefill_chunks": 90}}}, None),
    # ... or with one of them only
    ({"server_stats": {"start": {"prefill_grid_steps": 0},
                       "end": {"prefill_grid_steps": 9}}}, None),
    # a window without a launch through the kernel (key selection)
    ({"server_stats": {"start": _grid(480, 480), "end": _grid(480, 480)}},
     None),
    # warm-up left counts behind: only the window's difference counts
    ({"server_stats": {"start": _grid(90000, 50), "end": _grid(91176, 1226)}},
     100.0),
    # a launch with nothing live still takes its lone step a layer
    ({"server_stats": {"start": _grid(0, 0), "end": _grid(24 * 50, 24 * 49)}},
     98.0),
    # a static 16 x 32 x 64 sweep a layer with one prompt's 49 steps live
    ({"server_stats": {"start": _grid(0, 0), "end": _grid(32768, 49)}},
     100.0 * 49 / 32768),
], ids=["no-serving", "no-stats", "parent-tree", "half-a-tree",
        "no-prefill-launch", "tight-grid", "lone-steps", "static-sweep"])
def test_prefill_grid_live_share_reader(obs, want):
    """``None`` without its source, else the window's live steps over its
    grid steps, a share in (0, 100]."""
    got = prefill_grid_live_share.read(obs)
    assert got == (want if want is None else pytest.approx(want))


def test_manifest_entry_names_the_dense_serving_cells():
    """One entry, found by its name, in the two cells whose every request is
    prefilled by the kernel; both report the end-to-end metric it moves. A
    later cell may join its list, and a later entry may follow it."""
    manifest = harness.load_manifest()
    entry = {m["name"]: m for m in manifest["per_layer"]}[NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "ttft_p50_ms"}
    assert set(CELLS) <= set(entry["workloads"])
    (moved,) = [m for m in manifest["end_to_end"]
                if m["name"] == "ttft_p50_ms"]
    assert set(CELLS) <= set(moved["workloads"])
    for cell in CELLS:
        assert NAME in [m["name"] for m in harness.cell_metrics(
            manifest, "per_layer", cell)]
    assert harness.load_module("layer_metrics", NAME) \
        is prefill_grid_live_share


def test_rehearsed_chat_cell_prints_the_share():
    """The grid's counters need no chip: the traced rehearsal reports the
    share, and a flat grid's is far above the static sweep's percent."""
    line, _ = rehearse(ROOT, CELLS[0], 1)
    assert line["correct"] is True and line["compiles_in_window"] == 0
    got = line["metrics"][NAME]
    assert got["unit"] == "%"
    assert 90.0 <= got["value"] <= 100.0
