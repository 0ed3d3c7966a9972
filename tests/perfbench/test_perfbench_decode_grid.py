"""``decode_grid_live_share``: the per-layer metric that reads the paged
decode kernel's grid counters from ``srv.stats``. The reader on hand-made
observations gives the share, and ``None`` where the counters are missing (a
training run, a tree without them, a window without a decode tick); the
rehearsed chat cell prints it, because the counters are the host's."""
import pytest

from perfbench import harness
from perfbench.layer_metrics import decode_grid_live_share
from test_perfbench_rehearse import ROOT, rehearse

CELL = "gpt2-medium.chat-steady"
NAME = "decode_grid_live_share"


def _grid(steps, live, **more):
    return dict(more, decode_grid_steps=steps, decode_live_pages=live)


@pytest.mark.parametrize("obs,want", [
    ({}, None),                                          # a training run
    ({"server_stats": None}, None),
    # a tree before the counters: server stats without the two of them
    ({"server_stats": {"start": {"decode_ticks": 3},
                       "end": {"decode_ticks": 90}}}, None),
    # a window without a decode tick through the kernel
    ({"server_stats": {"start": _grid(480, 480), "end": _grid(480, 480)}},
     None),
    # warm-up left counts behind: only the window's difference counts
    ({"server_stats": {"start": _grid(4096, 50), "end": _grid(4696, 650)}},
     100.0),
    # a static 32 x 64 sweep with 25 pages live a layer
    ({"server_stats": {"start": _grid(0, 0), "end": _grid(2048, 25)}},
     100.0 * 25 / 2048),
], ids=["no-serving", "no-stats", "parent-tree", "no-decode-tick",
        "tight-grid", "static-sweep"])
def test_decode_grid_live_share_reader(obs, want):
    """``None`` without its source, else the window's live pages over its
    grid steps, a share in (0, 100]."""
    got = decode_grid_live_share.read(obs)
    assert got == (want if want is None else pytest.approx(want))


def test_manifest_entry_names_the_chat_cell():
    entry = {m["name"]: m for m in harness.load_manifest()["per_layer"]}[NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "itl_p95_ms", "workloads": [CELL]}


def test_rehearsed_chat_cell_prints_the_share():
    """The grid's counters need no chip: the traced rehearsal reports the
    share, and a flat grid's share is above the static sweep's percent."""
    line, _ = rehearse(ROOT, CELL, 1)
    assert line["correct"] is True and line["compiles_in_window"] == 0
    got = line["metrics"][NAME]
    assert got["unit"] == "%"
    assert 0.0 < got["value"] <= 100.0
