"""The trace reduction on hand-made rows with nesting: a known idle share,
copy share and mosaic share."""
import pytest

from perfbench import trace_reduce as tr
from perfbench.layer_metrics import copy_share, device_idle, mosaic_share

DEV, OPS, MODS = "/device:TPU:0", tr.OPS_LINE, tr.MODULES_LINE
US = 1000.0

WHILE = ("%while.29 = (s32[], bf16[32,1,1024]{2,1,0:T(8,128)(2,1)}) "
         "while((s32[], bf16[32,1,1024]{2,1,0}) %tuple.3), "
         "condition=%cond, body=%body")
COPY = ("%copy.41 = bf16[1,2049,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[1,2049,16,16,64]{1,4,3,2,0:T(8,128)(2,1)} %p)")
DUS_FUSION = ("%constant_dynamic-update-slice_fusion.62 = "
              "bf16[32,128,16,64]{3,2,1,0:T(8,128)(2,1)} "
              "fusion(bf16[32,128,16,64]{3,2,1,0} %a), kind=kLoop")
DS = ("%dynamic-slice.7 = bf16[1,8]{1,0} dynamic-slice(bf16[24,8]{1,0} %x, "
      "s32[] %i, s32[] %z)")
KERNEL = ("%closed_call.189 = bf16[32,8,16,64]{3,2,1,0:T(8,128)(2,1)S(1)} "
          "custom-call(s32[2048]{0:T(1024)S(1)} %copy-done.12), "
          "custom_call_target=\"tpu_custom_call\"")
MATMUL = ("%add_bitcast_fusion.3 = bf16[32,4096]{1,0:T(8,128)(2,1)} "
          "fusion(bf16[32,1024]{1,0} %h, bf16[1024,4096]{1,0} %w)")
COPY_START = "%copy-start.2 = (s32[8]{0}, s32[8]{0}, u32[]) copy-start(%q)"


def rows():
    """A 1000 us window. Device: a while of 600 us holding a copy (100), a
    kernel (200) and a matmul fusion (150), so 150 of its own; then outside
    it a slice fusion (100) and a dynamic-slice (50). Busy 750, idle 250."""
    ev = [
        (WHILE, 100, 600), (COPY, 120, 100), (KERNEL, 250, 200),
        (MATMUL, 480, 150),
        (DUS_FUSION, 750, 100), (DS, 850, 50),
        (COPY, 2000, 500),                       # outside the window
    ]
    out = [(DEV, OPS, n, s * US, d * US) for n, s, d in ev]
    out += [(DEV, MODS, "jit_run(123)", 100 * US, 600 * US),
            (DEV, MODS, "jit_run(456)", 750 * US, 150 * US),
            (DEV, MODS, "jit_scatter(9)", 905 * US, 1 * US)]
    out += [("/host:CPU", "python3", tr.WINDOW_MARK, 0.0, 1000 * US),
            ("/host:CPU", "python3", "perfbench.submit", 0.0, 90 * US),
            ("/host:CPU", "python3", "np.asarray(jax.Array)", 10 * US,
             60 * US),
            ("/host:CPU", "python3", "PjitFunction(scatter)", 905 * US,
             90 * US)]
    return out


@pytest.mark.parametrize("name,op,cls", [
    (WHILE, "while", "other"), (COPY, "copy", "copy"),
    (DUS_FUSION, "fusion", "copy"), (DS, "dynamic-slice", "copy"),
    (KERNEL, "custom-call", "mosaic"), (MATMUL, "fusion", "other"),
    (COPY_START, "copy-start", "other"),
    ("%dynamic-update-slice.4 = f32[8]{0} dynamic-update-slice(f32[8]{0} "
     "%a, f32[1]{0} %b, s32[] %i)", "dynamic-update-slice", "copy"),
    ("not an instruction", "not an instruction", "other"),
])
def test_classes_go_by_opcode(name, op, cls):
    assert tr.opcode(name) == op
    assert tr.op_class(name) == cls


def test_classification_ignores_suffixes_and_shapes():
    a = tr.op_class(COPY)
    b = tr.op_class(COPY.replace("%copy.41", "%copy.977")
                    .replace("2049", "513"))
    assert a == b == "copy"
    assert tr.instruction_words(DUS_FUSION) == {
        "constant", "dynamic-update-slice", "fusion"}


def test_self_time_subtracts_direct_children_only():
    ev = [("outer", 0.0, 100.0), ("mid", 10.0, 50.0), ("leaf", 20.0, 10.0),
          ("sib", 70.0, 20.0)]
    got = dict(tr.self_times(ev))
    assert got == {"outer": 30.0, "mid": 40.0, "leaf": 10.0, "sib": 20.0}


def test_busy_is_the_union_not_the_sum():
    ev = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0)]
    assert tr.busy_intervals(ev) == [(0.0, 15.0), (30.0, 35.0)]


def test_known_shares():
    red = tr.reduce_rows(rows())
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(1000e-6)
    assert red["busy_s"] == pytest.approx(750e-6)
    assert red["self_s"]["copy"] == pytest.approx(250e-6)
    assert red["self_s"]["mosaic"] == pytest.approx(200e-6)
    assert red["self_s"]["other"] == pytest.approx(300e-6)   # while 150 + mm
    obs = {"trace": red}
    assert device_idle.read(obs) == pytest.approx(25.0)
    assert copy_share.read(obs) == pytest.approx(100 * 250 / 750)
    assert mosaic_share.read(obs) == pytest.approx(100 * 200 / 750)
    # shares of self time add up to the busy time
    assert sum(red["self_s"].values()) == pytest.approx(red["busy_s"])


def test_breakdown_names_and_gaps():
    red = tr.reduce_rows(rows())
    names = [n for n, _ in red["device_ops"]]
    assert names[0].startswith("%closed_call.189")        # 200 us of self
    assert all(len(n) <= tr.NAME_CUT for n in names)
    assert dict(red["device_ops"])[names[0]] == pytest.approx(200e-6)
    gaps = dict(red["idle_gaps"])
    # [0,100): the asarray span is the shortest that covers half of it;
    # [700,750): no host event, the default; [900,1000): the scatter
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(100e-6)
    assert gaps["server thread"] == pytest.approx(50e-6)
    assert gaps["PjitFunction(scatter)"] == pytest.approx(100e-6)
    assert sum(gaps.values()) == pytest.approx(250e-6)


def test_modules_by_name_without_the_hash():
    red = tr.reduce_rows(rows())
    assert red["modules"]["jit_run"]["runs"] == 2
    assert red["modules"]["jit_run"]["median_s"] == pytest.approx(375e-6)
    name, st = tr.main_module(red)
    assert name == "jit_run" and st["total_s"] == pytest.approx(750e-6)


def test_short_gaps_are_pooled():
    ev = [(DEV, OPS, MATMUL, 0.0, 500.0), (DEV, OPS, MATMUL, 505.0, 495.0),
          ("/host:CPU", "python3", tr.WINDOW_MARK, 0.0, 1000.0)]
    red = tr.reduce_rows(ev)
    assert dict(red["idle_gaps"]) == {tr.SHORT_GAP_LABEL: pytest.approx(5e-9)}


def test_two_chips_are_averaged():
    two = rows() + [("/device:TPU:1", OPS, MATMUL, 0.0, 250 * US)]
    red = tr.reduce_rows(two)
    assert red["chips"] == 2
    assert red["busy_s"] == pytest.approx((750e-6 + 250e-6) / 2)


def test_nothing_on_the_device_reads_as_nothing():
    host_only = [r for r in rows() if not r[0].startswith("/device")]
    assert tr.reduce_rows(host_only) is None
    assert copy_share.read({"trace": None}) is None
    assert device_idle.read({}) is None


def test_without_a_mark_the_window_is_the_device_events_span():
    unmarked = [r for r in rows() if r[2] != tr.WINDOW_MARK]
    assert tr.window_of(unmarked) == (100 * US, 2500 * US)
