"""From a profiler trace to plain rows, and from rows to numbers.

The only place that touches the ``.xplane.pb`` is ``load_rows``; all the
arithmetic below it works on ``(plane, line, name, start_ns, duration_ns)``
tuples, so the tests feed it hand-made rows.

What the arithmetic knows about a TPU trace (seen in PR 23's v5e trace):

- a device is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
  one event per executed HLO instruction, its ``XLA Modules`` line one per
  executed program;
- events on ``XLA Ops`` NEST: a ``while`` holds its body's instructions. So
  busy time is the UNION of the intervals, and a share is of SELF time, an
  event's duration less the events directly inside it;
- an event's name is the printed HLO instruction,
  ``%name.N = type opcode(operands...)``. Classes go by the opcode (and, for
  a fusion, by the words of its name), never by a hash, a number suffix or a
  shape.
"""
import glob
import os
import re
import statistics

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "perfbench.window"        # the harness's own TraceAnnotation
NAME_CUT = 120
# a gap shorter than this is the device's own turn-around between two
# instructions, not the host's doing: such gaps are summed under one label
SHORT_GAP_NS = 10_000.0
SHORT_GAP_LABEL = "between operations (gaps under 10 us)"

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_COPY_OPS = ("copy", "dynamic-slice", "dynamic-update-slice")
_WORD_SPLIT = re.compile(r"[_.%]")


# ----------------------------------------------------------------- loading
def find_trace(trace_dir):
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` output directory,
    or None."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load_rows(path):
    """Every event of every line of every plane, as plain tuples."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns)))
    return rows


# ---------------------------------------------------------- classification
def opcode(name):
    """``%copy.3 = bf16[8]{0} copy(bf16[8]{0} %p)`` -> ``copy``. A name that
    is no printed instruction is its own opcode."""
    rhs = name.split(" = ", 1)[1] if " = " in name else name
    m = _OPCODE.search(" " + rhs)
    return m.group(1) if m else name


def instruction_words(name):
    """The words of the instruction's own name, number suffix dropped:
    ``%constant_dynamic-update-slice_fusion.62`` -> {constant,
    dynamic-update-slice, fusion}."""
    inst = name.split(" = ", 1)[0]
    return {w for w in _WORD_SPLIT.split(inst) if w and not w.isdigit()}


def op_class(name):
    """``copy`` (layout copies and slice traffic, fusions of them included),
    ``mosaic`` (custom calls: the Pallas kernels) or ``other``."""
    op = opcode(name)
    if op in _COPY_OPS:
        return "copy"
    if op == "custom-call":
        return "mosaic"
    if op == "fusion" and instruction_words(name) & set(_COPY_OPS):
        return "copy"
    return "other"


# --------------------------------------------------------------- intervals
def clip(events, lo, hi):
    """Events cut to ``[lo, hi]``; those outside are dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def busy_intervals(events):
    """The union of the events' intervals, as a sorted list of (lo, hi)."""
    merged = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(lo, hi) for lo, hi in merged]


def self_times(events):
    """[(name, self_ns)] for events of ONE line: duration less the events
    directly inside. Relies on proper nesting, which one device line has."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[name, dur] for name, _, dur in order]
    stack = []                                   # indices of open events
    for i, (_, start, dur) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= dur
        stack.append(i)
    return [(name, max(0.0, s)) for name, s in out]


# --------------------------------------------------------------- reduction
def device_planes(rows):
    return sorted({r[0] for r in rows if _DEVICE.match(r[0])})


def window_of(rows):
    """(lo, hi) of the harness's window annotation; without one, the span
    of the device events."""
    marks = [(s, s + d) for _, _, n, s, d in rows if n == WINDOW_MARK]
    if marks:
        return max(marks, key=lambda m: m[1] - m[0])
    dev = [(s, s + d) for p, line, _, s, d in rows
           if _DEVICE.match(p) and line == OPS_LINE]
    if not dev:
        return None
    return min(s for s, _ in dev), max(e for _, e in dev)


def _host_label(host_events, lo, hi, default):
    """What the host was doing in the gap ``[lo, hi]``: the SHORTEST host
    event that covers at least half of it (the most specific one), else the
    one that covers most of it."""
    half = (hi - lo) / 2.0
    best, best_key = default, None
    for name, start, dur in host_events:
        cover = min(hi, start + dur) - max(lo, start)
        if cover <= 0:
            continue
        key = (0, dur) if cover >= half else (1, -cover)
        if best_key is None or key < best_key:
            best, best_key = name, key
    return best[:NAME_CUT]


def reduce_rows(rows, idle_default="server thread"):
    """The traced window in numbers, or None where no device operation ran.

    ``busy_s`` and ``window_s`` (busy averaged over the chips), ``self_s`` by
    class (``copy``, ``mosaic``, ``other``), ``device_ops`` (the ten
    instructions with most self time) and ``idle_gaps`` (idle seconds by what
    the host was doing), ``modules`` (per program name, hash dropped:
    runs, total and median seconds)."""
    planes = device_planes(rows)
    win = window_of(rows)
    if not planes or win is None:
        return None
    lo, hi = win
    host = [(n, s, d) for p, _, n, s, d in rows
            if p.startswith("/host:") and n != WINDOW_MARK and d > 0]
    host = clip(host, lo, hi)
    busy, by_class, by_name, gaps, modules = 0.0, {}, {}, {}, {}
    for plane in planes:
        ops = clip([(n, s, d) for p, line, n, s, d in rows
                    if p == plane and line == OPS_LINE], lo, hi)
        merged = busy_intervals(ops)
        busy += sum(e - s for s, e in merged)
        for name, self_ns in self_times(ops):
            cls = op_class(name)
            by_class[cls] = by_class.get(cls, 0.0) + self_ns
            by_name[name] = by_name.get(name, 0.0) + self_ns
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g_lo, g_hi in zip(edges[0::2], edges[1::2]):
            if g_hi <= g_lo:
                continue
            label = (SHORT_GAP_LABEL if g_hi - g_lo < SHORT_GAP_NS
                     else _host_label(host, g_lo, g_hi, idle_default))
            gaps[label] = gaps.get(label, 0.0) + (g_hi - g_lo)
        for p, line, n, s, d in rows:
            if p == plane and line == MODULES_LINE and lo <= s <= hi:
                modules.setdefault(re.sub(r"\(\d+\)$", "", n), []).append(d)
    if busy <= 0:
        return None
    n = len(planes)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "chips": n,
        "busy_s": busy / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "self_s": {k: v / n / 1e9 for k, v in by_class.items()},
        "device_ops": [[name[:NAME_CUT], s / n / 1e9] for name, s in top],
        "idle_gaps": [[name, s / n / 1e9] for name, s in idle],
        "modules": {
            name: {"runs": len(ds), "total_s": sum(ds) / 1e9,
                   "median_s": statistics.median(ds) / 1e9}
            for name, ds in modules.items()},
    }


def share_of_busy(reduced, cls):
    """Percent of the device's busy time that is self time of ``cls``."""
    return 100.0 * reduced["self_s"].get(cls, 0.0) / reduced["busy_s"]


def idle_share(reduced):
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def main_module(reduced):
    """(name, stats) of the program that took most device time."""
    if not reduced["modules"]:
        return None
    return max(reduced["modules"].items(), key=lambda kv: kv[1]["total_s"])
