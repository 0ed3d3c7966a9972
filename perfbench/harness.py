"""What every driver gets from the harness: the run's context (cell,
configuration, mix, seed, clocks), the set-up split by phase, the count of
executables JAX builds or loads, and the manifest's lookups by name."""
import contextlib
import importlib
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def say(msg=""):
    for line in str(msg).splitlines() or [""]:
        print(line, flush=True)


# ---------------------------------------------------------------- manifest
class UnknownName(KeyError):
    """A cell, configuration, mix, driver or reader that no file defines."""

    def __str__(self):
        return str(self.args[0])


def load_manifest(path=MANIFEST):
    with open(path) as f:
        return json.load(f)


def find_cell(manifest, name):
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise UnknownName(f"unknown workload {name!r}: BENCHMARK.json has "
                      f"{[c['name'] for c in manifest['workloads']]}")


def load_config(manifest, name):
    for entry in manifest["configs"]:
        if entry["name"] == name:
            path = os.path.join(ROOT, entry["file"])
            if not os.path.isfile(path):
                raise UnknownName(f"configuration {name!r}: no file {path}")
            with open(path) as f:
                return json.load(f)
    raise UnknownName(f"unknown configuration {name!r}")


def cell_metrics(manifest, section, cell_name):
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``) that
    this cell reports: those with no ``workloads`` key, or that list it."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_module(package, name):
    """``perfbench.<package>.<name>``, found by name. A metric
    ``copy_share.steady`` is read by ``copy_share``: the suffix after the
    first dot makes a variant of the entry, not new code."""
    stem = name.split(".", 1)[0]
    if not stem.replace("_", "").replace("-", "").isalnum():
        raise UnknownName(f"bad {package} name {name!r}")
    try:
        return importlib.import_module(f"perfbench.{package}.{stem}")
    except ModuleNotFoundError as e:
        if e.name != f"perfbench.{package}.{stem}":
            raise
        raise UnknownName(f"unknown {package} {name!r}: no file "
                          f"perfbench/{package}/{stem}.py") from None


def load_peaks(device_kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownName(f"no peaks for device kind {device_kind!r} in "
                          f"perfbench/peaks.json (has {sorted(table)}): a "
                          f"share of a guessed peak is no measurement")
    return table[device_kind]


# ------------------------------------------------------- compile accounting
class CompileWatch:
    """Every executable JAX builds or loads in this process (jit, AOT and
    eager alike), counted from JAX's own monitoring events, plus the
    persistent cache's hits and misses (chip_smoke.py's)."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (self.count, self.seconds)

    def since(self, mark):
        return self.count - mark[0], self.seconds - mark[1]


# ------------------------------------------------------------------ context
class Context:
    """One run. ``started`` is the process's start on ``perf_counter``."""

    def __init__(self, args, cell, config, mix, family, started):
        self.cell, self.config, self.mix, self.family = (cell, config, mix,
                                                         family)
        self.seed = int(args.seed) & (2 ** 48 - 1)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.chips = int(cell["chips"])
        self.started = started
        self.setup_s = None
        self.phases = []
        self.watch = CompileWatch()
        self.costs = None
        self.trace_dir = os.path.join(ROOT, ".perfbench_out",
                                      "trace-" + cell["name"])

    @contextlib.contextmanager
    def phase(self, name):
        """One phase of set-up: its seconds and the executables it built or
        loaded, printed and kept."""
        t0 = time.perf_counter()
        mark = self.watch.mark()
        yield
        n, cs = self.watch.since(mark)
        dt = time.perf_counter() - t0
        self.phases.append((name, dt, n, cs))
        say(f"set-up: {name:<16} {dt:8.2f}s  ({n} executables built or "
            f"loaded in {cs:.2f}s)")

    def window_opens(self, t0):
        self.setup_s = t0 - self.started
        known = sum(p[1] for p in self.phases)
        say(f"set-up: {self.setup_s:.2f}s from process start to the window, "
            f"{known:.2f}s of it in the phases above; compile cache "
            f"{self.watch.cache_hits} hits, {self.watch.cache_misses} misses")

    def annotate(self, name):
        """A span on the profiler's own clock; free when not tracing."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def runtime_peak_bytes(self):
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:self.chips]]
        return int(max(peaks))
