"""Device-cost ledger + compile watch: price every dispatch from the
compiled programs (ISSUE 13).

PR 10's goodput ledger attributes device TOKENS; nothing yet prices
them. Kernel work claims its wins in roofline terms — FLOPs and HBM
bytes — so this layer turns the serving stack's host->device dispatch
profile (PR 9's ``_count_dispatches(op=)`` labels) into a priced
ledger:

- **Cost catalog**: each jitted serving program is priced ONCE per
  (op, shape-signature) at compile time via the compiler's own numbers
  — ``fn.lower(*args).compile().cost_analysis()`` (reference
  ``Compiled.cost_analysis``; same ground truth as
  ``cost_model.xla_cost_analysis``, but here the catalog KEEPS the
  compiled executable and the server dispatches through it, so pricing
  never costs a duplicate compile). Every subsequent dispatch charges
  the entry's FLOPs + HBM bytes into ``server_flops_total{op}`` /
  ``server_hbm_bytes_total{op}``. Host<->device data movement that is
  not a compiled program (slot-state pushes, page gathers/scatters,
  block-table syncs) is priced as BYTES MOVED via ``charge_bytes`` —
  flops 0, documented per site.
- **Compile watch**: trace/lower/compile of each new signature is
  timed (``server_compiles_total{op}``, ``serving_compile_seconds``)
  and, once an OP has WARMED (``warm_after_ticks`` consecutive
  charged ticks without a compile of THAT op — warmup is per-op,
  ISSUE 14 satellite), any further compile of it is flagged a
  RECOMPILE — the server lands it as a flight-recorder event and a
  ``compile_stall`` journey phase on every request parked behind the
  stalled tick, so an XLA-induced latency spike is attributable
  instead of mystery. Per-op warmup keeps ops independent: the
  prefill program's pow2 width ladder (new chunk-width signatures
  while traffic shapes are still being explored) neither
  trips alarms for an op still climbing its own ladder nor holds the
  decode program's shape-leak watch hostage. ``warmed`` (the global
  view) is true once every compiled op has warmed.
- **Tick-phase attribution**: the server's one phase boundary
  (``telemetry.serving.TickBoundary``) splits each tick's wall into
  ``TICK_PHASES`` and feeds them in through ``add_phase``; they ride
  ``last_tick_phases`` and the recorder's per-tick events — the
  host-bound-vs-device-bound verdict (the ``*_wait`` phases are the
  chip's). The ``serving_tick_phase_seconds{phase}`` histogram is the
  server telemetry's, fed by the same reads. (``callbacks`` runs
  outside the server lock after the tick flushes, so it folds into the
  NEXT CHARGED tick's breakdown — carried across idle polls, a
  one-tick skew; only a drain's final tail of callbacks has no later
  tick to land in.)
- **MFU / roofline**: per charged tick, achieved FLOPs/s over
  ``peak_flops`` is published as the ``serving_mfu`` gauge (and
  ``roofline_ratio`` — the max of the FLOPs and HBM-bandwidth
  utilizations — rides ``snapshot()``). Peaks come from
  ``DEVICE_PEAKS`` (one table keyed by ``device_kind``, with its
  source) or are injected; a device with no row — the CPU — has no
  peak, so no utilisation is computed and no gauge is published
  there. ``serving_mfu`` merges across a fleet by MEAN on ``/fleet``
  (``exposition.merge_snapshots``), like ``*_ratio`` gauges.

Cost contract (mirrors ``FlightRecorder``/``GoodputLedger``):
``charge``/``charge_bytes``/``add_phase`` are plain dict bumps under
the server's own lock — no clock reads, no extra lock; ``program()``
reads the clock only when it actually compiles; ``flush_tick`` takes
one short catalog lock to fold the tick into cumulative totals
(cross-thread ``/stats`` reads). A DISABLED catalog
(``enabled=False``) is treated by the server exactly like ``None`` —
one attribute check on the tick path, zero locks, zero clock reads
(FakeClock + counting-lock asserted in tests).

Pricing is best-effort by construction: a function the catalog cannot
lower/compile (no ``.lower``, or an AOT failure) falls back to the raw
callable with a zero-cost entry and bumps ``price_errors`` — never the
compile watch (a pricing failure is not an XLA stall) — so the serving
path never depends on the profiler layer working. Known cut: the
DENSE-mode admission prefill rides ``model._run_prefill``'s internal
jit entries and is counted in the dispatch profile but not
compiled-priced (its wall still lands in the phase split, so
dense-mode MFU reads low); the ragged path — the paged default and
the one the benchmark's cells run — is fully priced.

Published surfaces: the metrics above, ``snapshot()`` under
``/stats["costs"]``, a ``costs`` postmortem section (with the last
tick's phase breakdown), and the per-replica ``mfu`` riding remote
heartbeat digests next to the goodput ratio.
"""
import threading

from .clock import MonotonicClock

__all__ = ["CostCatalog", "COMPILE_BUCKETS", "PHASE_BUCKETS",
           "TICK_PHASES", "DEVICE_PEAKS", "device_peaks"]

# compiles span ~10 ms (tiny CPU programs) to minutes (big TPU fusions)
COMPILE_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                   5.0, 10.0, 30.0, 60.0)
# a phase of a tick runs from tens of microseconds to a prefill launch
PHASE_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)
# The tick's phases separate the host from the chip. A program's time
# is two phases: *_dispatch, the host building and enqueueing the
# launch (to the return of the call), and *_wait, from there to its
# value read back on the host (a launch that completes no prompt reads
# nothing back: its prefill_wait is bookkeeping, blocked=0 on the
# span). The chip is idle through the rest, but for the three tiny
# programs of state_push. The dense admission path marks
# prefill_launch.
TICK_PHASES = ("expire", "admit", "prefill_pack", "prefill_dispatch",
               "prefill_wait", "activate", "grow", "state_push",
               "decode_dispatch", "decode_wait", "emit", "harvest",
               "callbacks", "prefill_launch")

# The one peaks table: ``device_kind`` (as ``jax.devices()[0]`` reports
# it) -> (bf16 FLOP/s, HBM bytes/s) of one chip. Every utilisation in
# the repo divides by a row of this table; a kind with no row has no
# utilisation. Source: Google Cloud documentation, "TPU v5e" (197
# TFLOP/s bf16, 819 GB/s HBM).
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}


def device_peaks(device_kind=None):
    """``(peak bf16 FLOP/s, peak HBM bytes/s)`` for ``device_kind``
    (default: the first JAX device's). An unknown kind raises — a
    measurement against a guessed peak is not a measurement."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no peak FLOP/s / HBM bandwidth on record for device kind "
            f"{device_kind!r}; add a sourced row to "
            f"telemetry.costs.DEVICE_PEAKS") from None


def _signature(args):
    """Hashable shape/dtype signature of a call's argument pytree —
    the compile-cache key XLA itself would miss on."""
    import jax
    sig = []
    for leaf in jax.tree_util.tree_leaves(args):
        shp = getattr(leaf, "shape", None)
        if shp is not None:
            sig.append((tuple(int(d) for d in shp),
                        str(getattr(leaf, "dtype", ""))))
        else:
            sig.append((type(leaf).__name__, repr(leaf)))
    return tuple(sig)


class _PricedProgram:
    """One cataloged (op, signature): the compiled executable plus its
    price. Calling it dispatches the program AND charges the entry's
    FLOPs/bytes to the current tick — dispatch and charge cannot
    drift. ``compiled_now``/``recompile`` tell the caller whether THIS
    lookup paid a compile (and whether it happened after warmup)."""

    __slots__ = ("op", "sig", "flops", "hbm_bytes", "compile_s",
                 "compiled_now", "recompile", "_fn", "_catalog")

    def __init__(self, catalog, op, sig, fn, flops, hbm_bytes,
                 compile_s):
        self._catalog = catalog
        self._fn = fn
        self.op = op
        self.sig = sig
        self.flops = flops
        self.hbm_bytes = hbm_bytes
        self.compile_s = compile_s
        self.compiled_now = False
        self.recompile = False

    def __call__(self, *args):
        out = self._fn(*args)
        self._catalog.charge(self)
        return out

    @property
    def executable(self):
        """What a dispatch runs: the compiled stage (``as_text``,
        ``memory_analysis``…), or the raw callable if pricing failed."""
        return self._fn


class CostCatalog:
    """Compiled-program cost catalog + compile watch + tick phases.

    >>> cat = CostCatalog(registry=tele.registry)
    >>> srv = ContinuousBatchingServer(model, ..., costs=cat)
    >>> srv.run()
    >>> cat.snapshot()["ops"]["decode"]["flops"]
    >>> cat.recompiles                       # 0 after warmup, or else
    """

    def __init__(self, registry=None, clock=None, enabled=True,
                 peak_flops=None, peak_hbm_bytes_per_s=None,
                 warm_after_ticks=2):
        self.enabled = bool(enabled)
        self.clock = clock if clock is not None else MonotonicClock()
        if peak_flops is None or peak_hbm_bytes_per_s is None:
            import jax
            row = DEVICE_PEAKS.get(jax.devices()[0].device_kind,
                                   (None, None))
            if peak_flops is None:
                peak_flops = row[0]
            if peak_hbm_bytes_per_s is None:
                peak_hbm_bytes_per_s = row[1]
        # None = this device has no row: nothing to divide by, so no
        # MFU / roofline is computed and no gauge is registered
        self.peak_flops = None if peak_flops is None else float(peak_flops)
        self.peak_hbm_bytes_per_s = (
            None if peak_hbm_bytes_per_s is None
            else float(peak_hbm_bytes_per_s))
        for peak in (self.peak_flops, self.peak_hbm_bytes_per_s):
            if peak is not None and peak <= 0:
                raise ValueError("peak_flops / peak_hbm_bytes_per_s "
                                 "must be > 0")
        self._has_peaks = (self.peak_flops is not None
                           and self.peak_hbm_bytes_per_s is not None)
        self._warm_after = int(warm_after_ticks)
        self._lock = threading.Lock()
        self._programs = {}       # (op, sig) -> _PricedProgram
        self._tick = {}           # op -> [flops, bytes, dispatches]
        self._phases = {}         # phase -> seconds (current tick)
        self._totals = {}         # op -> [flops, bytes, dispatches]
        self._compiles = {}       # op -> count
        self._compile_s_total = 0.0
        self._ticks = 0
        # PER-OP compile watch (ISSUE 14 satellite): each op warms
        # after warm_after_ticks consecutive charged ticks without a
        # compile of THAT op, independently of the others' ladders
        self._quiet = {}          # op -> charged ticks since its compile
        self._warm = set()        # ops whose recompile alarm is armed
        self._compiled_ops = set()   # ops compiled since the last flush
        self.recompiles = 0
        self.price_errors = 0
        self._last_phases = {}
        self._last_mfu = None
        self._last_roofline = None
        self._c_flops = self._c_bytes = self._c_compiles = None
        self._h_compile = self._g_mfu = None
        self._flops_children = {}
        self._bytes_children = {}
        self._compile_children = {}
        if (self.enabled and registry is not None
                and getattr(registry, "enabled", False)):
            self._c_flops = registry.counter(
                "server_flops_total",
                "Device FLOPs charged per dispatch from the compiled "
                "programs' cost analysis, by op", labelnames=("op",))
            self._c_bytes = registry.counter(
                "server_hbm_bytes_total",
                "Device HBM bytes charged per dispatch (compiled-"
                "program cost analysis for programs, bytes-moved "
                "model for transfers), by op", labelnames=("op",))
            self._c_compiles = registry.counter(
                "server_compiles_total",
                "trace/lower/compile events per op — growth after "
                "warmup means a shape-signature leak is recompiling "
                "mid-serving", labelnames=("op",))
            self._h_compile = registry.histogram(
                "serving_compile_seconds",
                "Wall seconds per trace/lower/compile of one serving "
                "program", buckets=COMPILE_BUCKETS)
            if self._has_peaks:
                self._g_mfu = registry.gauge(
                    "serving_mfu",
                    "Achieved FLOP/s over peak_flops for the last "
                    "charged tick (merged by MEAN on /fleet, like "
                    "*_ratio gauges)")

    # --------------------------------------------------------- pricing
    def program(self, op, fn, args):
        """The priced executable for ``fn`` at ``args``' shape
        signature. First sight of (op, signature) pays ONE
        lower+compile (timed, priced via ``cost_analysis``); repeats
        are a dict hit. The returned ``_PricedProgram`` is called in
        place of ``fn`` — same HLO, same executable the jit cache
        would build, so tokens stay bit-identical."""
        if not self.enabled:
            return fn
        key = (op, _signature(args))
        prog = self._programs.get(key)
        if prog is not None:
            prog.compiled_now = False
            return prog
        t0 = self.clock.now()
        flops = hbm = 0.0
        priced = True
        try:
            compiled = fn.lower(*args).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            ca = ca or {}
            flops = float(ca.get("flops", 0.0) or 0.0)
            hbm = float(ca.get("bytes accessed", 0.0) or 0.0)
            run = compiled
        except Exception:
            # pricing must never break serving: fall back to the raw
            # callable with a zero-cost entry. A pricing FAILURE is not
            # a compile — it must not feed the compile watch, and above
            # all must not raise a false recompile/compile_stall alarm
            # after warmup (there was no XLA stall to attribute)
            self.price_errors += 1
            priced = False
            run = fn
        dt = self.clock.now() - t0
        prog = _PricedProgram(self, op, key[1], run, flops, hbm, dt)
        prog.compiled_now = priced
        # per-op alarm: only a compile of an op whose OWN watch armed
        # (warm_after_ticks charged ticks without one) is a recompile —
        # another op's ladder climb neither arms nor trips this one
        prog.recompile = priced and op in self._warm
        self._programs[key] = prog
        if priced:
            with self._lock:
                self._compiled_ops.add(op)
                self._compiles[op] = self._compiles.get(op, 0) + 1
                self._compile_s_total += dt
                if prog.recompile:
                    self.recompiles += 1
            if self._c_compiles is not None:
                child = self._compile_children.get(op)
                if child is None:
                    child = self._compile_children[op] = \
                        self._c_compiles.labels(op=op)
                child.inc()
                self._h_compile.observe(dt)
        return prog

    # -------------------------------------------------------- charging
    def charge(self, prog, n=1):
        """Charge ``n`` dispatches of a cataloged program to the
        current tick. Dict bump only — callers already hold the server
        lock (single writer per catalog), no clock reads."""
        cell = self._tick.get(prog.op)
        if cell is None:
            cell = self._tick[prog.op] = [0.0, 0.0, 0]
        cell[0] += prog.flops * n
        cell[1] += prog.hbm_bytes * n
        cell[2] += n

    def charge_bytes(self, op, nbytes, n=1):
        """Charge a host<->device transfer that is not a compiled
        program (slot-state push, page gather/scatter, block-table
        sync): bytes moved, zero FLOPs. The byte count is the
        caller's model of the movement (documented per site)."""
        cell = self._tick.get(op)
        if cell is None:
            cell = self._tick[op] = [0.0, 0.0, 0]
        cell[1] += float(nbytes) * n
        cell[2] += n

    # ---------------------------------------------------------- phases
    def add_phase(self, phase, seconds):
        """``seconds`` of the current tick spent in ``phase`` (the
        server's ``TickBoundary`` calls this with its own reads;
        phases may repeat and accumulate)."""
        if seconds > 0:
            self._phases[phase] = self._phases.get(phase, 0.0) + seconds

    def pending_phases(self):
        """The current (unflushed) tick's phase split — what the
        recorder embeds in its per-tick event."""
        return dict(self._phases)

    # ----------------------------------------------------------- flush
    def flush_tick(self):
        """Fold the tick's charges + phases into cumulative totals,
        publish metrics, and advance the compile watch's PER-OP
        warmup: a charged tick is quiet FOR AN OP when that op did not
        compile in it; ``warm_after_ticks`` consecutive quiet ticks
        arm that op's recompile detection (warmth is sticky — a later
        ladder climb alarms, which is the attribution the watch
        exists to give, but never arms or trips another op's watch).
        Returns the tick's ``{op: (flops, bytes, dispatches)}``, or
        None when nothing was charged — an idle serve-loop poll,
        whose phase scraps are DISCARDED (microsecond "ticks" at the
        poll rate are no tick's split)."""
        tick, self._tick = self._tick, {}
        phases, self._phases = self._phases, {}
        if not tick:
            # idle serve-loop poll: its expire/admit scraps
            # are discarded, but pending callbacks time (the one
            # phase generated OUTSIDE a tick) is carried forward so a
            # request-sparse loop doesn't systematically drop it — it
            # folds into the next CHARGED tick
            cb = phases.get("callbacks")
            if cb:
                self._phases["callbacks"] = cb
            return None
        elapsed = sum(phases.values())
        tick_flops = sum(c[0] for c in tick.values())
        tick_bytes = sum(c[1] for c in tick.values())
        mfu = roofline = None
        if elapsed > 0 and self._has_peaks:
            mfu = (tick_flops / elapsed) / self.peak_flops
            roofline = max(mfu, (tick_bytes / elapsed)
                           / self.peak_hbm_bytes_per_s)
        with self._lock:
            for op, cell in tick.items():
                tot = self._totals.get(op)
                if tot is None:
                    tot = self._totals[op] = [0.0, 0.0, 0]
                tot[0] += cell[0]
                tot[1] += cell[1]
                tot[2] += cell[2]
            if phases:
                self._last_phases = phases
            self._ticks += 1
            # advance every ever-compiled op's watch: compiled this
            # flush -> its quiet run restarts; otherwise one more
            # quiet charged tick toward (or past) its warm threshold
            for op in self._compiles:
                if op in self._compiled_ops:
                    self._quiet[op] = 0
                else:
                    self._quiet[op] = self._quiet.get(op, 0) + 1
                    if self._quiet[op] >= self._warm_after:
                        self._warm.add(op)
            self._compiled_ops.clear()
            if mfu is not None:
                self._last_mfu = mfu
                self._last_roofline = roofline
        if self._c_flops is not None:
            for op, cell in tick.items():
                if cell[0]:
                    child = self._flops_children.get(op)
                    if child is None:
                        child = self._flops_children[op] = \
                            self._c_flops.labels(op=op)
                    child.inc(cell[0])
                if cell[1]:
                    child = self._bytes_children.get(op)
                    if child is None:
                        child = self._bytes_children[op] = \
                            self._c_bytes.labels(op=op)
                    child.inc(cell[1])
            if mfu is not None:
                self._g_mfu.set(mfu)
        return tick or None

    # ------------------------------------------------------------ read
    @property
    def warmed(self):
        """Global warm view: every op that has ever compiled has
        finished its own ``warm_after_ticks`` quiet run. (Per-op warm
        state drives the recompile alarms; see ``warm_ops`` in
        ``snapshot()``.)"""
        with self._lock:
            return bool(self._compiles) \
                and all(op in self._warm for op in self._compiles)

    def warmed_op(self, op):
        """Whether ``op``'s own recompile alarm is armed."""
        return op in self._warm

    def mfu(self):
        """The last charged tick's model-FLOPs utilization (achieved
        FLOP/s over ``peak_flops``), or None before any charged tick
        and on a device without a peaks row — rides remote heartbeat
        digests for routing-side views."""
        return self._last_mfu

    def totals(self):
        """Cumulative ``{op: {"flops", "hbm_bytes", "dispatches"}}``."""
        with self._lock:
            return {op: {"flops": c[0], "hbm_bytes": c[1],
                         "dispatches": c[2]}
                    for op, c in self._totals.items()}

    @property
    def ticks(self):
        return self._ticks

    def programs(self):
        """Every cataloged program, ``[(op, priced program), ...]`` by
        op — for callers that inspect what was actually compiled."""
        return sorted(((op, prog) for (op, _), prog
                       in list(self._programs.items())),
                      key=lambda t: t[0])

    def compiles(self):
        """Cumulative compile counts by op."""
        with self._lock:
            return dict(self._compiles)

    def snapshot(self):
        """JSON-ready summary — the ``/stats["costs"]`` payload and the
        ``costs`` postmortem section (per-op totals, compile counts,
        warmup/recompile state, MFU/roofline, and the LAST tick's
        phase breakdown — "was it host-bound" without a live server)."""
        with self._lock:
            return {
                "ops": {op: {"flops": c[0], "hbm_bytes": c[1],
                             "dispatches": c[2]}
                        for op, c in self._totals.items()},
                "compiles": dict(self._compiles),
                "compile_seconds": self._compile_s_total,
                "cataloged_programs": len(self._programs),
                "recompiles": self.recompiles,
                "warmed": bool(self._compiles) and all(
                    op in self._warm for op in self._compiles),
                "warm_ops": sorted(self._warm),
                "price_errors": self.price_errors,
                "ticks": self._ticks,
                "mfu": self._last_mfu,
                "roofline_ratio": self._last_roofline,
                "peak_flops": self.peak_flops,
                "peak_hbm_bytes_per_s": self.peak_hbm_bytes_per_s,
                "last_tick_phases": dict(self._last_phases),
            }
