"""Lightweight host-side trace spans on an injectable clock.

``Tracer`` collects named spans. Export is Chrome-trace JSON
(``chrome://tracing`` / Perfetto "traceEvents" with complete 'X'
events), the same artifact family the profiler's jax trace lands in.

Two ways to open a span, and they differ in who may close it:

- ``span(name)`` is closed by the thread that opened it: a ``with``
  block, or the serving tick's phase boundary (which hands in its own
  clock reads: ``span(name, at=t0)`` ... ``end(at=t1)``). Such a span
  is MIRRORED into a ``jax.profiler.TraceAnnotation``, so it lands on
  the profiler's clock beside the device's operations whenever a
  profiling session is running (outside one the annotation costs a flag
  test).
- ``begin_span(name)`` may end on another thread (a request's *queued*
  span opens in ``submit()`` and closes on the serve thread). It is
  NEVER mirrored: a ``TraceAnnotation`` nests per thread, and one
  closed by a thread that did not open it corrupts that thread's stack.

Spans are host-side only: never open one inside jit-traced code (it
would measure trace time, then be baked out).

A disabled tracer returns a shared null span and performs NO clock
reads — the hot-path off switch mirrors ``MetricRegistry``.
"""
import collections
import json
import threading

from .clock import MonotonicClock

__all__ = ["Tracer", "Span", "NullSpan", "NULL_SPAN"]


class NullSpan:
    """No-op span (disabled tracer)."""

    __slots__ = ()

    def set(self, **args):
        return self

    def end(self, at=None, **args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = NullSpan()


class Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "_tid", "_mirror")

    def __init__(self, tracer, name, args, t0, tid, mirror):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = t0
        self._tid = tid
        self._mirror = mirror     # entered TraceAnnotation, or None

    def set(self, **args):
        """Attach/override span args before it ends."""
        self.args.update(args)
        return self

    def end(self, at=None, **args):
        """Close the span at ``at`` (a read of the tracer's clock the
        caller already made) or, without one, now."""
        if self._tracer is None:      # double end() is a no-op
            return
        if args:
            self.args.update(args)
        tracer, self._tracer = self._tracer, None
        if self._mirror is not None:
            self._mirror.__exit__(None, None, None)
        tracer._finish(self, tracer.clock.now() if at is None else at)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Tracer:
    """Bounded in-memory span collector.

    >>> tr = Tracer()
    >>> with tr.span("prefill", tokens=128):
    ...     ...
    >>> tr.export_chrome_trace("/tmp/trace.json")

    ``max_events`` bounds memory on long-running servers: the buffer
    keeps the NEWEST events, ``dropped`` counts the ones pushed out.
    """

    def __init__(self, clock=None, enabled=True, max_events=100_000):
        self.clock = clock if clock is not None else MonotonicClock()
        self.enabled = bool(enabled)
        self.max_events = int(max_events)
        self.dropped = 0
        self._lock = threading.Lock()
        self._events = collections.deque(maxlen=self.max_events)
        self._annotation = None
        if self.enabled:
            import jax
            self._annotation = jax.profiler.TraceAnnotation

    # ------------------------------------------------------------- spans
    def span(self, name, at=None, **args):
        """A span its opening thread will close; mirrored into the
        profiler. ``at``: the start, when the caller already read the
        tracer's clock."""
        if not self.enabled:
            return NULL_SPAN
        # a sequence would break the annotation's "k=v,k=v" encoding
        mirror = self._annotation(name, **{
            k: " ".join(map(str, v)) if isinstance(v, (list, tuple))
            else v for k, v in args.items()})
        mirror.__enter__()
        return Span(self, name, dict(args),
                    self.clock.now() if at is None else at,
                    threading.get_ident(), mirror)

    def begin_span(self, name, at=None, **args):
        """A span that may be ended from another thread: collected,
        never mirrored into the profiler. ``at``: the start, when the
        caller already read the tracer's clock."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, dict(args),
                    self.clock.now() if at is None else at,
                    threading.get_ident(), None)

    def _append(self, ev):
        with self._lock:
            if len(self._events) == self.max_events:
                self.dropped += 1
            self._events.append(ev)

    def _finish(self, span, t1):
        ev = {"name": span.name, "ph": "X", "pid": 0, "tid": span._tid,
              "ts": span._t0 * 1e6, "dur": (t1 - span._t0) * 1e6}
        if span.args:
            ev["args"] = span.args
        self._append(ev)

    def instant(self, name, **args):
        """Zero-duration marker event."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "pid": 0,
              "tid": threading.get_ident(), "ts": self.clock.now() * 1e6,
              "s": "t"}
        if args:
            ev["args"] = args
        self._append(ev)

    # ------------------------------------------------------------ export
    def events(self):
        with self._lock:
            return list(self._events)

    def export_chrome_trace(self, file):
        """Write Chrome-trace JSON; ``file`` is a path or file object.
        Returns the event count."""
        payload = {"traceEvents": self.events(),
                   "displayTimeUnit": "ms"}
        if hasattr(file, "write"):
            json.dump(payload, file)
        else:
            with open(file, "w") as f:
                json.dump(payload, f)
        return len(payload["traceEvents"])
