"""Goodput ledger: attribute every device token to useful work or a
named waste reason (ISSUE 11).

PR 9 answered "what happened to request X"; nothing yet answered "how
much of the hardware's work is USEFUL?". The decode program steps every
slot every tick whether or not the slot holds live work, the ragged
prefill pads chunk widths up a pow2 ladder, the paged kernels DMA pages
they then mask out, and a preemption replays its whole chain from token
0 — waste that was previously scattered across two ad-hoc counters
(``kv_null_redirected_writes_total``,
``serving_wasted_block_tokens_total``) or not measured at all. The
ROADMAP's kernel work (the live-page grid A3, the quantized pool A7,
multi-token steps B6) claims wins in exactly these categories, so this
ledger is the baseline those PRs are judged against.

Taxonomy — every device token each tick lands in EXACTLY ONE kind:

- ``goodput``          committed prefill rows (fresh prompt tokens
                       written once) and committed decode rows
- ``null_redirect``    decode rows of slots holding no live decode work
                       (empty slots, and mid-prefill slots parked past
                       the block table so their writes null-redirect —
                       the dense backend drops them out of bounds, same
                       waste class)
- ``chunk_pad``        prefill rows padded past the real chunk: the
                       ragged pow2 ladder (PR 6) and the dense
                       ``prefill_chunk`` remainder pad
- ``skipped_page_dma`` page tokens the ragged-prefill kernel and the
                       decode step's XLA fallback read but mask: they
                       cover the full block-table width per slot, so
                       pages wholly beyond a slot's live length still
                       cost a read (PR 6 known cut; counted for LIVE
                       slots only — an idle slot's whole ride is
                       already ``null_redirect``; the decode KERNEL's
                       grid holds live pages only, so on the chip the
                       decode share of this kind is a model of the
                       fallback)
- ``replay``           preemption recompute (PR 8 known cut): prompt
                       re-prefill rows of a resumed request, and decode
                       rows re-generating tokens its waiter was already
                       streamed
- ``tail_reprefill``   sub-page tails of registered prefixes the ragged
                       path re-prefills (page-granular tree matching,
                       PR 6 stats-contract change)
- ``block_waste``      decode rows a ``tick_block > 1`` program runs
                       past a slot's finish (amortization cost,
                       previously ``serving_wasted_block_tokens_total``)

The conservation law (test-asserted): within one tick, the kinds sum
exactly to the tick's total device tokens — decode rows
(``slots x tick_block``) + prefill launch rows (participating slots x
padded chunk width, or the dense segment + pad) + masked page DMAs
(token-equivalents). ``register_prefix`` prefill is operator setup, not
serving work, and stays OFF the ledger.

Cost contract (mirrors ``FlightRecorder``): ``add`` is a plain dict
bump under the server's own lock — no clock reads ever, no extra lock;
``flush_tick`` takes one short ledger lock to fold the tick into the
cumulative totals (cross-thread ``/stats`` reads). A DISABLED ledger
(``enabled=False``) is treated by the server exactly like ``None`` —
one attribute check on the hot path, zero locks, zero clock reads.

Published surfaces: ``server_tokens_total{kind}`` counter and the
per-tick ``serving_goodput_ratio`` gauge (when a registry is wired),
``snapshot()`` under ``/stats["goodput"]``, and a ``goodput`` section
in postmortem bundles.
"""
import threading

__all__ = ["GoodputLedger", "WASTE_KINDS", "TOKEN_KINDS"]

WASTE_KINDS = ("null_redirect", "chunk_pad", "skipped_page_dma",
               "replay", "tail_reprefill", "block_waste")
TOKEN_KINDS = ("goodput",) + WASTE_KINDS


class GoodputLedger:
    """Per-tick device-token attribution, folded into cumulative totals.

    >>> led = GoodputLedger(registry=tele.registry)
    >>> srv = ContinuousBatchingServer(model, ..., ledger=led)
    >>> srv.run()
    >>> led.snapshot()["goodput_ratio"]          # useful / total
    >>> led.totals()["replay"]                   # preemption burn

    The server calls ``add(kind, n)`` at each attribution site (under
    its own lock) and ``flush_tick()`` once per tick; everything else
    is read-side.
    """

    def __init__(self, registry=None, enabled=True):
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._tick = {}                      # current tick, single-writer
        self._totals = {}
        self._ticks = 0
        self._last = None                    # last flushed tick dict
        self._last_ratio = None
        self._tok = None
        self._tok_children = {}
        self._g_ratio = None
        if (self.enabled and registry is not None
                and getattr(registry, "enabled", False)):
            self._tok = registry.counter(
                "server_tokens_total",
                "Device tokens per tick by attribution kind "
                "(goodput / null_redirect / chunk_pad / "
                "skipped_page_dma / replay / tail_reprefill / "
                "block_waste) — kinds sum to total device tokens",
                labelnames=("kind",))
            self._g_ratio = registry.gauge(
                "serving_goodput_ratio",
                "goodput / total device tokens for the last non-empty "
                "tick")

    # ----------------------------------------------------------- write
    def add(self, kind, n):
        """Attribute ``n`` device tokens of this tick to ``kind``.
        Zero-count adds are dropped so a flushed tick's kinds are
        exactly the nonzero ones. No lock, no clock: callers already
        hold the server lock (single writer per ledger)."""
        if n:
            self._tick[kind] = self._tick.get(kind, 0) + int(n)

    def flush_tick(self):
        """Fold the current tick into the cumulative totals and publish
        metrics. Empty ticks (nothing attributed — an idle poll)
        publish nothing. Returns the tick's ``{kind: tokens}`` dict, or
        None when it was empty."""
        tick, self._tick = self._tick, {}
        if not tick:
            return None
        total = sum(tick.values())
        ratio = tick.get("goodput", 0) / total
        with self._lock:
            for k, n in tick.items():
                self._totals[k] = self._totals.get(k, 0) + n
            self._ticks += 1
            self._last = tick
            self._last_ratio = ratio
        if self._tok is not None:
            for k, n in tick.items():
                child = self._tok_children.get(k)
                if child is None:
                    child = self._tok_children[k] = \
                        self._tok.labels(kind=k)
                child.inc(n)
            self._g_ratio.set(ratio)
        return tick

    # ------------------------------------------------------------ read
    def totals(self):
        """Cumulative ``{kind: tokens}`` over every flushed tick."""
        with self._lock:
            return dict(self._totals)

    @property
    def ticks(self):
        return self._ticks

    def goodput_ratio(self):
        """Cumulative goodput / total device tokens (1.0 before any
        token was attributed — an idle server wastes nothing)."""
        with self._lock:
            total = sum(self._totals.values())
            if not total:
                return 1.0
            return self._totals.get("goodput", 0) / total

    def snapshot(self):
        """JSON-ready summary — the ``/stats["goodput"]`` payload and
        the ``goodput`` postmortem section."""
        with self._lock:
            totals = dict(self._totals)
            total = sum(totals.values())
            good = totals.get("goodput", 0)
            return {
                "tokens": totals,
                "total": total,
                "goodput_ratio": (good / total) if total else 1.0,
                "last_tick": dict(self._last) if self._last else None,
                "last_tick_ratio": self._last_ratio,
                "ticks": self._ticks,
            }
