"""Continuous-batching decode server (slot-based, static shapes).

The reference's serving depth is AnalysisPredictor + the fused-transformer
decode op driven per request (analysis_predictor.h:95,
fused_multi_transformer_op.cu). The TPU-native upgrade is CONTINUOUS
BATCHING: a fixed pool of decode slots steps as ONE batched XLA program
every tick; finished slots are refilled from the queue without stopping
the others. Static shapes throughout (slot count, cache length) — no
recompiles as requests come and go; per-slot positions ride the vector-t
decode step fns (models/generation.py).

Host/device split: the device does batched prefill + batched decode
steps; the host only assigns slots, harvests finished rows, and swaps
new prompts in — O(requests), not O(tokens), host work.
"""
import collections
import contextlib
import logging
import threading

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import unwrap
from ..jit.hoist import hoisted_jit
from .kv_cache import OutOfPages
from ..reliability import (CallbackError, CircuitOpenError, DEAD,
                           DEGRADED, DRAINING, DeadlineExceeded, HEALTHY,
                           HealthMonitor, MigrationError, PreemptedError,
                           QueueFullError, ReliabilityError,
                           RequestCancelled, ServeSupervisor, ServerClosed,
                           faults)
from ..telemetry.clock import MonotonicClock
from ..telemetry.serving import HostEventLog, SLOW_PHASE_S, TickBoundary

__all__ = ["ContinuousBatchingServer", "PreemptionPolicy", "PoolBalance"]

_log = logging.getLogger(__name__)


# The most rows (chunks x width) ANY prefill launch may compute. A launch is
# PACKED (``_prefill_tick``): it has a row for the slots in its plan and not
# for every slot, because its dense matmuls run over all of its rows,
# whatever is live. A server's own limit is ``_launch_row_limit`` of its
# per-tick prefill budget: a launch CARRIES at most the budget's tokens, so
# the power of two over the budget is the smallest limit that never shortens
# a take the budget allows, and every row beyond it is computed for nothing
# (at four times the budget a launch of at most 1,024 tokens computed 4,096
# rows in every serving cell: PERF.md section 6, PR 35).
# 4,096 stays as the CEILING it was introduced as (PR 31): 64 slots x 1,024
# is 65,536 rows, whose activations do not fit beside a 10 GB model, at
# 16,384 rows a launch held every decoding slot for 310 ms, and a server
# left at the default budget with ``max_cache_len`` 16,384 must not pack
# 16,384 rows of short chunks (a take wider than the ceiling still launches
# whole, in one row, as it always did).
_LAUNCH_ROWS_MAX = 4096


def _launch_row_limit(budget):
    """Rows a prefill launch may compute under a per-tick token budget:
    the power of two over the budget, at most ``_LAUNCH_ROWS_MAX``."""
    return min(1 << (int(budget) - 1).bit_length(), _LAUNCH_ROWS_MAX)


class _Pending:
    """A queued request awaiting a slot."""

    __slots__ = ("rid", "ids", "budget", "seed", "on_token", "deadline",
                 "priority", "journey")

    def __init__(self, rid, ids, budget, seed, on_token, deadline,
                 priority=0, journey=None):
        self.rid = rid
        self.ids = ids
        self.budget = budget
        self.seed = seed
        self.on_token = on_token
        self.deadline = deadline      # absolute clock time, or None
        self.priority = priority      # higher = preempted later
        self.journey = journey        # fleet trace handle (router), or
        #                               None — every emission site is
        #                               guarded, so no-journey costs
        #                               one attribute check


class _Slot:
    __slots__ = ("rid", "ids", "prompt_len", "budget", "emitted",
                 "on_token", "streamed", "deadline", "phase", "fill_pos",
                 "filled", "n_pre", "seed", "priority", "preempts",
                 "replayed", "journey", "reprefill_upto", "sent_pages")

    def __init__(self, rid, ids, prompt_len, budget, on_token=None,
                 deadline=None):
        self.rid = rid
        self.ids = ids                # prompt tokens (donated at release)
        self.prompt_len = prompt_len
        self.budget = budget          # max_new_tokens remaining
        self.emitted = []
        self.on_token = on_token
        self.streamed = 0             # tokens already sent to on_token
        self.deadline = deadline      # absolute clock time, or None
        # ragged-prefill lifecycle (dense admission completes prefill
        # atomically, so its slots are born in the "decode" phase with
        # the whole prompt marked filled)
        self.phase = "decode"         # "prefill" until first token
        self.fill_pos = prompt_len    # next prompt position to prefill
        self.filled = prompt_len      # prompt rows actually written
        self.n_pre = 0                # prefix-cache tokens reused
        self.seed = 0                 # sampling chain seed
        self.priority = 0             # preemption class (higher = safer)
        self.preempts = 0             # times this request was preempted
        self.journey = None           # fleet trace handle, or None
        self.reprefill_upto = 0       # prefill rows below this position
        #                               redo a registered prefix's
        #                               sub-page tail (ledger:
        #                               tail_reprefill, ragged mode)
        self.sent_pages = 0           # pages already shipped by a
        #                               pipelined handoff
        #                               (migrate_out(partial=True));
        #                               reset on migrate_abort so a
        #                               later full handoff re-ships
        # the partial recorded BEFORE a preemption: a resumed slot
        # replays the identical chain, so the longer of (replayed,
        # emitted) is always the request's true partial — a deadline/
        # cancel/hard-stop mid-replay must not hand the waiter fewer
        # tokens than its on_token stream already delivered
        self.replayed = ()

    def partial(self):
        """The request's current partial output: replayed tokens from
        before a preemption, or the live emitted list — whichever is
        longer (they agree on the common prefix by bit-exact replay)."""
        return self.emitted if len(self.emitted) >= len(self.replayed) \
            else list(self.replayed)

    def stream(self, sink):
        """Queue this slot's unstreamed chunk on ``sink``; the server
        fires callbacks AFTER releasing its lock (a slow or blocking
        callback must not stall decode/submit/cancel). A RESUMED slot
        starts with ``streamed`` at its pre-preemption offset, so the
        replayed (bit-identical) tokens below it are never re-sent."""
        if self.on_token is None:
            return
        upto = min(len(self.emitted), self.budget)
        if upto > self.streamed:
            sink.append((self.on_token, self.rid,
                         np.asarray(self.emitted[self.streamed:upto],
                                    np.int32)))
            self.streamed = upto


class _Preempted:
    """A request parked off its slot under pool pressure, awaiting
    re-admission (``admission="optimistic"``). Carries everything a
    bit-exact replay needs: the RESOLVED sampling seed (the replayed
    chain draws identically), the ABSOLUTE deadline (time spent parked
    keeps counting), ``streamed`` (on_token never re-sends delivered
    chunks), and ``emitted`` — the longest partial so far, flushed as
    the result if the request must leave early (deadline, cancel, hard
    stop, dead-replica evacuation) before decode resumes."""

    __slots__ = ("rid", "ids", "budget", "seed", "on_token", "deadline",
                 "priority", "emitted", "streamed", "preempts", "journey")

    def __init__(self, st):
        self.rid = st.rid
        self.ids = st.ids
        self.budget = st.budget
        self.seed = st.seed
        self.on_token = st.on_token
        self.deadline = st.deadline
        self.priority = st.priority
        self.emitted = list(st.partial())
        self.streamed = st.streamed
        self.preempts = st.preempts + 1
        self.journey = st.journey


class PreemptionPolicy:
    """Victim selection for ``admission="optimistic"``: when a
    mid-decode page grow hits an exhausted pool, ``pick`` names the
    slot whose pages are freed. The default order sacrifices the LEAST
    valuable work first — lowest ``priority`` class, then fewest
    tokens generated (least recompute thrown away), then the youngest
    request (highest rid) so ties are deterministic and two same-seed
    runs preempt identically.

    The growing slot is itself a candidate: when it ranks last it
    parks ITSELF instead of evicting more valuable work. That makes
    the ranking a strict total order over live slots, so the top
    request is never preempted, only gains tokens, and finishes —
    global progress follows by induction no matter how hard the pool
    thrashes (recompute-preemption as in paged-attention serving
    stacks, PAPERS.md)."""

    def key(self, slot, st):
        """Sort key over live slots; the MINIMUM is preempted first.
        Work is the request's TRUE partial (``st.partial()`` — the
        longer of the pre-preemption tokens and the live replay), not
        the raw replay progress: a resumed victim early in its replay
        must keep the seniority of the work it already did once, or
        every squeeze would re-pick the same just-resumed request and
        throw its replay away again (thrash/starvation of exactly the
        requests that already lost the gamble)."""
        return (st.priority, len(st.partial()), -st.rid)

    def pick(self, grower, candidates):
        """``candidates`` is ``[(slot, _Slot)]`` for every live slot,
        the grower included. Returns the victim slot id (possibly
        ``grower`` itself), or None when there is nothing to free."""
        if not candidates:
            return None
        return min(candidates, key=lambda c: self.key(*c))[0]


class PoolBalance(tuple):
    """``pool_balance()``'s result: a plain ``(free, live, pinned,
    cached)`` 4-tuple (existing unpacks keep working), with optimistic-
    admission state riding as ATTRIBUTES: ``preempted`` — requests
    currently parked on the preempted queue (their pages are already
    donated or freed, so they contribute nothing to ``live``) — and
    ``preemptions`` — cumulative victims preempted so far.

    On a sharded pool (mesh serving) the per-shard view rides as
    attributes too: ``num_shards`` (1 = unsharded/replicated),
    ``per_shard`` — one ``{"free", "live", "pinned", "cached"}`` dict
    per shard — and ``shard_page_bytes``, the pool bytes actually
    resident on one shard's device. Because pages shard on the KV-HEAD
    dim, every shard holds the same page set: the per-shard counts are
    balanced by construction, and this view exists so dashboards,
    storms, and postmortems can ASSERT that instead of assuming it
    (a future page-partitioned layout reports through the same
    surface).

    Tiered KV (ISSUE 17) rides as attributes too: ``host`` —
    host-resident radix-tree nodes (spilled pages; they hold NO device
    page, so they are outside the 4-tuple, which keeps summing to the
    usable pool) — and ``host_bytes``, the host tier's buffer bytes.
    Chaos suites assert ``host == 0 and host_bytes == 0`` after a
    drain + full eviction proves neither tier leaked."""

    def __new__(cls, free, live, pinned, cached, preempted=0,
                preemptions=0, num_shards=1, per_shard=(),
                shard_page_bytes=None, host=0, host_bytes=0):
        self = super().__new__(cls, (free, live, pinned, cached))
        self.preempted = preempted
        self.preemptions = preemptions
        self.num_shards = num_shards
        self.per_shard = tuple(per_shard)
        self.shard_page_bytes = shard_page_bytes
        self.host = host
        self.host_bytes = host_bytes
        return self


class ContinuousBatchingServer:
    """Serve ``model.generate``-compatible requests through a fixed slot
    pool. Results are bit-identical to a solo ``model.generate`` call —
    greedy trivially (slots are row-wise independent), and sampled
    decoding too: each request carries its own PRNG chain, split in the
    same pattern as ``sample_generate``, so ``submit(..., seed=s)``
    draws exactly what ``generate(..., do_sample=True, seed=s)`` draws.

    >>> srv = ContinuousBatchingServer(model, max_slots=4,
    ...                                max_cache_len=256)
    >>> rid = srv.submit(prompt_ids, max_new_tokens=32)
    >>> outs = srv.run()            # {rid: np.ndarray of new tokens}

    ``cache_backend="paged"`` swaps the dense ``[slots, max_cache_len]``
    KV buffers for a global page pool + per-slot block tables (ragged
    paged attention; ops/pallas/paged_attention.py, inference/
    kv_cache.py): cache HBM and decode attention bandwidth scale with
    ACTUAL sequence lengths, ``num_pages`` (default: worst case, every
    slot maxed out) sizes the pool to the real working set, registered
    prefixes are stored once and page-shared across slots, and tokens
    stay bit-identical to the dense backend. When the pool is full,
    admission waits (FIFO) for a harvest to free pages.

    With ``auto_prefix_cache=True`` (the paged default — None reads as
    True wherever pages are a request's whole state; see
    inference/prefix_cache.py) prefix reuse needs no operator calls at
    all: every finished request donates its full prompt pages into a
    radix tree keyed by token content, every admission looks up the
    longest cached page-aligned prefix automatically and prefills only
    the remainder, and unpinned cached pages are evicted LRU whenever
    the allocator runs short — the cache soaks up idle pool capacity
    and shrinks under load with zero correctness impact (auto hits are
    bit-identical to cold runs). ``register_prefix`` entries live in
    the same tree as PINNED nodes that eviction never touches.

    A model with PER-SLOT STATE beside the pool (a hybrid's short-
    convolution or state-space layers: ``caches["state"]``, a tree of
    leaves addressed by the slot and not by the block table) serves
    through the same tick. What assumes
    that pages are the whole state is refused for it BY NAME, at
    construction or at the call: the prefix cache (``auto_prefix_cache``
    reads as False for such a model; True, and ``register_prefix``,
    raise), ``admission="optimistic"`` (a replayed victim resumes from
    donated pages), ``host_tier`` and ``migrate_*`` (ROADMAP B5: state
    snapshots at page boundaries lift all four).

    Paged serving prefills RAGGED by default (``prefill_mode="ragged"``):
    admissions only reserve pages, and every tick runs the next chunk
    of ALL mid-prefill slots as ONE packed launch straight into pool
    pages (ops/pallas/ragged_prefill.py) — several admissions per tick,
    no dense batch-1 seed/gather/scatter detour on prefix-cache hits,
    and Sarathi-style interleaving: ``prefill_tokens_per_tick`` (default
    ``max_cache_len``) bounds the prefill work done per tick so a long
    prompt streams in across ticks while in-flight slots keep decoding
    every tick. ``max_admissions_per_tick`` caps reservations per
    scheduling pass; ``prefill_mode="dense"`` restores the PR-5
    per-admission dense prefill (the dispatch-count baseline;
    ``prefill_chunk`` only applies there and to ``register_prefix``).
    Tokens are bit-identical across all three of dense backend, paged+
    dense prefill, and paged+ragged prefill.

    ``admission="optimistic"`` (paged backend only; default
    ``"reserve"``) lifts the full-extent admission pessimism: a
    request is admitted with only its PROMPT pages plus
    ``headroom_pages``, decode grows its block table page-by-page on
    demand, and when a grow finds the pool empty the
    ``preemption_policy`` picks victims — lowest priority class first,
    then fewest tokens generated, deterministic ties — frees their
    pages (written prompt prefixes are donated into the prefix cache
    first), and parks them on a preempted queue. Re-admission REPLAYS
    the victim bit-exactly: the resolved seed restarts the identical
    sampling chain, the donated pages usually auto-hit so the prompt
    is not re-prefilled, and streamed callbacks resume at their old
    offset — under pressure the server degrades throughput, never
    correctness, and no request ever fails because the gamble lost.
    ``submit(priority=...)`` sets the preemption class (higher = safer,
    admitted first); admission order becomes priority-aware FIFO.

    ``telemetry`` (``paddle_tpu.telemetry.ServerTelemetry``, or ``True``
    for a default one) turns on SLO instrumentation: per-request
    lifecycle spans and TTFT/TPOT/queue-wait histograms, per-tick
    latency/occupancy, page-pool gauges and prefix-cache counters —
    scrape via ``telemetry.MetricsServer(srv.telemetry.registry)``.
    Host-side only; with the default ``telemetry=None`` the hot path
    pays a single attribute check, no locks and no clock reads.

    ``recorder`` (``telemetry.FlightRecorder``, or ``True``) adds the
    flight-recorder layer: a bounded ring of structured events
    (admissions, grows, preemptions/replays, evictions, per-tick
    dispatch profiles, health/breaker flips) and postmortem bundles
    captured on breaker open, request failure, and ``kill()`` —
    ``srv.postmortems()``, or ``/debug/postmortem`` via
    ``serve_metrics``. A disabled recorder is treated exactly like
    the default None (same zero-cost contract as telemetry).

    ``ledger`` (``telemetry.GoodputLedger``, or ``True``) turns on the
    goodput ledger: every device token each tick is attributed to
    exactly one kind — committed work (``goodput``) or a named waste
    reason (``null_redirect`` / ``chunk_pad`` / ``skipped_page_dma`` /
    ``replay`` / ``tail_reprefill`` / ``block_waste``) — published as
    ``server_tokens_total{kind}``, the per-tick
    ``serving_goodput_ratio`` gauge, ``srv.goodput()`` (also
    ``/stats["goodput"]``), and a ``goodput`` postmortem section.
    Kinds sum to the tick's total device tokens (conservation is
    test-asserted); a disabled ledger is treated exactly like None.

    ``costs`` (``telemetry.CostCatalog``, or ``True``) turns on the
    device-cost ledger + compile watch: every jitted serving program
    (the decode block, each ragged-prefill chunk width) is priced ONCE
    per shape signature from the compiler's own
    ``cost_analysis`` at compile time, every dispatch is charged FLOPs
    + HBM bytes (``server_flops_total{op}`` /
    ``server_hbm_bytes_total{op}``, ``serving_mfu``), compiles are
    timed (``server_compiles_total{op}``, ``serving_compile_seconds``)
    and a compile AFTER warmup lands as a ``compile`` flight-recorder
    event with ``recompile=True`` plus a ``compile_stall`` journey
    phase on every request parked behind it, and each tick's wall is
    split into phases (``serving_tick_phase_seconds{phase}``) —
    ``srv.device_costs()`` (also ``/stats["costs"]``) and a ``costs``
    postmortem section. A disabled catalog is treated exactly like
    None (zero clock reads / locks on the tick path).

    ``journeys`` (``telemetry.JourneyRecorder``, or ``True``) lets a
    STANDALONE server mint its own request journeys: ``submit()``
    begins one per request unless a router-supplied handle arrives via
    ``submit(journey=)``, and ``srv.journey(rid)`` returns the
    timeline (also ``/debug/journey/<rid>``).

    Reliability (paddle_tpu.reliability): ``submit(deadline_s=...)``
    bounds waiting, ``max_queue`` + ``shed_policy`` bound the queue,
    the ``start()`` serve thread is SUPERVISED (``retry_policy`` /
    ``breaker`` drive backoff and circuit breaking; a tick exception
    retries instead of killing the thread), ``stop(drain=True)``
    drains gracefully, ``srv.health`` walks
    healthy/degraded/draining/dead (also ``/healthz`` via
    ``serving.serve_metrics``), and ``fault_injector`` arms named
    chaos failure points (prefill / decode tick / page alloc /
    on_token). All typed failures reach waiters as
    ``reliability.ReliabilityError`` subclasses from ``wait()``.
    """

    def __init__(self, model, max_slots=4, max_cache_len=256,
                 do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                 eos_token_id=None, seed=0, weight_dtype=None,
                 prefill_chunk=None, mesh=None, tick_block=1,
                 cache_dtype=None, cache_backend="dense", page_size=16,
                 num_pages=None, auto_prefix_cache=None,
                 admission="reserve", headroom_pages=1,
                 preemption_policy=None,
                 prefill_mode=None, prefill_tokens_per_tick=None,
                 max_admissions_per_tick=None, telemetry=None,
                 recorder=None, ledger=None, journeys=None, costs=None,
                 host_tier=None, host_tier_bytes=None,
                 max_queue=None, shed_policy="reject",
                 retry_policy=None, breaker=None, fault_injector=None,
                 clock=None, role="hybrid"):
        if role not in ("prefill", "decode", "hybrid"):
            raise ValueError(
                "role must be 'prefill', 'decode' or 'hybrid', got "
                f"{role!r}")
        # disaggregated serving (ISSUE 20): the role is a PLACEMENT
        # hint the router reads — a "prefill" specialist runs ragged
        # prefill and hands finished prompt pages to decode replicas;
        # its one hard rule is refusing decode-phase migrate_in (it
        # still decodes locally when the fleet degrades to hybrid
        # routing). "decode" is advisory only.
        self.role = role
        self.model = model
        self.mesh = mesh
        self.max_slots = int(max_slots)
        self.max_cache_len = int(max_cache_len)
        self.eos_token_id = eos_token_id
        self.do_sample = bool(do_sample)
        self._temperature = float(temperature)
        self._top_k = int(top_k)
        self._top_p = float(top_p)
        self._seed = int(seed)
        self._keys = jnp.zeros((int(max_slots), 2), jnp.uint32)
        # the dense bundle always exists: prefill (and the prefix cache)
        # run on dense batch-1 caches whatever the decode backend is
        self._bundle = model._decode_bundle(max_cache_len, weight_dtype,
                                            mesh, cache_dtype)
        (self._init_caches, self._embed_fn, self._step_fn,
         self._head_fn, self._prefill_jit) = self._bundle
        self._prefill_chunk = prefill_chunk
        self.tick_block = max(1, int(tick_block))

        if cache_backend not in ("dense", "paged"):
            raise ValueError(f"cache_backend must be 'dense' or 'paged', "
                             f"got {cache_backend!r}")
        self.cache_backend = cache_backend
        self._kv = None
        if cache_backend == "paged":
            # decode runs on a global K/V page pool addressed through
            # per-slot block tables (ragged paged attention); the pool —
            # not slots x max_cache_len — is the cache HBM budget, so it
            # can be sized to the ACTUAL token working set
            from .kv_cache import PagedKVCache
            page_size = int(page_size)
            if self.max_cache_len % page_size:
                raise ValueError(
                    f"page_size ({page_size}) must divide max_cache_len "
                    f"({self.max_cache_len})")
            pages_per_slot = self.max_cache_len // page_size
            if num_pages is None:     # worst case: every slot maxed out
                num_pages = self.max_slots * pages_per_slot + 1
            self.page_size = page_size
            # the paged kernels' grid covers the FULL block-table width
            # per slot — the goodput ledger's skipped-page-DMA model
            self._bt_pages = pages_per_slot
            self._paged_bundle = model._decode_bundle(
                max_cache_len, weight_dtype, mesh, cache_dtype,
                cache_backend="paged", page_size=page_size,
                num_pages=int(num_pages))
            self._step_fn = self._paged_bundle[2]
            self._kv = PagedKVCache(int(num_pages), page_size,
                                    self.max_slots, pages_per_slot,
                                    fault_injector=fault_injector)
            self._caches = self._paged_bundle[0](self.max_slots)
            # per-slot recurrent state beside the pool (a short
            # convolution's last inputs, a state-space layer's window
            # and float32 state: a tree of leaves): carried and donated
            # like the pool, addressed by the slot — no page holds it
            self._slot_state = "state" in self._caches
            if self._slot_state:
                auto_prefix_cache = self._refuse_for_slot_state(
                    auto_prefix_cache, admission, host_tier,
                    host_tier_bytes, prefill_mode)
            # how many ways the pool actually sharded (1 = replicated
            # fallback: kv heads not divisible by the mp axis) — the
            # host-side bookkeeping's ONLY mesh knowledge, feeding the
            # per-shard balance views and the cost-op namespacing
            from ..models.generation import (paged_kv_heads,
                                             paged_pool_shards)
            # the pool stores a token's kv heads merged into one
            # lane-dense axis (generation.paged_pool_shape): the head
            # count comes from the model's config, and the page movers
            # below view rows per head at the host boundary
            self._kv_heads = paged_kv_heads(model.cfg)
            self._pool_shards = paged_pool_shards(mesh, self._kv_heads)
            # heads each pool leaf stores a token, off the dense
            # bundle's cache tree ([L, B, T, heads, dim] a leaf), which
            # has the same leaves unmerged
            dense = jax.eval_shape(lambda: self._init_caches(1))
            self._leaf_heads = {name: int(dense[name].shape[3])
                                for name in self._caches["pool"]}
            # host KV tier (kv_tier.HostTier): eviction SPILLS cold
            # prefix pages to checksummed host buffers instead of
            # dropping them, and admissions hitting a spilled run
            # restore it into fresh pool pages. True builds a default
            # tier (host_tier_bytes= bounds it; None = unbounded);
            # None/disabled keeps eviction exactly as before — zero
            # locks, zero clock reads, structurally free, the same
            # contract as ledger/recorder/costs
            if host_tier is None and host_tier_bytes is not None:
                host_tier = True
            if host_tier is True:
                from .kv_tier import HostTier
                host_tier = HostTier(budget_bytes=host_tier_bytes,
                                     fault_injector=fault_injector)
            self.host_tier = host_tier
            self._host = host_tier if (host_tier is not None
                                       and host_tier.enabled) else None
            if self._host is not None and self._host._faults is None:
                # like the recorder: a bare tier adopts the server's
                # injector so tier.spill/tier.restore storms need no
                # extra wiring
                self._host._faults = fault_injector
            # the radix tree indexes EVERY page-granular prefix in the
            # pool: register_prefix entries live in it pinned; with
            # auto_prefix_cache (default) finished requests donate
            # their prompt pages into it and lookups happen on every
            # admission — unpinned entries are evicted LRU whenever
            # the allocator runs short (demoted to the host tier when
            # one is attached)
            from .prefix_cache import PrefixCache
            self._prefix = PrefixCache(self._kv,
                                       fault_injector=fault_injector,
                                       host_tier=self._host,
                                       spill=self._spill_payload)
            self._kv.reclaimer = self._reclaim_pages
            self._auto_prefix = (True if auto_prefix_cache is None
                                 else bool(auto_prefix_cache))
            self._ragged_fn = (self._paged_bundle[5]
                               if len(self._paged_bundle) > 5 else None)
        else:
            if host_tier is True or (host_tier is not None
                                     and host_tier.enabled):
                raise ValueError("host_tier= needs cache_backend="
                                 "'paged' (the tier spills pool pages)")
            self.host_tier = None
            self._host = None
            self.page_size = None
            self._bt_pages = None
            self._pool_shards = 1
            self._caches = self._init_caches(self.max_slots)
            self._slot_state = False    # dense: state rides the slot's rows
            self._prefix = None
            self._auto_prefix = False
            self._ragged_fn = None
        # ------------------------------------------------ prefill mode
        # "ragged" (the paged default): admissions reserve pages only;
        # their prompt chunks run BATCHED as one ragged launch per tick
        # straight into pool pages — no dense batch-1 seed/gather/
        # scatter detour — interleaved with decode under a token budget.
        # "dense" keeps the PR-5 per-admission dense prefill (the only
        # mode for the dense cache backend, and the baseline the
        # benchmarks compare dispatch counts against).
        if prefill_mode is None:
            prefill_mode = "ragged" if self._ragged_fn is not None \
                else "dense"
        if prefill_mode not in ("dense", "ragged"):
            raise ValueError(f"prefill_mode must be 'dense' or 'ragged',"
                             f" got {prefill_mode!r}")
        if prefill_mode == "ragged":
            if cache_backend != "paged":
                raise ValueError("prefill_mode='ragged' needs "
                                 "cache_backend='paged' (prefill writes "
                                 "straight into pool pages)")
            if self._ragged_fn is None:
                raise ValueError(
                    "prefill_mode='ragged' but this model's paged "
                    "decode bundle has no ragged-prefill entry point "
                    "(6th element); use prefill_mode='dense'")
        self.prefill_mode = prefill_mode
        self._ragged = prefill_mode == "ragged"
        if prefill_tokens_per_tick is None:
            prefill_tokens_per_tick = self.max_cache_len
        self._prefill_budget = int(prefill_tokens_per_tick)
        if self._prefill_budget < 1:
            raise ValueError("prefill_tokens_per_tick must be >= 1")
        # rows a prefill launch may compute: what its budget can fill
        self._launch_rows = _launch_row_limit(self._prefill_budget)
        self._admit_cap = None if max_admissions_per_tick is None \
            else int(max_admissions_per_tick)
        if self._admit_cap is not None and self._admit_cap < 1:
            raise ValueError("max_admissions_per_tick must be >= 1 "
                             "(0 would admit nothing, forever)")
        # ------------------------------------------------ admission mode
        # "reserve" (default): admission takes a request's FULL extent
        # (prompt + budget) up front — decode can never hit an empty
        # pool, but concurrency is capped by the WORST-case decode
        # length even though most requests finish far earlier.
        # "optimistic": admission reserves only the prompt pages plus
        # ``headroom_pages``; decode grows each slot page-by-page on
        # demand, and when the pool runs dry mid-tick the
        # ``preemption_policy`` frees victims — parked on a preempted
        # queue and re-admitted with a BIT-EXACT replay (resolved seed
        # + prefix-cache-assisted recompute), so pressure degrades
        # throughput, never correctness.
        if admission not in ("reserve", "optimistic"):
            raise ValueError(f"admission must be 'reserve' or "
                             f"'optimistic', got {admission!r}")
        if admission == "optimistic" and cache_backend != "paged":
            raise NotImplementedError(
                "admission='optimistic' needs cache_backend='paged': "
                "the dense backend allocates every slot's full "
                "[max_cache_len] KV rows up front, so there is no pool "
                "to admit optimistically against — virtualizing dense "
                "slot buffers is the same page-pool work as the "
                "quantized paged pool (ROADMAP A7); use "
                "cache_backend='paged'")
        self.admission = admission
        self._optimistic = admission == "optimistic"
        self._headroom_pages = int(headroom_pages)
        if self._headroom_pages < 0:
            raise ValueError("headroom_pages must be >= 0")
        self._preempt_policy = preemption_policy \
            if preemption_policy is not None else PreemptionPolicy()
        self._preempted = []      # _Preempted records awaiting re-admission
        self._migrating = {}      # rid -> (slot, tele t0, prior phase):
        #                           paused slots whose gathered pages are
        #                           in flight to a sibling (migrate_out) —
        #                           settled by migrate_finish (handoff
        #                           committed, pages released/donated
        #                           here) or migrate_abort (resume
        #                           decoding — or prefilling, for an
        #                           empty-`emitted` handoff — here)
        self._staging = {}        # handle -> pipelined-restore slot
        #                           (migrate_in_begin): pages scatter in
        #                           batches while the source still
        #                           prefills; settled by
        #                           migrate_in_commit / migrate_in_abort
        self._next_xfer = 1       # staged-restore handle mint
        self._priority_seen = False   # sticky: any submit(priority != 0)
        self._prefill_fifo = []   # slot ids mid-prefill, admission order
        self._prefill_used = 0    # tokens prefilled this tick
        # slot-state updates batched into one device push per array per
        # tick (the dense path paid 3 dispatches per admission)
        self._pending_tok = {}
        self._pending_t = {}
        self._pending_key = {}
        self._tok = jnp.zeros((self.max_slots,), jnp.int32)
        # every slot starts parked on the idle sentinel (_park_slot)
        self._t = jnp.full((self.max_slots,), self.max_cache_len,
                           jnp.int32)
        self._active = np.zeros((self.max_slots,), bool)   # host-side
        self._slots = [None] * self.max_slots
        self._queue = []          # (rid, ids_np, max_new_tokens)
        self._results = {}
        self._next_rid = 0
        self._decode_jit = None
        self._prefixes = []   # [(ids, cache_rows, last_logits, pages)]
        self.stats = {"prefill_tokens": 0, "prefix_hit_tokens": 0,
                      "prefix_auto_hits": 0, "prefix_auto_hit_tokens": 0,
                      "admissions": 0, "prefill_dispatches": 0,
                      "prefill_wall_s": 0.0, "tick_dispatches": 0,
                      # tick phases of telemetry.serving.SLOW_PHASE_S or
                      # longer, and their seconds (flat, so a window's
                      # difference can be taken); srv.slow_phases has a
                      # record of each
                      "slow_phases": 0, "slow_phase_s": 0.0,
                      # admission="optimistic" accounting
                      "preemptions": 0, "preempt_resumed": 0,
                      "grow_pages": 0, "headroom_pages": 0,
                      # live KV-page migration accounting: handoffs
                      # committed as the SOURCE / degraded to
                      # evacuate+replay / restored as the TARGET
                      "migrations": 0, "migration_fallbacks": 0,
                      "migrated_in": 0,
                      # disaggregated prefill handoff accounting:
                      # partial page batches shipped as the source
                      # (migrate_out(partial=True)) / staged batches
                      # landed as the target (migrate_in_pages)
                      "handoff_pages_out": 0, "handoff_pages_in": 0,
                      # rows the decode ticks carried (slots x block a
                      # tick) and those of a slot that was decoding:
                      # the rest rode parked on the idle sentinel. The
                      # steps the paged decode kernel's grid took, a
                      # layer at a time, and the pages its live rows
                      # spanned (ops.pallas.paged_attention.decode_grid
                      # on the host's lengths: the function that sizes
                      # the grid on the device). The same of the ragged
                      # prefill kernel's launches: the steps its grid
                      # took and those that attended a page of a live
                      # query tile (ops.pallas.paged_attention.
                      # prefill_grid on the launch's offsets and takes).
                      # What a routed-expert / key-selecting model's
                      # launches did (zero for models with neither):
                      # rows the expert FFN computed (those of the
                      # slots that rode the launch live: a parked
                      # slot's join no expert's group) and those of a
                      # live token; distinct experts the live rows of a
                      # DECODE tick chose, summed over expert layers
                      # and ticks; keys in the context of live decode rows
                      # (a tick's last row a slot, a layer at a time)
                      # and keys the selection kept of them, which the
                      # device counts where it makes the mask
                      # slot-chunks the prefill launches ran (one a
                      # slot a launch) and those that began past a
                      # prompt's start: a model with slot state reads
                      # the state its last chunk left there; the dense
                      # rows the launches computed (rows x width a
                      # launch, live or not: prefill_tokens over it is
                      # how full the launches ran)
                      "prefill_chunks": 0, "prefill_chunks_carried": 0,
                      "prefill_rows": 0,
                      "decode_ticks": 0, "decode_rows": 0,
                      "decode_live_rows": 0, "decode_grid_steps": 0,
                      "decode_live_pages": 0, "prefill_grid_steps": 0,
                      "prefill_live_steps": 0, "moe_rows": 0,
                      "moe_live_rows": 0, "moe_experts_touched": 0,
                      # the (row, expert) choices of the decode ticks'
                      # live rows, and those that fell on an expert this
                      # model holds (a share of the router's, or all)
                      "moe_pairs_routed": 0, "moe_pairs_held": 0,
                      "attn_keys_context": 0, "attn_keys_selected": 0}
        cfg = getattr(model, "cfg", None)
        self._moe_k = int(getattr(cfg, "top_k", 0) or 0) \
            if getattr(cfg, "num_experts", 0) else 0
        # the share of the router's experts the model holds: (first,
        # count), None where it holds them all
        self._moe_held = getattr(cfg, "experts_held", None)
        indexer = getattr(cfg, "indexer", None)
        self._select_k = int(indexer[2]) if indexer else 0
        # layers that attend: the pool's own count on the paged backend
        # (a model with a layer spec has fewer than it has layers)
        self._n_layers = (int(self._caches["pool"]["k"].shape[0])
                          if self._kv is not None
                          else int(getattr(cfg, "num_layers", 0) or 0))
        # telemetry (paddle_tpu.telemetry.ServerTelemetry): True builds
        # a default-enabled one; None (default) keeps the hot path at
        # a single attribute check — no locks, no clock reads
        if telemetry is True:
            from ..telemetry import ServerTelemetry
            telemetry = ServerTelemetry()
        self.telemetry = telemetry
        self._tele = telemetry if (telemetry is not None
                                   and telemetry.enabled) else None
        # one time base for everything (events must correlate with
        # spans/deadlines in a postmortem, and FakeClock tests need
        # determinism): explicit clock > telemetry's > monotonic
        self._clock = clock if clock is not None else (
            telemetry.clock if self._tele is not None else MonotonicClock())
        # flight recorder (telemetry.FlightRecorder): structured event
        # ring + postmortem bundles. True builds a default one on the
        # server's clock; a DISABLED recorder is treated exactly like
        # None, so the hot path pays one `is None` check — no locks,
        # no clock reads
        if recorder is True:
            from ..telemetry import FlightRecorder
            recorder = FlightRecorder(clock=self._clock)
        self.recorder = recorder
        self._rec = recorder if (recorder is not None
                                 and recorder.enabled) else None
        # goodput ledger (telemetry.GoodputLedger): per-tick device-
        # token attribution — goodput vs null_redirect / chunk_pad /
        # skipped_page_dma / replay / tail_reprefill / block_waste.
        # True builds one on the telemetry registry (metrics ride
        # server_tokens_total{kind} + serving_goodput_ratio); a
        # DISABLED ledger is treated exactly like None — one `is None`
        # check per site, no locks, no clock reads (it never reads a
        # clock at all)
        if ledger is True:
            from ..telemetry import GoodputLedger
            ledger = GoodputLedger(
                registry=self._tele.registry
                if self._tele is not None else None)
        self.ledger = ledger
        self._led = ledger if (ledger is not None
                               and ledger.enabled) else None
        # device-cost catalog + compile watch (telemetry.CostCatalog):
        # every jitted serving program priced once per shape signature
        # at compile time (lower/compile/cost_analysis — the catalog
        # keeps the executable, so pricing costs no duplicate compile),
        # every dispatch charged FLOPs + HBM bytes, recompiles after
        # warmup surfaced, tick wall split into phases. True builds one
        # on the telemetry registry + server clock; a DISABLED catalog
        # is treated exactly like None — one `is None` check per site,
        # zero locks, zero clock reads on the tick path
        if costs is True:
            from ..telemetry import CostCatalog
            costs = CostCatalog(
                registry=self._tele.registry
                if self._tele is not None else None, clock=self._clock)
        self.costs = costs
        self._costs = costs if (costs is not None
                                and costs.enabled) else None
        # the tick's one phase boundary (telemetry.TickBoundary): set
        # by _step_locked, closed by _fire_callbacks; None whenever
        # telemetry and costs are both off
        self._boundary = None
        self._tick_seq = 0          # ticks opened, the spans' tick=<n>
        # a phase that stalls names itself (_slow_phase, the boundary's
        # sink): the newest 32 records, and what the host did meanwhile
        # that no phase names (compiles, cache loads, collector pauses;
        # telemetry.serving.HostEventLog, on the boundary's clock).
        # With telemetry and costs both off there is no boundary, no
        # log, no listener and never a record
        self.slow_phases = collections.deque(maxlen=32)
        self._host_events = None
        if self._tele is not None or self._costs is not None:
            self._host_events = HostEventLog(
                self._tele.clock if self._tele is not None
                else self._costs.clock)
        self._launch_shapes = set()     # (width, rows) launched so far
        self._fresh_launch = False      # the last launch's was new
        # launches enqueued since the host last read a value back: what
        # the next wait may have to sit through (a long prompt's chunks
        # launch one after another with nothing read back between them
        # while no slot decodes, and the last one waits for them all)
        self._unawaited = 0
        self._decode_prog = None    # priced decode program (static sig)
        self._kv_row_nbytes = None  # lazy: bytes per K+V token row
        # journey recorder for STANDALONE servers (closes the PR-9
        # "router-minted only" cut): submit() mints "s<rid>" journeys
        # when no router-supplied handle arrives, and journey(rid)
        # returns the timeline. Router-fronted servers keep receiving
        # handles via submit(journey=) — those always win.
        if journeys is True:
            from ..telemetry import JourneyRecorder
            journeys = JourneyRecorder(clock=self._clock)
        self.journeys = journeys
        self._jrec = journeys if (journeys is not None
                                  and journeys.enabled) else None
        # per-tick host->device dispatch profile {op: count} — what
        # ROADMAP A2 prices (host work inside the tick); reset at each
        # tick, published to telemetry
        # + recorder when nonempty (plain dict ops: always maintained,
        # costs no locks/clock)
        self._tick_disp = {}
        if fault_injector is not None:
            # chaos storms become VISIBLE: fires publish to this
            # server's registry and land in its flight recorder (an
            # injector shared across servers keeps the first recorder
            # it was given)
            if self._tele is not None \
                    and hasattr(fault_injector, "publish_to"):
                fault_injector.publish_to(self._tele.registry)
            if self._rec is not None \
                    and getattr(fault_injector, "recorder", None) is None:
                fault_injector.recorder = self._rec
        self._failures = {}   # rid -> admission exception (ADVICE r5 #2)
        self._run_failures = {}   # last run()'s drained failures
        # submit()/cancel() may come from request threads while a serve
        # thread drives step(); one lock covers the queue/slot state and
        # a condition on it wakes wait()ers at harvest time
        self._lock = threading.RLock()
        self._done_cv = threading.Condition(self._lock)
        self._stop = threading.Event()
        self._thread = None
        self._thread_error = None
        self._deferred_cbs = []   # (cb, rid, tokens) fired OUTSIDE the lock
        # ------------------------------------------------- reliability
        # admission control: a bounded queue sheds instead of growing
        # without limit under overload; deadlines bound waiting
        if shed_policy not in ("reject", "evict_oldest"):
            raise ValueError(f"shed_policy must be 'reject' or "
                             f"'evict_oldest', got {shed_policy!r}")
        self._max_queue = None if max_queue is None else int(max_queue)
        self._shed_policy = shed_policy
        self._faults = fault_injector
        self._sup = ServeSupervisor(retry=retry_policy, breaker=breaker)
        self._health = HealthMonitor(on_change=self._publish_health)
        self._accepting = True     # False while draining / after stop
        self._draining = False
        if self._tele is not None:
            self._tele.set_health(HEALTHY)

    def _refuse_for_slot_state(self, auto_prefix_cache, admission,
                               host_tier, host_tier_bytes, prefill_mode):
        """Construction-time refusals for a model with slot state; the
        resolved ``auto_prefix_cache`` (None reads as False here)."""
        asked = [what for what, on in (
            ("auto_prefix_cache=True (a prefix hit resumes at a page "
             "boundary)", bool(auto_prefix_cache)),
            ("admission='optimistic' (a preempted request is replayed "
             "from the pages it donated)", admission == "optimistic"),
            ("host_tier (spilled pages are restored into prefix hits)",
             host_tier is not None and host_tier is not False
             or host_tier_bytes is not None),
            ("prefill_mode='dense' on the paged backend (a dense "
             "batch-1 prefill hands the slot its pages only)",
             prefill_mode == "dense")) if on]
        if asked:
            self._refuse_slot_state("; ".join(asked))
        return False

    def _refuse_slot_state(self, what):
        """THE refusal of whatever assumes that pages are a request's
        whole state, for a model that keeps state beside them."""
        if self._slot_state:
            raise NotImplementedError(
                "this model keeps per-slot recurrent state (its short-"
                "convolution or state-space layers', caches['state']) "
                f"beside the page pool, and {what} assumes that pages are a "
                "request's whole state: a run of pages would be resumed "
                "with no state to go with it. State snapshots at page "
                "boundaries are ROADMAP B5")

    # ------------------------------------------------------ prefix cache
    def register_prefix(self, prefix_ids):
        """Prefill a shared prompt prefix (e.g. a system prompt) ONCE and
        reuse its KV rows for every later request that starts with it —
        admission then only prefills the remainder. Longest registered
        match wins. Returns the prefix length; the entry it pins is
        PERMANENT — unlike automatically cached (donated) pages, pinned
        entries are never evicted, whatever the pool pressure. Safe to
        call while a serve thread is decoding (the lock serializes it
        against ticks: the paged path writes pool pages and takes
        allocator pages, both of which would otherwise race the
        donating decode program). Paged backend: full pages the auto
        prefix cache already holds for these tokens are adopted (and
        pinned) rather than re-allocated."""
        self._refuse_slot_state("register_prefix (a prefix hit resumes "
                                "at a page boundary)")
        ids = np.asarray(unwrap(prefix_ids)).astype(np.int32).reshape(-1)
        T = ids.shape[0]
        if T + 1 > self.max_cache_len:
            raise ValueError(f"prefix ({T}) leaves no room in "
                             f"max_cache_len ({self.max_cache_len})")
        with self._lock:
            for pre_ids, _, _, _ in self._prefixes:
                # idempotent: re-registering (e.g. a client retry) must
                # not re-prefill or pin a second, unreachable page set
                if (pre_ids.shape[0] == T
                        and np.array_equal(pre_ids, ids)):
                    return T
            if self._prefill_chunk and not self._ragged:
                # a queued request was bound-checked at submit against
                # the prefixes registered THEN; refuse a new prefix
                # whose remainder-chunk pad would overflow its rows
                # mid-admission (ADVICE r5 #2). Ragged admission never
                # pads a remainder (chunking is the per-tick token
                # budget, cut at any position), so no such hazard.
                for item in self._queue:
                    q_ids = item.ids
                    Tq = q_ids.shape[0]
                    if Tq <= T or not np.array_equal(q_ids[:T], ids):
                        continue
                    cur = self._match_prefix(q_ids)
                    if cur is not None and cur[0].shape[0] >= T:
                        continue    # a longer match still wins
                    rpad = self._chunk_pad(Tq - T)
                    if Tq + rpad > self.max_cache_len:
                        raise ValueError(
                            f"registering this {T}-token prefix "
                            f"would pad the queued {Tq}-token "
                            f"request's remainder prefill {rpad} "
                            f"rows past max_cache_len "
                            f"({self.max_cache_len}) — register "
                            f"prefixes before submitting")
            logits, caches1 = self.model._run_prefill(
                self._bundle, ids[None], chunk=self._prefill_chunk)
            self.stats["prefill_tokens"] += T
            if self._tele is not None:
                self._tele.add_prefill_tokens(T)
            # dense prefill mode seeds admissions from these retained
            # rows/logits; ragged mode matches through the pinned tree
            # pages alone and never reads them — retaining a full
            # per-layer dense KV copy of the prefix for the server's
            # lifetime would be pure HBM waste there
            rows = None if self._ragged else jax.tree_util.tree_map(
                lambda c: c[:, :, :T], caches1)
            if self._ragged:
                logits = None
            pages, run, own, pin_delta = [], [], [], 0
            if self._kv is not None:
                # store the prefix's FULL pages once in the pool; every
                # slot that hits the prefix points its block table at
                # them. The radix tree is the page index: nodes the
                # auto cache already donated for these tokens are
                # adopted (pinned below), only the missing tail is
                # freshly allocated and filled
                nfull = T // self._kv.page_size
                if nfull:
                    aligned = ids[:nfull * self._kv.page_size]
                    run = self._prefix.node_run(aligned)
                    pin_delta = nfull - sum(1 for nd in run if nd.pinned)
                    if nfull > len(run):
                        # the adopted run must survive the allocation's
                        # own LRU reclaim sweep
                        self._prefix.protect(run)
                        try:
                            own = self._kv.alloc(nfull - len(run))
                        finally:
                            self._prefix.protect(())
                    pages = [nd.page for nd in run] + own
            entry = (ids, rows, logits, pages)
            self._prefixes.append(entry)
            self._prefixes.sort(key=lambda e: -e[0].shape[0])
            if self._kv is not None and pages:
                # pinning shrinks the pool for everyone else: a queued
                # request that can no longer EVER fit would silently
                # starve the FIFO — refuse the registration instead
                usable = self._kv.num_pages - 1 \
                    - (self._prefix.pinned_pages + pin_delta)
                for item in list(self._queue) + list(self._preempted):
                    # parked preempted requests must stay re-admittable
                    # too: their FULL extent is the binding bound (the
                    # top-ranked one must be able to run to completion)
                    q_ids = item.ids
                    q_need = self._request_pages(
                        q_ids, item.budget, self._match_prefix(q_ids))
                    if q_need > usable:
                        self._prefixes = [e for e in self._prefixes
                                          if e is not entry]
                        if own:
                            self._kv.release(own)
                        raise ValueError(
                            f"registering this {T}-token prefix pins "
                            f"{len(pages)} pages and would strand an "
                            f"already-queued request needing "
                            f"{q_need} of "
                            f"{usable} usable pages — grow num_pages "
                            f"or register prefixes before submitting")
                if own:
                    self._fill_pages(caches1, own,
                                     len(run) * self._kv.page_size)
                self._prefix.extend_pinned(
                    ids[:len(pages) * self._kv.page_size], run, own)
                self._prefix.flush_sketch()
            self._pool_gauges()
        return T

    def _chunk_pad(self, seg_len):
        """Rows the chunked prefill pads past ``seg_len`` — zero when
        the segment runs UNCHUNKED (``seg_len <= chunk``:
        generation._run_prefill takes the direct path and writes exactly
        ``seg_len`` rows)."""
        c = self._prefill_chunk
        if not c or seg_len <= c:
            return 0
        return (-seg_len) % c

    def _match_prefix(self, ids):
        for pre_ids, rows, logits, pages in self._prefixes:
            n = pre_ids.shape[0]
            if ids.shape[0] >= n and np.array_equal(ids[:n], pre_ids):
                return pre_ids, rows, logits, pages
        return None

    # ------------------------------------------------------------ queue
    def submit(self, input_ids, max_new_tokens=32, seed=None,
               on_token=None, deadline_s=None, priority=0,
               journey=None):
        """Queue a prompt; returns a request id. The FIRST generated
        token is produced by the prefill (same contract as generate()).
        ``seed`` drives this request's sampling chain (default: the
        server seed + request id). ``on_token(rid, tokens)`` streams
        each harvested chunk (1..tick_block tokens) as it lands.

        ``priority`` (``admission="optimistic"`` only; ignored under
        ``"reserve"``) is the request's preemption class: under pool
        pressure victims are taken from the LOWEST class first, and
        admission prefers higher classes (priority-aware FIFO — same
        class keeps submit order). Whatever the pressure, every
        request's full extent must still fit the pool on its own
        (checked here), so the top-ranked request can always run to
        completion.

        ``deadline_s`` bounds the request's TOTAL time from submit: a
        request still queued when it expires fails with
        ``DeadlineExceeded`` (no prefill is wasted on it); one expiring
        mid-decode is cancelled and its PARTIAL tokens are recorded as
        the result. With ``max_queue`` set, a full queue sheds per
        ``shed_policy`` — ``"reject"`` raises ``QueueFullError`` here,
        ``"evict_oldest"`` fails the oldest queued request instead and
        accepts this one.

        ``journey`` (a ``telemetry.Journey`` handle, normally minted by
        the router and rebound per dispatch) threads this request's
        fleet timeline through admission, prefill chunks, grow/preempt/
        replay and completion; the default None costs one attribute
        check per lifecycle site."""
        ids = np.asarray(unwrap(input_ids)).astype(np.int32)
        if ids.ndim == 2:
            if ids.shape[0] != 1:
                raise ValueError("submit() takes one request; batch by "
                                 "calling submit() per row")
            ids = ids[0]
        T = ids.shape[0]
        with self._submit_lock() as lock_wait:
            if not self._accepting:
                raise ServerClosed(
                    f"server is {self._health.state}; not accepting "
                    f"new requests")
            if deadline_s is not None and deadline_s <= 0:
                raise DeadlineExceeded(
                    f"deadline_s={deadline_s} is already expired")
            hit = None if self._ragged else self._match_prefix(ids)
            pad = 0
            if self._prefill_chunk and not self._ragged:
                # a registered-prefix hit prefills only the REMAINDER at
                # t0=n, whose own chunk pad can exceed the full-prompt
                # pad (ADVICE r5 #2). Longest match wins at admission,
                # prefixes are never removed, and register_prefix
                # refuses new ones that would strand a queued request —
                # so the CURRENT longest match decides the bound. The
                # RAGGED path never pads: prompts are chunked by the
                # per-tick token budget at arbitrary cut points, so the
                # only bound is prompt + budget (prefill_chunk is
                # ignored at ragged admission).
                pad = self._chunk_pad(T - hit[0].shape[0]) \
                    if hit is not None else self._chunk_pad(T)
            if max(T + max_new_tokens, T + pad) > self.max_cache_len:
                seg = "prefix-remainder" \
                    if hit is not None and self._prefill_chunk else "prompt"
                raise ValueError(
                    f"prompt ({T}) + max({max_new_tokens} new tokens, "
                    f"{pad} prefill-chunk pad rows on the {seg}) "
                    f"exceeds max_cache_len ({self.max_cache_len})")
            if self._kv is not None:
                # full-extent reservation (prompt + budget): a request
                # that can never fit must fail HERE, not stall the FIFO
                # forever — pool minus prefix-pinned pages, minus the
                # pinned pages this request would itself share. Ragged
                # mode matches through the tree: only the PINNED run is
                # stable enough to count at submit time (donated pages
                # can be evicted before admission).
                if self._ragged:
                    need = self._npages_for(T + int(max_new_tokens)) \
                        - self._pinned_run_pages(ids)
                else:
                    need = self._request_pages(ids, int(max_new_tokens),
                                               hit)
                usable = self._kv.num_pages - 1 \
                    - self._prefix.pinned_pages
                if need > usable:
                    raise ValueError(
                        f"prompt ({T}) + max_new_tokens "
                        f"({max_new_tokens}) needs {need} pages beyond "
                        f"its prefix hit but only {usable} are not "
                        f"pinned by prefixes — grow num_pages")
            if (self._max_queue is not None
                    and len(self._queue) >= self._max_queue):
                # evict_oldest with nobody to evict (max_queue=0) must
                # still shed SOMETHING — fall back to rejecting
                if self._shed_policy == "reject" or not self._queue:
                    if self._tele is not None:
                        self._tele.on_shed("reject")
                    raise QueueFullError(
                        f"queue holds {len(self._queue)} requests "
                        f"(max_queue={self._max_queue}); shed_policy="
                        f"'reject' — resubmit with backoff")
                old = self._queue.pop(0)
                err = QueueFullError(
                    f"request {old.rid} evicted by a newer submit "
                    f"(queue full at max_queue={self._max_queue}, "
                    f"shed_policy='evict_oldest')")
                self._failures[old.rid] = err
                if self._tele is not None:
                    self._tele.on_shed("evict_oldest")
                    self._tele.on_admission_failure(old.rid, err)
                self._note_request_failure_locked(old.rid, err,
                                                  old.journey,
                                                  bundle=False)
                self._done_cv.notify_all()
            rid = self._next_rid
            self._next_rid += 1
            if journey is None and self._jrec is not None:
                # standalone server: mint this request's own journey
                # ("s<rid>", location "server") so journey(rid) works
                # without a router; a router-supplied handle (above)
                # always wins — the fleet timeline stays singular
                journey = self._jrec.begin(f"s{rid}", where="server")
                journey.event("submitted", rid=rid,
                              prompt_tokens=int(T))
            if seed is None:
                # default-seed rule; remote.ReplicaHost._op_submit
                # reports the same value to its client mirror — keep
                # the two in sync (tests/test_remote_replica.py pins
                # the parity)
                seed = self._seed + rid
            deadline = None if deadline_s is None \
                else self._clock.now() + float(deadline_s)
            if priority:
                self._priority_seen = True
            self._queue.append(_Pending(rid, ids, int(max_new_tokens),
                                        int(seed), on_token, deadline,
                                        int(priority), journey))
            if self._tele is not None:
                self._tele.on_submit(rid, T, len(self._queue), lock_wait)
            if journey is not None:
                journey.event("queued", rid=rid, prompt_tokens=int(T))
        return rid

    @contextlib.contextmanager
    def _submit_lock(self):
        """The server's lock for ``submit()``. With telemetry on it
        yields how long the acquisition waited (a tick holds the lock
        while it runs), under a ``serve.submit`` annotation on the
        caller's thread; with telemetry off it is the bare lock."""
        tele = self._tele
        if tele is None:
            with self._lock:
                yield None
            return
        with jax.profiler.TraceAnnotation("serve.submit"):
            t = tele.clock.now()
            with self._lock:
                yield tele.clock.now() - t

    def cancel(self, rid):
        """Drop a request: un-queue it, or free its slot mid-decode (the
        partial result is recorded under the rid). Returns True if the
        request was found live."""
        with self._lock:
            return self._cancel_locked(rid)

    def _cancel_locked(self, rid):
        for i, item in enumerate(self._queue):
            if item.rid == rid:
                del self._queue[i]
                # a still-queued cancel produces no result; record the
                # typed failure so a blocked wait(rid) raises instead
                # of running out its timeout
                self._failures[rid] = RequestCancelled(
                    f"request {rid} cancelled while queued")
                if self._tele is not None:
                    self._tele.on_cancel(rid)
                    self._tele.set_queue_depth(len(self._queue))
                if self._rec is not None:
                    self._rec.record("cancel", rid=rid, where="queued")
                if item.journey is not None:
                    item.journey.event("cancelled")
                self._done_cv.notify_all()
                return True
        for slot in range(self.max_slots):
            st = self._slots[slot]
            if st is not None and st.rid == rid:
                # covers decoding AND mid-ragged-prefill slots (the
                # latter record an empty partial; their filled prefix
                # pages are still donated)
                if self._rec is not None:
                    self._rec.record("cancel", rid=rid,
                                     where="in_flight")
                if st.journey is not None:
                    st.journey.event("cancelled")
                self._finish_partial_locked(slot)
                if self._tele is not None:
                    self._tele.on_cancel(rid)
                    self._pool_gauges()
                # wake waiters NOW — without this a blocked wait(rid)
                # only notices the recorded partial at its next 1 s poll
                self._done_cv.notify_all()
                return True
        for i, rec in enumerate(self._preempted):
            if rec.rid == rid:
                # parked under pool pressure: mid-flight cancel
                # semantics — the pre-preemption partial is the result
                # (its pages were already donated/freed at preemption)
                del self._preempted[i]
                if self._rec is not None:
                    self._rec.record("cancel", rid=rid,
                                     where="preempted")
                self._flush_parked_locked(rec)
                if self._tele is not None:
                    self._tele.on_cancel(rid)
                    self._preempt_gauge()
                if rec.journey is not None:
                    rec.journey.event("cancelled")
                self._done_cv.notify_all()
                return True
        return False

    def _park_slot(self, slot):
        """Take ``slot`` out of the decoding set — THE owner of the idle
        sentinel. A slot that holds no decoding request (never used,
        finished, cancelled, expired, preempted, rolled back, paused
        for a migration, staged, or still prefilling) carries ``t =
        max_cache_len`` on the device: the table's span, which is what
        the tick programs compare against. Its decode rows then write
        to the null page, attend over nothing, select no key and join
        no expert's group (``models/generation.py``), so the tick pays
        for the slots that decode. The write rides ``_pending_t``, the
        state push the next decode dispatch makes; activation
        (``_activate``) and resume overwrite it with the real position."""
        self._active[slot] = False
        self._pending_t[slot] = self.max_cache_len

    def _release_slot(self, slot, cold=False):
        """Tear down a slot's host + page state (no result recording).
        Paged backend with auto prefix caching: the request's full
        prompt pages are DONATED into the radix tree (future prompts
        sharing the prefix auto-hit them; eviction reclaims them under
        pressure) instead of being freed; everything else — partial
        prompt tail, decode budget — returns to the free list. An
        injected ``prefix.donate`` fault abandons the insert and the
        pages are simply freed: donation is best-effort cache
        maintenance, never a correctness or leak risk. ``cold=True``
        (preemption teardown) donates at the cold end of the LRU so
        the grow that displaced this slot reclaims its pages first."""
        st = self._slots[slot]
        self._park_slot(slot)
        self._slots[slot] = None
        if slot in self._prefill_fifo:
            self._prefill_fifo.remove(slot)
        if self._kv is None:
            return
        pages = self._kv.detach_slot(slot)
        if not pages:
            return
        if self._auto_prefix and st is not None:
            try:
                # only prompt rows actually WRITTEN are donated: a slot
                # torn down mid-ragged-prefill (deadline, cancel, fault)
                # caches its filled prefix, never unwritten pages
                n_known = min(st.prompt_len, st.filled)
                new = self._prefix.donate(st.ids, pages, n_known,
                                          cold=cold)
            except Exception:
                self._kv.release(pages)
            else:
                if new and self._tele is not None:
                    self._tele.on_prefix_donate(new)
                if new and self._rec is not None:
                    self._rec.record("donate", rid=st.rid, pages=new,
                                     cold=cold)
        else:
            self._kv.release(pages)

    def _finish_partial_locked(self, slot):
        """Record the slot's partial tokens as its rid's RESULT and tear
        the slot down — the one way a live request leaves early with its
        output kept (cancel, deadline expiry, hard stop). A resumed
        slot's partial is the LONGER of its pre-preemption tokens and
        the replay so far (never fewer tokens than already streamed)."""
        st = self._slots[slot]
        self._results[st.rid] = np.asarray(st.partial()[:st.budget],
                                           np.int32)
        if self._rec is not None:
            self._rec.record("flush", rid=st.rid,
                             tokens=len(self._results[st.rid]))
        if st.journey is not None:
            st.journey.event("flushed",
                             tokens=len(self._results[st.rid]))
        self._release_slot(slot)
        return st

    # ---------------------------------------------------- paged backend
    def _fill_pages(self, caches1, pages, start):
        """Scatter dense batch-1 cache rows [start, start + len(pages) *
        page_size) into the pool at ``pages`` (position order)."""
        if not pages:
            return
        from ..models.generation import pool_lanes
        pg = self._kv.page_size
        n = len(pages) * pg
        ids = jnp.asarray(np.asarray(pages, np.int32))

        def seg(c):            # [L, 1, T', h, hd] -> [L, npg, pg, h*hd]
            s = pool_lanes(c[:, 0, start:start + n])
            return s.reshape(s.shape[0], len(pages), pg, s.shape[2])

        pool = {name: leaf.at[:, ids].set(
                    seg(caches1[name]).astype(leaf.dtype))
                for name, leaf in self._caches["pool"].items()}
        self._caches = dict(self._caches, pool=pool)

    def _seed_from_pages(self, pages):
        """Inverse of ``_fill_pages``: gather cached pool pages back
        into a dense batch-1 cache covering [0, len(pages) *
        page_size) — the auto-hit remainder prefill attends to these
        rows. The decode program reads the SAME pages through the block
        table, so the pool copy stays the single source of truth.
        DENSE prefill mode only: the ragged path attends over cached
        pages through the block table directly, so an auto hit costs
        zero extra dispatches (BENCHNOTES Round 7 measured this
        gather→dense→scatter round-trip exceeding the saved FLOPs on
        small models)."""
        from ..models.generation import pool_heads
        pg = self._kv.page_size
        n = len(pages) * pg
        idx = jnp.asarray(np.asarray(pages, np.int32))
        base = self._init_caches(1)

        def take(name, pool, dense):   # [L, P, pg, h*hd] -> dense rows
            s = pool[:, idx]
            s = pool_heads(s.reshape(s.shape[0], 1, n, s.shape[3]),
                           self._leaf_heads[name])
            return dense.at[:, :, :n].set(s.astype(dense.dtype))

        pool = self._caches["pool"]
        if self._costs is not None:    # byte model priced lazily: the
            # pool flatten must not run on the costs=None path
            self._charge_transfer("page_gather",
                                  2 * n * self._row_nbytes())
        return {name: take(name, leaf, base[name])
                for name, leaf in pool.items()}

    def _spill_payload(self, page):
        """One pool page's rows as host numpy arrays, one a pool leaf
        by leaf NAME in sorted order (``k`` and ``v`` ``[L, pg, kvh,
        hd]``; between them ``ki``, a key-selecting model's indexer
        keys ``[L, pg, 1, dim]``) — the demotion gather ``PrefixCache.evict``
        routes through the host tier, and the wire format of migration
        and handoff. On a sharded pool the gather goes PER SHARD: each
        device ships only its kv-head slice (``addressable_shards``,
        ordered by their offset on the pool's merged head axis) and
        the slices concatenate on that axis — never a full-pool
        replication bounce (the PR-14 gap). Runs inside an allocator
        reclaim under the server lock, off the tick path."""
        from ..models.generation import pool_heads
        page = int(page)
        out = []
        pool = self._caches["pool"]
        for name in sorted(pool):    # THE payload order: leaf names sorted
            leaf = pool[name]
            rows = None
            if self._pool_shards > 1:
                try:
                    shards = sorted(leaf.addressable_shards,
                                    key=lambda s: s.index[3].start or 0)
                    rows = np.concatenate(
                        [np.asarray(s.data[:, page]) for s in shards],
                        axis=2)
                except Exception:
                    pass       # runtime hid the buffers: global gather
            if rows is None:
                rows = np.asarray(jax.device_get(leaf[:, page]))
            out.append(pool_heads(rows, self._leaf_heads[name]))
        return out

    def _write_pages(self, pages, payloads):
        """Scatter page payloads (``_spill_payload``'s format: one
        array ``[L, pg, heads, hd]`` a pool leaf a page) into pool pages
        ``pages`` — one batched ``.at[:, idx].set`` per leaf. On a
        sharded pool the host rows are laid out against the pool's own
        sharding first (``jax.device_put`` with the leaf's sharding —
        each device receives only its kv-head slice): the mirror of
        the spill's per-shard gather."""
        from ..models.generation import pool_lanes
        idx = jnp.asarray(np.asarray(pages, np.int32))
        pool = dict(self._caches["pool"])
        for j, name in enumerate(sorted(pool)):   # _spill_payload's order
            leaf = pool[name]
            # [L, n, pg, kvh*hd]: page payloads stacked on a new pages
            # axis, matching leaf[:, idx]
            val = pool_lanes(np.stack([p[j] for p in payloads], axis=1))
            val = val.astype(leaf.dtype)
            if self._pool_shards > 1:
                try:
                    val = jax.device_put(val, leaf.sharding)
                except Exception:
                    pass
            pool[name] = leaf.at[:, idx].set(jnp.asarray(val))
        self._caches = dict(self._caches, pool=pool)

    def _restore_match(self, m):
        """Restore a tree match's host-resident suffix into freshly
        allocated pool pages so admission can take the WHOLE run by
        reference through the normal ``admit_slot``/refcount path —
        a restored run is bit-exact with a never-evicted one. Returns
        a fresh all-hot ``PrefixMatch`` over the same nodes (possibly
        trimmed to the hot prefix), or None when nothing survives.
        Any failure is a MISS for the affected pages, never a request
        failure: an injected ``tier.restore`` fault leaves the run
        spilled for a later attempt, a checksum mismatch forgets the
        corrupt node (and its all-host subtree) for good, and an
        OutOfPages trims to the hot prefix.

        The scatter is ``_write_pages`` (per shard on a sharded
        pool) — the restore mirror of the spill gather."""
        from .prefix_cache import PrefixMatch
        nodes = m.nodes
        hot = m.hot_len()
        if hot == len(nodes):
            return m
        tele = self._tele
        t0 = tele.restore_started() if tele is not None else None
        payloads, restoring, n_restored = [], [], 0
        for nd in nodes[hot:]:
            try:
                payload = self._host.get(nd.host, fp=nd.fp)
            except Exception:
                break          # transient (injected) miss: run stays
            #                    spilled, nodes intact for retry
            if payload is None:
                # checksum mismatch: the payload is unservable — drop
                # the node and everything under it so the corrupt
                # entry can never be matched again
                if tele is not None:
                    tele.on_host_restore_corrupt()
                if self._rec is not None:
                    self._rec.record("restore_corrupt", fp=nd.fp)
                self._prefix.drop_subtree(nd)
                break
            payloads.append(payload)
            restoring.append(nd)
        if restoring:
            # fresh pages for the suffix: protect the whole run across
            # the alloc — its reclaim sweep must not demote the hot
            # prefix (not yet referenced by a slot) or shrink away the
            # very entries being restored
            self._prefix.protect(nodes[:hot] + restoring)
            try:
                fresh = self._kv.alloc(len(restoring))
            except Exception:
                fresh = None   # pool exhausted even after reclaim:
            finally:           # serve the hot prefix only
                self._prefix.protect(())
            if fresh is not None:
                self._write_pages(fresh, payloads)
                for nd, page in zip(restoring, fresh):
                    self._prefix.promote(nd, page)
                if self._costs is not None:
                    # priced like the gather/scatter detours: bytes
                    # moved both ways, zero FLOPs — and NOT a tick
                    # dispatch (restores must not count in the
                    # serving_tick_dispatches profile)
                    self._charge_transfer(
                        "page_restore",
                        2 * len(fresh) * self._kv.page_size
                        * self._row_nbytes())
                if self._rec is not None:
                    self._rec.record("restore", pages=len(fresh))
                n_restored = len(fresh)
                hot += n_restored
        if tele is not None:
            tele.on_host_restore(n_restored, t0)
        if hot == 0:
            return None
        return PrefixMatch(nodes[:hot], self._kv.page_size)

    def _sync_block_table(self):
        """Push the host block-table mirror to the device copy the
        decode program reads. Same shape every time — page churn never
        triggers a recompile."""
        if self._kv is not None and self._kv.dirty:
            self._caches = dict(self._caches,
                                bt=jnp.asarray(self._kv.block_table))
            self._kv.dirty = False
            self._tick_dispatch("block_table")
            self._charge_transfer("block_table",
                                  2 * self._kv.block_table.nbytes)

    def _shard_pool_bytes(self):
        """Pool bytes (every leaf) actually RESIDENT on one shard's device —
        measured off the live arrays (an addressable shard's buffer),
        not derived, so a placement bug (pool silently replicated when
        it should shard) shows up as 1x instead of 1/mp. Falls back to
        global bytes / shards where the runtime hides buffers. The pool
        shape and placement are fixed for the server's lifetime, so the
        first measurement is memoized — this rides the per-tick gauge
        path."""
        if self._kv is None:
            return None
        memo = getattr(self, "_shard_bytes_memo", None)
        if memo is not None:
            return memo
        leaves = list(self._caches["pool"].values())
        try:
            memo = int(sum(leaf.addressable_shards[0].data.nbytes
                           for leaf in leaves))
        except Exception:
            memo = int(sum(leaf.nbytes for leaf in leaves)
                       // max(1, self._pool_shards))
        self._shard_bytes_memo = memo
        return memo

    def _pool_gauges(self):
        """Refresh the page-pool occupancy gauges (paged backend)."""
        if self._tele is not None and self._kv is not None:
            used = self._kv.used_pages()
            pinned = self._prefix.pinned_pages
            cached = self._prefix.cached_pages
            self._tele.set_pool(self._kv.free_pages(),
                                used - pinned - cached, pinned, cached,
                                self._prefix.host_pages)
            self._tele.set_pool_shards(self._pool_shards,
                                       self._shard_pool_bytes())

    def pool_balance(self):
        """``PoolBalance`` — a ``(free, live, pinned, cached)`` tuple
        of page counts summing to the usable pool (``num_pages - 1``;
        page 0 is the null page): ``live`` pages belong to decoding
        slots, ``pinned`` to registered prefixes (never evicted),
        ``cached`` to the auto prefix cache (evictable LRU). Chaos
        suites assert ``live == 0`` once drained — free + pinned +
        cached then covers the whole pool and no injected failure
        leaked a page. Optimistic-admission state rides as ATTRIBUTES
        (``.preempted`` parked requests, ``.preemptions`` cumulative
        victims) so existing 4-way unpacks keep working. Dense backend
        returns None."""
        if self._kv is None:
            return None
        with self._lock:
            free = self._kv.free_pages()
            pinned = self._prefix.pinned_pages
            cached = self._prefix.cached_pages
            live = self._kv.used_pages() - pinned - cached
            shards = self._pool_shards
            per_shard = ()
            if shards > 1:
                # kv-head sharding splits every page across ALL shards
                # equally, so each shard's page counts equal the
                # globals — the view makes that balance assertable
                per_shard = tuple(
                    {"free": free, "live": live, "pinned": pinned,
                     "cached": cached} for _ in range(shards))
            return PoolBalance(free, live, pinned, cached,
                               preempted=len(self._preempted),
                               preemptions=self.stats["preemptions"],
                               num_shards=shards, per_shard=per_shard,
                               shard_page_bytes=self._shard_pool_bytes(),
                               host=self._prefix.host_pages,
                               host_bytes=self._host.bytes_used
                               if self._host is not None else 0)

    def _reclaim_pages(self, shortfall):
        """``PagedKVCache.alloc``'s reclaimer: evict LRU cached prefix
        pages when the free list runs short. An injected
        ``prefix.evict`` fault aborts THIS sweep — alloc then raises
        OutOfPages and admission defers to the next tick; either way
        no page leaks and no request fails. With a host tier the
        sweep DEMOTES instead of dropping: spills are counted (and
        priced — ``page_spill``, 2x bytes moved, never a tick
        dispatch) here by diffing the tier's totals across the sweep,
        so the eviction metrics split into spilled vs dropped."""
        tier = self._host
        s0 = tier.spilled_pages_total if tier is not None else 0
        try:
            freed = self._prefix.evict(shortfall)
        except Exception:
            return 0
        spilled = tier.spilled_pages_total - s0 \
            if tier is not None else 0
        if spilled:
            if self._tele is not None:
                self._tele.on_host_spill(spilled)
            if self._rec is not None:
                self._rec.record("spill", pages=spilled)
            if self._costs is not None:
                self._charge_transfer(
                    "page_spill",
                    2 * spilled * self._kv.page_size
                    * self._row_nbytes())
        dropped = freed - spilled
        if dropped and self._tele is not None:
            self._tele.on_prefix_evict(dropped)
        if freed and self._rec is not None:
            self._rec.record("evict", pages=freed)
        return freed

    def _best_hit(self, ids):
        """The longest reusable prefix state for ``ids``: the
        registered match (dense rows + final logits, token-exact
        length) vs the radix tree's page-aligned cached run — whichever
        covers more tokens. Returns ``("reg", entry)``, ``("tree",
        PrefixMatch)``, or None. A tree match is trimmed page-by-page
        until the remainder's prefill-chunk pad still fits
        ``max_cache_len`` (submit() bound-checked the pad against the
        hits known THEN; the tree moves underneath queued requests),
        and capped one token short of the prompt — the remainder
        prefill must emit the first-token logits.

        RAGGED mode matches through the tree alone: register_prefix
        entries already live in it as pinned nodes, so a registered hit
        reuses its page-aligned run (the sub-page tail re-prefills with
        the remainder — recomputation is deterministic, tokens are
        unchanged) and the stored dense rows are never touched. No
        chunk-pad trim either: ragged remainders never pad."""
        if self._ragged:
            T = int(ids.shape[0])
            tree = self._prefix.lookup(ids, T - 1)
            return None if tree is None else ("tree", tree)
        reg = self._match_prefix(ids)
        best = None if reg is None else ("reg", reg)
        if self._auto_prefix:
            T = int(ids.shape[0])
            tree = self._prefix.lookup(ids, T - 1)
            while tree is not None and \
                    T + self._chunk_pad(T - tree.tokens) \
                    > self.max_cache_len:
                tree = tree.shrink()
            reg_n = reg[0].shape[0] if reg is not None else 0
            if tree is not None and tree.tokens > reg_n:
                best = ("tree", tree)
        return best

    def _pinned_run_pages(self, ids):
        """Pages of the PINNED (register_prefix) tree run this prompt
        would share — the stable floor on page reuse a ragged-mode
        submit may count (capped at T-1 like ``_best_hit``'s lookup, so
        the remainder prefill keeps its first-token row)."""
        T = int(ids.shape[0])
        aligned = (T - 1) // self._kv.page_size * self._kv.page_size
        n = 0
        for nd in self._prefix.node_run(ids[:aligned]):
            if not nd.pinned:
                break
            n += 1
        return n

    def _request_pages(self, ids, budget, hit):
        """Fresh pages a request needs for its FULL extent (prompt +
        budget — reserved at admission so decode-time growth can never
        hit an empty pool mid-flight), net of the shared pages of
        ``hit`` (the caller's ``_match_prefix`` result)."""
        shared = len(hit[3]) if hit is not None else 0
        return self._npages_for(ids.shape[0] + budget) - shared

    def _extent_tokens(self, T, budget):
        """Tokens' worth of pages admission reserves for a request.
        ``admission="reserve"``: the FULL extent (prompt + budget), so
        decode can never hit an empty pool mid-flight.
        ``"optimistic"``: the prompt plus ``headroom_pages`` worth —
        decode grows page-by-page on demand (``_grow_locked``) and the
        preemption policy settles the bill when the gamble loses."""
        if self._optimistic:
            return min(T + self._headroom_pages * self.page_size,
                       T + budget)
        return T + budget

    def _head_fits_pool(self, head, best):
        """Can the pool admit ``head`` (the chosen admission candidate)
        right now? If not it (and everything behind it in admission
        order) waits for a harvest to free pages. Evictable
        prefix-cache pages count as available headroom (alloc reclaims
        them on demand) — minus the nodes the head's own cache hit
        (``best``, computed once per admission attempt and shared with
        the admit) is about to take by reference, which obviously
        cannot be evicted to make room for it. Optimistic admission
        only asks for the prompt + headroom reservation here."""
        if best is None:
            shared, nodes = 0, ()
        elif best[0] == "reg":
            shared, nodes = len(best[1][3]), ()
        else:
            # only the HOT prefix is shared by reference; a
            # host-resident suffix needs fresh pool pages (the restore
            # allocates them before admit_slot), so it counts toward
            # need exactly like prefilling those tokens would
            hot = best[1].hot_len()
            shared, nodes = hot, best[1].nodes[:hot]
        need = self._npages_for(
            self._extent_tokens(head.ids.shape[0], head.budget)) - shared
        avail = self._kv.free_pages() \
            + self._prefix.evictable_pages(exclude=nodes)
        return avail >= need

    def _npages_for(self, n_tokens):
        return -(-int(n_tokens) // self._kv.page_size)

    def _skipped_dma(self, live_tokens):
        """The goodput ledger's host-side MODEL of one slot's masked
        page traffic in one launch: the ragged prefill kernel's grid
        (ROADMAP A2b-c) and the decode step's XLA fallback cover the
        full block-table width, so every page wholly beyond the slot's
        live length is read but masked — ``(table_width -
        ceil(live/pg)) * pg`` token-equivalents; this is the ONE
        definition both the decode and prefill hooks charge. The decode
        KERNEL steps over live pages only (``decode_grid_steps``
        counts them): on the chip the decode hook's charge models the
        fallback, not the kernel."""
        live = -(-int(live_tokens) // self.page_size)
        return max(0, self._bt_pages - live) * self.page_size

    # -------------------------------------------- admission scheduling
    def _next_admission_locked(self):
        """``(item, source)`` of the next admission candidate, or
        ``(None, None)``. Reserve mode: strict FIFO — the queue head.
        Optimistic mode: PRIORITY-AWARE FIFO — highest priority class
        first, then original submit order (rid), in one order across
        the preempted queue and the main queue; a preempted request
        keeps its original rid, so at equal priority it re-enters
        ahead of later arrivals. ``source`` is the pop/defer handle."""
        if not self._optimistic \
                or (not self._priority_seen and not self._preempted):
            # reserve mode, or optimistic with every priority at the
            # default and nothing parked: the priority-aware order IS
            # rid order, so skip the O(queue) scan per admission (the
            # common case keeps the reserve path's O(1) head peek)
            if not self._queue:
                return None, None
            return self._queue[0], ("queue", 0)
        best, src = None, None
        for where, items in (("queue", self._queue),
                             ("preempted", self._preempted)):
            for i, item in enumerate(items):
                if best is None or (-item.priority, item.rid) \
                        < (-best.priority, best.rid):
                    best, src = item, (where, i)
        return best, src

    def _pop_admission_locked(self, src):
        where, i = src
        items = self._queue if where == "queue" else self._preempted
        item = items.pop(i)
        if where == "preempted":
            self._preempt_gauge()
        return item

    def _defer_admission_locked(self, src, item):
        """Put a popped candidate back where it came from (an admission
        attempt rolled back — OutOfPages defer)."""
        where, i = src
        (self._queue if where == "queue"
         else self._preempted).insert(i, item)
        if where == "preempted":
            self._preempt_gauge()

    def _preempt_gauge(self):
        if self._tele is not None:
            self._tele.set_preempted_depth(len(self._preempted))

    def _flush_parked_locked(self, rec):
        """Record a parked record's pre-preemption partial as its
        rid's RESULT — the one way a preempted request leaves the
        parked queue without decode resuming (cancel, deadline expiry,
        hard stop, dead-replica evacuation). The caller removes the
        record from ``_preempted`` and handles telemetry/notify."""
        self._results[rec.rid] = np.asarray(rec.emitted[:rec.budget],
                                            np.int32)
        if self._rec is not None:
            self._rec.record("flush", rid=rec.rid,
                             tokens=len(self._results[rec.rid]),
                             parked=True)
        if rec.journey is not None:
            rec.journey.event("flushed",
                              tokens=len(self._results[rec.rid]),
                              parked=True)

    # ------------------------------------------------------- scheduling
    def _admit(self, run_prefill=True):
        """Fill free slots from the queue. Dense prefill mode: one
        dense batch-1 prefill program per admission (the PR-5 path).
        Ragged mode: admissions only RESERVE their slot + full page
        extent here (cheap, host-side); the actual prompt chunks run
        batched in ``_prefill_tick`` — several admissions, one launch,
        straight into pool pages — interleaved with decode under the
        per-tick token budget. A request whose admission raises is
        recorded in ``_failures`` (its waiters get the error) instead
        of killing the serve thread or losing the rest of the queue
        (ADVICE r5 #2)."""
        if self._ragged:
            self._admit_ragged(run_prefill)
            return
        admitted = 0
        for slot in range(self.max_slots):
            if self._slots[slot] is not None:
                continue
            if self._admit_cap is not None and admitted >= self._admit_cap:
                break
            item, src = self._next_admission_locked()
            if item is None:
                break
            # one _best_hit per admission attempt: the radix walk (and
            # registered-prefix scan) feeds the fits check AND the
            # admission itself — same lock, same tick, the tree cannot
            # move between the two
            best = self._best_hit(item.ids)
            if self._kv is not None \
                    and not self._head_fits_pool(item, best):
                break
            req = self._pop_admission_locked(src)
            rid = req.rid
            if self._tele is not None:
                self._tele.on_admit(rid, len(self._queue))
            try:
                self._admit_one(slot, req, best)
            except OutOfPages:
                # eviction could not free enough right now (an injected
                # ``prefix.evict`` fault aborted the sweep, or a cache
                # hit shrank the headroom mid-admission): roll back and
                # DEFER — the request returns to the head of the queue
                # (FIFO preserved) and is retried next tick, it does
                # NOT fail
                if self._kv is not None and self._kv.slot_pages(slot):
                    self._kv.free_slot(slot)
                self._park_slot(slot)
                self._slots[slot] = None
                self._defer_admission_locked(src, req)
                if self._tele is not None:
                    self._tele.on_admission_deferred(rid,
                                                     len(self._queue))
                if self._rec is not None:
                    self._rec.record("defer", rid=rid)
                if req.journey is not None:
                    req.journey.event("deferred")
                break
            except Exception as e:
                if self._kv is not None and self._kv.slot_pages(slot):
                    self._kv.free_slot(slot)     # roll back a part-admit
                self._park_slot(slot)
                self._slots[slot] = None
                self._failures[rid] = e
                if self._tele is not None:
                    self._tele.on_admission_failure(rid, e)
                self._note_request_failure_locked(rid, e, req.journey)
                self._done_cv.notify_all()
            else:
                admitted += 1
        if self._tele is not None:
            self._pool_gauges()

    def _admit_ragged(self, run_prefill=True):
        """Ragged-mode scheduling pass: pop queued requests into free
        slots (reservation only — ``admit_slot`` takes the full
        prompt + budget extent, shared cache-hit pages by reference),
        then run one batched ragged prefill launch over every slot with
        prompt rows still to write. OutOfPages DEFERS the head request
        exactly like the dense path; nothing is prefilled for a
        deferred reservation, so counters see each admission once."""
        admitted = 0
        for slot in range(self.max_slots):
            if self._admit_cap is not None and admitted >= self._admit_cap:
                break
            if self._slots[slot] is not None:
                continue
            item, src = self._next_admission_locked()
            if item is None:
                break
            best = self._best_hit(item.ids)
            if not self._head_fits_pool(item, best):
                break
            req = self._pop_admission_locked(src)
            if self._tele is not None:
                self._tele.on_admit(req.rid, len(self._queue))
            try:
                self._reserve_one(slot, req, best)
            except OutOfPages:
                # eviction could not free enough right now (an injected
                # ``prefix.evict`` fault aborted the sweep): the request
                # returns to the head of the queue (FIFO preserved) and
                # is retried next tick — admit_slot rolled its own
                # shared-page refs back, nothing was prefilled
                self._defer_admission_locked(src, req)
                if self._tele is not None:
                    self._tele.on_admission_deferred(req.rid,
                                                     len(self._queue))
                if self._rec is not None:
                    self._rec.record("defer", rid=req.rid)
                if req.journey is not None:
                    req.journey.event("deferred")
                break
            except Exception as e:
                if self._kv.slot_pages(slot):
                    self._kv.free_slot(slot)     # roll back a part-admit
                self._park_slot(slot)
                self._slots[slot] = None
                if slot in self._prefill_fifo:
                    self._prefill_fifo.remove(slot)
                self._failures[req.rid] = e
                if self._tele is not None:
                    self._tele.on_admission_failure(req.rid, e)
                self._note_request_failure_locked(req.rid, e,
                                                  req.journey)
                self._done_cv.notify_all()
            else:
                admitted += 1
        if run_prefill:
            self._prefill_tick()
        if self._tele is not None:
            self._pool_gauges()

    def _reserve_one(self, slot, req, best):
        """Reserve ``slot`` for ``req``: full-extent page reservation
        (prompt + budget, cache-hit pages joined by reference) and a
        prefill-phase slot record. No device work happens here — the
        prompt's chunks run in ``_prefill_tick`` launches."""
        if self._faults is not None:
            # chaos failure point: an admission that dies is a
            # PER-REQUEST failure (_admit_ragged records it), never a
            # server one — and it fires BEFORE the reservation, so no
            # pages need rolling back
            self._faults.check(faults.PREFILL, rid=req.rid)
        ids = req.ids
        T = ids.shape[0]
        if best is not None and best[0] == "tree" \
                and self._host is not None:
            # the match may carry a host-resident suffix: restore it
            # into fresh pool pages FIRST so admit_slot below shares
            # the whole run by reference like any hot hit (a failed
            # restore just trims the match — prefill covers the rest)
            m = self._restore_match(best[1])
            best = None if m is None else ("tree", m)
        if best is not None:
            m = best[1]
            n_pre, pre_pages = m.tokens, m.pages
        else:
            m, n_pre, pre_pages = None, 0, []
        self._kv.admit_slot(slot, self._extent_tokens(T, req.budget),
                            pre_pages)
        self._count_headroom(slot, T)
        if m is not None:
            self._prefix.use(m)               # LRU: reuse is recency
            # attribution: pinned nodes are register_prefix state (the
            # run's head — extend_pinned pins whole root paths), the
            # unpinned tail is the automatic cache's
            n_auto = n_pre - sum(1 for nd in m.nodes if nd.pinned) \
                * self._kv.page_size
        else:
            n_auto = 0
        self.stats["prefix_hit_tokens"] += n_pre
        if n_auto:
            self.stats["prefix_auto_hits"] += 1
            self.stats["prefix_auto_hit_tokens"] += n_auto
        if self._tele is not None and self._auto_prefix:
            self._tele.on_prefix_auto(n_auto > 0, n_auto)
        st = _Slot(req.rid, ids, T, req.budget, req.on_token,
                   req.deadline)
        st.phase = "prefill"
        st.fill_pos = st.filled = n_pre
        st.n_pre = n_pre
        st.seed = req.seed
        if self._led is not None:
            # ragged matching is page-granular, so a registered
            # prefix's sub-page tail re-prefills with the remainder —
            # the ledger's tail_reprefill kind. The longest registered
            # match decides; rows below reprefill_upto that the prefill
            # launches are recomputation of registered state
            reg = self._match_prefix(ids)
            if reg is not None and reg[0].shape[0] > n_pre:
                st.reprefill_upto = int(reg[0].shape[0])
        self._bind_request(st, req, slot)
        self._slots[slot] = st
        self._prefill_fifo.append(slot)
        # parked until activation: its decode rows must not write into
        # the pages being prefilled
        self._park_slot(slot)

    def _bind_request(self, st, req, slot):
        """Carry the request's scheduling state onto its slot. A
        RESUMED (previously preempted) request keeps its stream offset
        (on_token never re-sends delivered chunks — the replay is
        bit-identical below it), its pre-preemption partial (flushed if
        it must leave early again), and its preemption count. Also the
        observability funnel for admissions: one flight-recorder event
        and one journey phase per (re)admission, ``replay`` when the
        request came off the preempted queue."""
        st.priority = req.priority
        st.journey = req.journey
        resumed = isinstance(req, _Preempted)
        if resumed:
            st.streamed = req.streamed
            st.replayed = tuple(req.emitted)
            st.preempts = req.preempts
            self.stats["preempt_resumed"] += 1
            if self._tele is not None:
                self._tele.on_preempt_resumed()
        if self._rec is not None:
            self._rec.record("replay" if resumed else "admit",
                             rid=st.rid, slot=slot,
                             prompt=st.prompt_len, prefix_hit=st.n_pre)
        if st.journey is not None:
            st.journey.event("replay" if resumed else "admitted",
                             slot=slot, prefix_hit=st.n_pre)

    def _count_headroom(self, slot, T):
        """Account the pages an optimistic admission reserved BEYOND
        the prompt (its pre-paid growth headroom)."""
        if not self._optimistic:
            return
        hr = len(self._kv.slot_pages(slot)) - self._npages_for(T)
        if hr > 0:
            self.stats["headroom_pages"] += hr
            if self._tele is not None:
                self._tele.add_headroom_pages(hr)

    def _prefill_tick(self):
        """Run one batched ragged prefill launch: the next chunk of
        every mid-prefill slot (head-of-FIFO first — Sarathi-style, the
        oldest admission completes soonest), bounded by the per-tick
        token budget so a long prompt cannot stall in-flight decode
        ticks. Chunk width C is padded up a power-of-two ladder (min 2:
        single-row matmuls take XLA's fused-reduce path and break
        bit-parity with the dense prefill) so compiles stay
        O(log max_cache_len). The launch is PACKED: row j is the
        plan's j-th slot, and width C has ``R // C`` rows (at most a
        row a slot, at least one), so one program a width. ``R`` is
        ``_launch_row_limit`` of the per-tick budget: a launch carries
        at most the budget's tokens, so the power of two over it is
        the smallest limit that never shortens a take (``C <= R``, the
        head of the FIFO always fits), and its rows are what the
        budget can fill, not a constant (4,096, which stays as the
        ceiling of ``_launch_row_limit``) four times that."""
        budget = self._prefill_budget - self._prefill_used
        if not self._prefill_fifo or budget <= 0:
            return
        b = self._boundary
        if b is not None:
            b.mark("prefill_pack")
        plan = []                        # (slot, start, take)
        used = widest = 0
        S, R = self.max_slots, self._launch_rows
        width = lambda take: max(2, 1 << (take - 1).bit_length())
        for slot in self._prefill_fifo:
            if used >= budget:
                break
            st = self._slots[slot]
            take = min(st.prompt_len - st.fill_pos, budget - used)
            # a launch computes every one of its rows, so it has
            # R // C of them at width C, and a slot that would not
            # fit waits for the next launch
            C = width(max(widest, take))
            if plan and (len(plan) + 1) * C > R:
                break
            plan.append((slot, st.fill_pos, take))
            used, widest = used + take, max(widest, take)
        if not plan:
            return
        self._prefill_used += used
        C = width(widest)
        # row j is the plan's j-th slot; the rest are padding rows: no
        # slot's (slot S), parked on the idle sentinel
        P = min(S, max(1, R // C))
        toks = np.zeros((P, C), np.int32)
        t0 = np.full((P,), self.max_cache_len, np.int32)
        out_idx = np.zeros((P,), np.int32)
        takes = np.zeros((P,), np.int32)
        slots = np.full((P,), S, np.int32)
        done = []                        # (slot, row)
        for row, (slot, start, take) in enumerate(plan):
            st = self._slots[slot]
            toks[row, :take] = st.ids[start:start + take]
            t0[row], takes[row], slots[row] = start, take, slot
            if start + take == st.prompt_len:
                out_idx[row] = take - 1
                done.append((slot, row))
        self._sync_block_table()
        args = (jnp.asarray(toks), jnp.asarray(t0), self._caches,
                jnp.asarray(out_idx), jnp.asarray(takes),
                jnp.asarray(slots))
        prefill_fn = self._ragged_fn
        if self._costs is not None:
            # one priced program per chunk width on the pow2 ladder —
            # a width first seen AFTER warmup is exactly the recompile
            # the watch exists to surface
            prefill_fn = self._cost_program(
                self._cost_op("prefill"), self._ragged_fn, args)
        if b is not None:
            # the host's until the call returns (building and enqueueing
            # the launch), then a wait for the first value read back (in
            # _activate, which marks "activate"). A launch that
            # completes no prompt reads nothing back: blocked=0, its
            # device time is waited for in a later phase
            launch = dict(
                width=C, rows=len(plan), launch_rows=P * C,
                rids=[self._slots[slot].rid for slot, _, _ in plan])
            self._fresh_launch = (C, P) not in self._launch_shapes
            if self._fresh_launch:
                self._launch_shapes.add((C, P))
            t_launch = b.mark("prefill_dispatch", **launch)
        logits, self._caches = prefill_fn(*args)
        self._unawaited += 1
        if b is not None:
            b.mark("prefill_wait", blocked=int(bool(done)), **launch)
        self._count_dispatches(1, op="prefill")
        carried = sum(1 for _, start, _ in plan if start > 0)
        self.stats["prefill_chunks"] += len(plan)
        self.stats["prefill_chunks_carried"] += carried
        self.stats["prefill_rows"] += P * C
        if self._tele is not None:
            self._tele.on_prefill_chunks(len(plan), carried, P * C)
        if not self._select_k:
            self._count_prefill_grid(t0, takes, C)
        if self._moe_k:
            # the experts compute the rows of the slots in the plan;
            # a padding row rides this launch on the sentinel
            self._count_routed(None, used, len(plan) * C)
        led = self._led
        for slot, start, take in plan:
            st = self._slots[slot]
            st.fill_pos = st.filled = start + take
            self.stats["prefill_tokens"] += take
            if led is not None:
                # the launch runs C query rows for each participating
                # slot (padding rows are kernel-skipped): `take` real
                # rows + pow2-ladder pad, and maxp page DMAs of which
                # only the covered prefix is unmasked
                if st.preempts:
                    # a resumed request's prompt re-prefill is pure
                    # preemption recompute, whatever rows it covers
                    led.add("replay", take)
                else:
                    tail = max(0, min(start + take,
                                      st.reprefill_upto) - start)
                    led.add("tail_reprefill", tail)
                    led.add("goodput", take - tail)
                led.add("chunk_pad", C - take)
                led.add("skipped_page_dma",
                        self._skipped_dma(start + take))
            if st.journey is not None:
                st.journey.event("prefill_chunk", start=start,
                                 take=take)
        for slot, row in done:
            self._activate(slot, logits[row:row + 1])
        if b is not None:
            # only a launch the host waited for has a wall: dispatch to
            # its last activation
            wall = b.mark("admit") - t_launch
            if done:
                self.stats["prefill_wall_s"] += wall
            else:
                wall = None
            if self._tele is not None:
                self._tele.on_prefill_batch(wall, width=C)

    def _activate(self, slot, logits):
        """A slot's prompt is fully written: draw its first token from
        the ragged launch's logits row (same PRNG chain and logit ops
        as the dense path — bit-identical draws) and flip it into the
        decode phase."""
        st = self._slots[slot]
        key = jax.random.PRNGKey(st.seed)
        if self.do_sample:
            # same split pattern as sample_generate.run: one split,
            # sample tok0 from the [1, V] prefill logits row
            key, sub = jax.random.split(key)
            from .decode_loop import process_logits
            first = int(jax.random.categorical(
                sub, process_logits(logits, self._temperature,
                                    self._top_k, self._top_p),
                axis=-1)[0])
        else:
            first = int(jnp.argmax(logits, -1)[0])
        b = self._boundary
        if b is not None and b.phase == "prefill_wait":
            # the launch's first value is back on the host: from here
            # the chip is idle (later draws are tiny programs)
            b.mark("activate")
        self._unawaited = 0
        self._pending_key[slot] = key
        self._pending_tok[slot] = first
        self._pending_t[slot] = st.prompt_len
        st.phase = "decode"
        self._active[slot] = True
        self._prefill_fifo.remove(slot)
        st.emitted.append(first)
        if st.journey is not None:
            st.journey.event("first_token")
        st.stream(self._deferred_cbs)
        self.stats["admissions"] += 1
        if self._tele is not None:
            self._tele.on_first_token(st.rid, st.prompt_len - st.n_pre,
                                      st.n_pre,
                                      streams=st.on_token is not None)

    def _flush_slot_state(self):
        """Push pending per-slot decode state (first token, write
        position, PRNG key) to the device arrays the decode program
        consumes — ONE batched update per array per tick instead of
        three dispatches per admission."""
        if self._pending_tok:
            idx = jnp.asarray(list(self._pending_tok), jnp.int32)
            vals = jnp.asarray(list(self._pending_tok.values()),
                               jnp.int32)
            self._tok = self._tok.at[idx].set(vals)
            self._pending_tok.clear()
            self._count_dispatches(1, op="state_push")
            self._charge_transfer("state_push", 2 * self._tok.nbytes)
        if self._pending_t:
            idx = jnp.asarray(list(self._pending_t), jnp.int32)
            vals = jnp.asarray(list(self._pending_t.values()), jnp.int32)
            self._t = self._t.at[idx].set(vals)
            self._pending_t.clear()
            self._count_dispatches(1, op="state_push")
            self._charge_transfer("state_push", 2 * self._t.nbytes)
        if self._pending_key:
            idx = jnp.asarray(list(self._pending_key), jnp.int32)
            vals = jnp.stack(list(self._pending_key.values()))
            self._keys = self._keys.at[idx].set(vals)
            self._pending_key.clear()
            self._count_dispatches(1, op="state_push")
            self._charge_transfer("state_push", 2 * self._keys.nbytes)

    def _count_dispatches(self, n=1, op="prefill"):
        """Account ``n`` host->device dispatches on the admission/
        prefill path (prefill program launches, page gathers/scatters,
        slot-state pushes) — the counter-asserted signal that the
        ragged path eliminated the per-admission detour. ``op`` labels
        the dispatch in this tick's profile (the item-4 baseline)."""
        self.stats["prefill_dispatches"] += n
        self._tick_disp[op] = self._tick_disp.get(op, 0) + n
        if self._tele is not None:
            self._tele.add_prefill_dispatches(n)

    def _tick_dispatch(self, op, n=1):
        """Account ``n`` dispatches that are NOT admission/prefill work
        (the decode program itself, block-table syncs) in this tick's
        per-op profile only."""
        self._tick_disp[op] = self._tick_disp.get(op, 0) + n

    def _slow_phase(self, phase, seconds, start, tick, args):
        """The boundary's sink for a phase of ``SLOW_PHASE_S`` or longer
        (``TickBoundary._close``), whichever consumers are on: two flat
        stats, ``serving_slow_phases_total{phase}``, a record in
        ``slow_phases`` and in the flight recorder, and one WARNING.
        The record: ``phase``, ``seconds``, ``tick``, ``start`` (the
        clock's read that opened it), ``args`` (the span's: ``width``,
        ``rows``, ``launch_rows``, ``rids`` for a launch), ``live`` and
        ``queued`` requests, ``first_use`` for a program's phases (this
        server had not launched that width and rows, or decoded,
        before) and ``host_events``: the (name, seconds) of every
        compile step, cache load and collector pause that ended inside
        it (``HostEventLog``). A ``*_wait`` sits through every launch
        enqueued since the host last read a value back (a long prompt's
        chunks while nothing decodes: 14 launches of 54 ms before one
        read-back in the long-context cell), so its limit is
        ``SLOW_PHASE_S`` a launch awaited, and its record says how many
        (``launches_awaited``). Warm-up's compiles are slow phases by
        design: the warning waits until the catalog's compile watch is
        ``warmed`` (a server without a catalog warns at once)."""
        waits = phase in ("prefill_wait", "decode_wait")
        if waits and seconds < SLOW_PHASE_S * self._unawaited:
            return
        self.stats["slow_phases"] += 1
        self.stats["slow_phase_s"] += seconds
        rec = {"phase": phase, "seconds": seconds, "tick": tick,
               "start": start,
               "args": {k: v for k, v in args.items() if k != "tick"},
               "live": int(self._active.sum()),
               "queued": len(self._queue),
               "host_events": self._host_events.ended_in(
                   start, start + seconds)}
        if waits:
            rec["launches_awaited"] = self._unawaited
        if phase in ("prefill_dispatch", "prefill_wait"):
            rec["first_use"] = self._fresh_launch
        elif phase in ("decode_dispatch", "decode_wait"):
            rec["first_use"] = self.stats["decode_ticks"] == 0
        self.slow_phases.append(rec)
        if self._tele is not None:
            self._tele.on_slow_phase(phase)
        if self._rec is not None:
            self._rec.record("slow_phase", **rec)
        if self._costs is None or self._costs.warmed:
            _log.warning(
                "slow phase: tick %s %s %.2f s (live %d, queued %d)%s",
                tick, phase, seconds, rec["live"], rec["queued"],
                "".join(f"; {name} {s:.2f} s"
                        for name, s in rec["host_events"]))

    def _cost_op(self, name):
        """Cost-catalog op name for a serving program: suffixed with
        the pool shard count on a mesh (``decode_mp4``) so a catalog
        SHARED across servers at different mp never sees one op's
        shape signature change — a warmed op's new signature is
        exactly what the post-warmup recompile alarm fires on, and a
        mesh size is a deployment choice, not a recompile. Unsharded
        servers keep the bare names (dashboards unchanged)."""
        return name if self._pool_shards <= 1 \
            else f"{name}_mp{self._pool_shards}"

    def _cost_program(self, op, fn, args):
        """The cost catalog's priced executable for ``fn`` at ``args``'
        shape signature (compiled + priced on first sight; calling it
        dispatches AND charges). The compile-watch funnel lives here: a
        fresh compile lands a ``compile`` recorder event, and one that
        happens AFTER the catalog warmed is a RECOMPILE — flagged on
        the event and stamped as a ``compile_stall`` journey phase on
        every request parked behind the stalled tick (queued, mid-
        prefill, live slots, preempted), so the latency spike those
        requests see is attributable to XLA. Caller guarantees
        ``self._costs is not None``."""
        prog = self._costs.program(op, fn, args)
        if getattr(prog, "compiled_now", False):
            if self._rec is not None:
                self._rec.record("compile", op=op,
                                 recompile=prog.recompile,
                                 seconds=prog.compile_s)
            if prog.recompile:
                stalled = [item.journey for item in self._queue]
                stalled += [rec.journey for rec in self._preempted]
                stalled += [st.journey for st in self._slots
                            if st is not None]
                for journey in stalled:
                    if journey is not None:
                        journey.event("compile_stall", op=op)
        return prog

    def _charge_transfer(self, op, nbytes):
        """Price a host<->device data movement that is not a compiled
        program (slot-state push, page gather/scatter, block-table
        sync): bytes moved — read + write of the touched buffers —
        zero FLOPs. No-op without an enabled cost catalog."""
        if self._costs is not None:
            self._costs.charge_bytes(op, int(nbytes))

    def _row_nbytes(self):
        """Bytes one token's K+V rows occupy across every layer of the
        page pool — the unit the page gather/scatter transfer charges
        are priced in. Computed once from the pool leaves."""
        if self._kv_row_nbytes is None:
            pool = self._caches["pool"]
            pg = self._kv.page_size
            self._kv_row_nbytes = sum(
                leaf.nbytes // (leaf.shape[1] * pg)
                for leaf in jax.tree_util.tree_leaves(pool))
        return self._kv_row_nbytes

    def _n_prefill_calls(self, seg_len):
        """Dense-prefill program launches ``_run_prefill`` makes for a
        ``seg_len``-token segment (1 unchunked, else one per chunk)."""
        if seg_len <= 0:
            return 0
        c = self._prefill_chunk
        if not c or seg_len <= c:
            return 1
        return (seg_len + self._chunk_pad(seg_len)) // c

    def _admit_one(self, slot, req, best=None):
        rid, ids, budget = req.rid, req.ids, req.budget
        req_seed, on_token, deadline = req.seed, req.on_token, req.deadline
        if self._faults is not None:
            # chaos failure point: an admission prefill that dies is a
            # PER-REQUEST failure (_admit records it), never a server one
            self._faults.check(faults.PREFILL, rid=rid)
        T = ids.shape[0]
        # per-request prefill at batch 1 (optionally in fixed-size
        # chunks: one compiled program for every prompt length),
        # then scatter into the pool. A registered-prefix hit seeds
        # the caches from the stored dense rows; an AUTOMATIC
        # prefix-cache hit (radix tree over donated pages) gathers the
        # cached pages back into a dense batch-1 cache — either way
        # only the remainder is prefilled.
        if best is None:
            best = self._best_hit(ids)
        if best is not None and best[0] == "tree" \
                and self._host is not None:
            # restore any host-resident suffix before the pages are
            # shared/gathered below (dense path mirror of the ragged
            # _reserve_one wiring)
            m2 = self._restore_match(best[1])
            best = None if m2 is None else ("tree", m2)
        if best is not None and best[0] == "tree":
            n_pre, pre_pages = best[1].tokens, best[1].pages
        elif best is not None:
            n_pre, pre_pages = best[1][0].shape[0], best[1][3]
        else:
            n_pre, pre_pages = 0, []
        own = []
        if self._kv is not None:
            # reserve the slot's FULL extent (prompt + budget) before
            # any prefill work or stats: an OutOfPages here (aborted
            # eviction sweep, headroom shrunk mid-tick) defers the
            # request with no prefill wasted and nothing counted — the
            # retry starts from zero, so counters see each admission
            # ONCE. Shared cache-hit pages join the slot's table by
            # reference and are referenced before the alloc, so its
            # reclaim sweep can never evict them; mid-decode growth can
            # never exhaust the pool. (Optimistic admission reserves
            # only prompt + headroom here; _grow_locked pays as it goes.)
            own = self._kv.admit_slot(slot,
                                      self._extent_tokens(T, budget),
                                      pre_pages)
            self._count_headroom(slot, T)
        tele = self._tele
        b = self._boundary
        if b is not None:
            t_launch = b.mark("prefill_launch")

        def _ledger_prefill(n_seg):
            # dense-path prefill rows: n_seg real rows (replay when a
            # preempted request re-prefills its prompt) + the chunked
            # prefill's remainder pad. The dense program runs on dense
            # batch-1 caches — no page DMAs to model here.
            if self._led is not None and n_seg:
                self._led.add("replay" if isinstance(req, _Preempted)
                              else "goodput", n_seg)
                self._led.add("chunk_pad", self._chunk_pad(n_seg))

        if best is not None and best[0] == "tree":
            m = best[1]
            self._prefix.use(m)               # LRU: reuse is recency
            caches1 = self._seed_from_pages(m.pages)
            self._count_dispatches(1, op="page_gather")   # the detour
            rest = ids[n_pre:]                # never empty (lookup cap)
            self.stats["prefix_hit_tokens"] += n_pre
            self.stats["prefix_auto_hits"] += 1
            self.stats["prefix_auto_hit_tokens"] += n_pre
            logits, caches1 = self.model._run_prefill(
                self._bundle, rest[None], chunk=self._prefill_chunk,
                caches=caches1, t0=n_pre)
            self._count_dispatches(self._n_prefill_calls(rest.shape[0]))
            self.stats["prefill_tokens"] += rest.shape[0]
            _ledger_prefill(rest.shape[0])
            if tele is not None:
                tele.on_prefix_auto(True, n_pre)
        elif best is not None:
            rows, pre_logits = best[1][1], best[1][2]
            caches1 = jax.tree_util.tree_map(
                lambda full, r: full.at[:, :, :r.shape[2]].set(r),
                self._init_caches(1), rows)
            self._count_dispatches(1, op="page_scatter")  # dense-row seed
            if self._costs is not None:    # byte model priced lazily:
                # the tree flatten must not run on the costs=None path
                self._charge_transfer(
                    "page_scatter",
                    2 * sum(leaf.nbytes for leaf
                            in jax.tree_util.tree_leaves(rows)))
            rest = ids[n_pre:]
            self.stats["prefix_hit_tokens"] += n_pre
            if rest.shape[0]:
                logits, caches1 = self.model._run_prefill(
                    self._bundle, rest[None],
                    chunk=self._prefill_chunk, caches=caches1, t0=n_pre)
                self._count_dispatches(
                    self._n_prefill_calls(rest.shape[0]))
                self.stats["prefill_tokens"] += rest.shape[0]
                _ledger_prefill(rest.shape[0])
            else:
                logits = pre_logits
            if tele is not None and self._auto_prefix:
                tele.on_prefix_auto(False, 0)
        else:
            logits, caches1 = self.model._run_prefill(
                self._bundle, ids[None], chunk=self._prefill_chunk)
            self._count_dispatches(self._n_prefill_calls(T))
            self.stats["prefill_tokens"] += T
            _ledger_prefill(T)
            if tele is not None and self._auto_prefix:
                tele.on_prefix_auto(False, 0)
        key = jax.random.PRNGKey(req_seed)
        if self.do_sample:
            # same split pattern as sample_generate.run: one split,
            # sample tok0 from the [1, V] prefill logits
            key, sub = jax.random.split(key)
            from .decode_loop import process_logits
            first = int(jax.random.categorical(
                sub, process_logits(logits, self._temperature,
                                    self._top_k, self._top_p),
                axis=-1)[0])
        else:
            first = int(jnp.argmax(logits, -1)[0])
        self._keys = self._keys.at[slot].set(key)
        if self._kv is not None:
            # only prompt rows are copied into the reserved pages; the
            # shared prefix pages ahead of them are already filled
            pg = self._kv.page_size
            n_prompt = -(-T // pg) - len(pre_pages)
            if own[:n_prompt]:
                self._count_dispatches(1, op="page_scatter")  # remainder pages
                if self._costs is not None:
                    # charged HERE, not inside _fill_pages: the other
                    # _fill_pages caller is register_prefix, which
                    # stays off the cost ledger like it stays off
                    # goodput
                    self._charge_transfer(
                        "page_scatter",
                        2 * len(own[:n_prompt]) * pg
                        * self._row_nbytes())
            self._fill_pages(caches1, own[:n_prompt],
                             len(pre_pages) * pg)
        else:
            self._caches = jax.tree_util.tree_map(
                lambda pool, one: pool.at[:, slot].set(one[:, 0]),
                self._caches, caches1)
            self._count_dispatches(1, op="page_scatter")  # dense row copy
            if self._costs is not None:
                self._charge_transfer(
                    "page_scatter",
                    2 * sum(leaf.nbytes for leaf
                            in jax.tree_util.tree_leaves(caches1)))
        self._tok = self._tok.at[slot].set(first)
        self._pending_t.pop(slot, None)     # the park of its last tenant
        self._t = self._t.at[slot].set(T)
        self._count_dispatches(3, op="state_push")    # tok/t/key pushes
        if self._costs is not None:
            # three transfers, charged as three — the cost ledger's
            # dispatch count must reconcile 1:1 with the tick profile
            self._charge_transfer("state_push", 2 * self._tok.nbytes)
            self._charge_transfer("state_push", 2 * self._t.nbytes)
            self._charge_transfer("state_push", 2 * self._keys.nbytes)
        self._active[slot] = True
        st = _Slot(rid, ids, T, budget, on_token, deadline)
        st.n_pre = n_pre
        st.seed = req_seed
        self._bind_request(st, req, slot)
        st.emitted.append(int(first))
        if st.journey is not None:
            st.journey.event("first_token")
        st.stream(self._deferred_cbs)
        self._slots[slot] = st
        self.stats["admissions"] += 1
        if b is not None:
            wall = b.mark("admit") - t_launch
            self.stats["prefill_wall_s"] += wall
        if tele is not None:
            tele.on_prefill_batch(wall)
            tele.on_first_token(rid, T - n_pre, n_pre,
                                streams=on_token is not None)

    # ------------------------------------- optimistic growth / preemption
    def _grow_locked(self):
        """Optimistic admission's per-tick growth pass: every active
        slot whose next ``tick_block`` decode writes would cross its
        block-table coverage gets pages appended ON DEMAND
        (``PagedKVCache.grow_slot``); when the pool cannot supply them
        the preemption policy frees victims (``_grow_one_locked``).
        Runs under the server lock BEFORE the decode dispatch, so the
        device program always sees tables covering every row it will
        genuinely need — rows past a request's total extent
        null-redirect harmlessly, exactly like reserve mode's wasted
        block steps."""
        n = self.tick_block
        for slot in range(self.max_slots):
            if not self._active[slot]:
                continue              # empty, mid-prefill, or just parked
            st = self._slots[slot]
            # next tick writes rows [t, t + n), t = prompt_len +
            # emitted - 1; rows at or past prompt + budget are never
            # read back (harvest stops the slot first)
            needed = min(st.prompt_len + len(st.emitted) - 1 + n,
                         st.prompt_len + st.budget)
            try:
                self._grow_one_locked(slot, st, needed)
            except PreemptedError:
                # the grower itself ranked last and was parked — typed,
                # internal, and caught HERE: it never reaches a waiter
                continue

    def _grow_one_locked(self, slot, st, needed_tokens):
        """Grow one slot to cover ``needed_tokens``, preempting victims
        if the pool is genuinely exhausted. Loop invariant: every
        iteration either succeeds, raises (transient tick failure —
        retried by the supervisor with all state consistent), or
        removes one live slot from the candidate set, so it terminates;
        when the grower itself is the least valuable live work it parks
        itself (``PreemptedError``, caught by ``_grow_locked``) rather
        than evict anyone ranked above it."""
        kv = self._kv
        need = self._npages_for(needed_tokens) - len(kv.slot_pages(slot))
        if need <= 0:
            return
        while True:
            try:
                kv.grow_slot(slot, need)
            except OutOfPages:
                if kv.free_pages() \
                        + self._prefix.evictable_pages() >= need:
                    # pages exist but this reclaim sweep died (injected
                    # ``prefix.evict`` fault): a TRANSIENT tick failure
                    # — the supervisor retries; preempting here would
                    # burn a victim for pages already reclaimable
                    raise
                cands = [(s, self._slots[s])
                         for s in range(self.max_slots)
                         if self._slots[s] is not None]
                victim = self._preempt_policy.pick(slot, cands)
                if victim is None:
                    raise      # no live work to free: genuine exhaustion
                if self._faults is not None:
                    # chaos point: an aborted victim teardown leaves the
                    # victim decoding and fails the TICK (supervised
                    # retry); victims already parked this sweep stay
                    # safely parked — nothing leaks either way
                    self._faults.check(faults.SERVER_PREEMPT,
                                       slot=victim, grower=slot,
                                       rid=self._slots[victim].rid)
                if victim == slot:
                    self._preempt_slot_locked(slot)
                    raise PreemptedError(
                        f"request {st.rid} parked by its own page "
                        f"growth (least valuable live work)")
                self._preempt_slot_locked(victim)
            else:
                self.stats["grow_pages"] += need
                if self._tele is not None:
                    self._tele.add_grow_pages(need)
                if self._rec is not None:
                    self._rec.record("grow", rid=st.rid, slot=slot,
                                     pages=need)
                if st.journey is not None:
                    st.journey.event("grow", pages=need)
                return

    def _preempt_slot_locked(self, slot):
        """Tear a victim down BIT-EXACTLY resumable: park its replay
        record (resolved seed, absolute deadline, stream offset, the
        partial so far) on the preempted queue, donate its written
        prompt prefix pages into the radix tree COLD (the triggering
        grow reclaims them first; a quick re-admission still auto-hits
        whatever survives), and free the rest. The waiter keeps
        blocking: re-admission replays the identical token chain —
        greedy trivially, sampled because the chain restarts from the
        same resolved seed through the same programs."""
        st = self._slots[slot]
        rec = _Preempted(st)
        if self._rec is not None:
            self._rec.record("preempt", rid=st.rid, slot=slot,
                             tokens=len(rec.emitted),
                             preempts=rec.preempts)
        if st.journey is not None:
            st.journey.event("preempted", slot=slot,
                             tokens=len(rec.emitted))
        self._release_slot(slot, cold=True)
        self._preempted.append(rec)
        self.stats["preemptions"] += 1
        if self._tele is not None:
            self._tele.on_preempt(st.rid, len(self._preempted))
            self._pool_gauges()

    # ------------------------------------------------------------ steps
    def _build_decode_step(self):
        """One jitted program running ``tick_block`` decode steps per
        host dispatch (lax.scan; emits the [slots, n] token matrix).
        Larger blocks amortize dispatch at the price of admission
        latency and ≤n-1 wasted steps on slots that finish mid-block —
        wasted rows write out of bounds (dropped) or above the frontier
        (masked), never corrupting live slots. A slot parked on the
        idle sentinel (``_park_slot``) stays on it through every step
        of every block."""
        embed_p, step_p, head_p = (self._embed_fn, self._step_fn,
                                   self._head_fn)
        do_sample = self.do_sample
        temperature, top_k, top_p = (self._temperature, self._top_k,
                                     self._top_p)
        n = self.tick_block
        span = self.max_cache_len

        def one(tok, caches, t, keys):
            x = embed_p(tok, t)
            out, caches = step_p(x, caches, t)
            logits = head_p(out)
            if logits.ndim == 3:
                logits = logits[:, -1]
            if do_sample:
                from .decode_loop import process_logits

                def samp(k, row):
                    # identical draw chain to sample_generate.body:
                    # split this slot's key, sample over its [1, V] row
                    k2, sub = jax.random.split(k)
                    nxt = jax.random.categorical(
                        sub, process_logits(row[None], temperature,
                                            top_k, top_p), axis=-1)[0]
                    return k2, nxt.astype(jnp.int32)

                keys, nxt = jax.vmap(samp)(keys, logits)
            else:
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            # a parked slot stays parked, step after step and tick after
            # tick: its t rests ON the sentinel instead of counting on
            # from it (a server left up for months would wrap an int32)
            return nxt, caches, jnp.minimum(t + 1, span), keys

        def decode_tick(tok, caches, t, keys):
            def body(carry, _):
                carry = one(*carry)
                return carry, carry[0]
            (tok, caches, t, keys), toks = jax.lax.scan(
                body, (tok, caches, t, keys), None, length=n)
            toks = jnp.transpose(toks, (1, 0))
            # what each slot's last row did rides the read-back the
            # tick makes anyway, beside its tokens: the experts it chose
            # ([L, S, k] -> [S, L * k]), then the keys its attention
            # kept, summed over layers ([L, S] -> [S, 1])
            aux = caches if isinstance(caches, dict) else {}
            if "route" in aux:
                route = aux["route"]
                toks = jnp.concatenate(
                    [toks, jnp.transpose(route, (1, 0, 2)).reshape(
                        route.shape[1], -1)], axis=1)
            if "kept" in aux:
                toks = jnp.concatenate(
                    [toks, jnp.sum(aux["kept"], axis=0)[:, None]], axis=1)
            return tok, caches, t, keys, toks

        # the trace's ``jit_decode_tick``
        return hoisted_jit(decode_tick, donate_argnums=(1,))

    def step(self):
        """One server tick: admit waiting requests, run ``tick_block``
        batched decode steps as one program, harvest finished rows.
        Returns the number of active slots after the tick."""
        with self._lock:
            n = self._step_locked()
            if self._prefix is not None:
                self._prefix.flush_sketch()   # one publish per tick
        self._fire_callbacks()
        return n

    def _fire_callbacks(self):
        """Run streamed-token callbacks collected during locked work.
        EVERY queued callback fires even when one raises — a poisoned
        stream must not starve the other requests' chunks (they were
        already swapped out of ``_deferred_cbs`` and would be lost) —
        then the failures are re-raised together as a ``CallbackError``
        (``.errors`` per rid, ``__cause__`` the first) to the
        step()/run() caller or the supervised serve loop, which fails
        exactly the offending requests."""
        cbs, self._deferred_cbs = self._deferred_cbs, []
        b, self._boundary = self._boundary, None
        # streaming requests whose first token is drawn and not yet
        # handed over (telemetry's request.deliver spans); None or empty
        # but for the turn after an activation
        owed = None if self._tele is None else self._tele.undelivered
        errors = []
        for cb, rid, toks in cbs:
            try:
                if self._faults is not None:
                    self._faults.check(faults.ON_TOKEN, rid=rid)
                cb(rid, toks)
            except Exception as e:
                errors.append((rid, e))
            if owed and rid in owed:
                self._tele.on_first_delivery(rid)
        if b is not None:
            # the tick's last phase, "callbacks" (opened by
            # _step_locked), ends here, OUTSIDE the lock and after the
            # tick flushed: the cost catalog folds it into the NEXT
            # tick's breakdown (a one-tick skew, documented in
            # telemetry.costs)
            b.close()
        if errors:
            raise CallbackError(errors, what="on_token callback")

    def _step_locked(self):
        """One tick under the lock. Wraps the real work so the tick's
        host->device dispatch profile is published however the tick
        exits (normal, drained early-return, or a raising fault — a
        partial profile in the recorder is exactly what a postmortem
        wants to see)."""
        self._tick_disp = {}
        ct = self._costs
        b = None
        if ct is not None or self._tele is not None:
            self._tick_seq += 1
            b = self._boundary = TickBoundary(
                ct, self._tele, "expire", tick=self._tick_seq,
                slow=self._slow_phase)
        try:
            n = self._step_inner()
        except BaseException:
            if b is not None:            # a raising tick fires no callbacks
                b.close()
                self._boundary = None
            raise
        else:
            if b is not None:
                # whatever phase the tick ended in (an early return
                # stays in the phase it left from) closes here;
                # "callbacks" runs until _fire_callbacks closes it
                b.mark("callbacks")
            return n
        finally:
            prof = self._tick_disp
            if prof:
                total = sum(prof.values())
                self.stats["tick_dispatches"] += total
                if self._tele is not None:
                    self._tele.on_tick_dispatches(prof)
                if self._rec is not None:
                    extra = {}
                    if ct is not None:
                        extra["phases"] = ct.pending_phases()
                    self._rec.record("tick", dispatches=dict(prof),
                                     total=total,
                                     active=int(self._active.sum()),
                                     **extra)
            if self._led is not None:
                # the conservation boundary: whatever this tick
                # attributed (even a partial, faulted tick) is folded
                # and published NOW — kinds sum to the tick's device
                # tokens by construction of the sites above
                self._led.flush_tick()
            if ct is not None:
                # same boundary for the cost side: fold charges +
                # phases, publish FLOPs/bytes/MFU, advance the compile
                # watch's warmup
                ct.flush_tick()

    def _step_inner(self):
        self._prefill_used = 0       # per-tick prefill token budget
        # the tick opened in "expire"; each mark below opens the phase
        # it names (TICK_PHASES says which leave the chip idle)
        b = self._boundary
        self._expire_locked()
        if b is not None:
            b.mark("admit")
        # a prefill launch marks itself out from inside (prefill_pack,
        # prefill_dispatch, prefill_wait, activate) and comes back in
        # "admit"
        self._admit()
        if not self._active.any():
            if self._tele is not None:     # keep the gauge live when a
                self._tele.set_active_slots(0)   # drained tick skips decode
            return 0
        # harvest BEFORE stepping: a slot whose budget is spent (or that
        # emitted eos at admission) must not decode further
        if b is not None:
            b.mark("harvest")
        self._harvest()
        if not self._active.any():
            if self._tele is not None:
                self._tele.set_active_slots(0)
            return 0
        if self._kv is not None:
            # reserve mode: admission took each slot's FULL extent
            # (prompt + budget), so no page growth happens mid-flight.
            # optimistic mode: grow every slot about to cross its
            # coverage NOW, preempting victims if the pool is dry —
            # the dispatch below must never write a needed row through
            # a missing page. Writes past a slot's table (wasted block
            # steps of finished/inactive rows) are redirected to the
            # null page and need no coverage in either mode.
            if self._optimistic:
                if b is not None:
                    b.mark("grow")
                self._grow_locked()
                if not self._active.any():
                    # extreme pressure: growth parked every decoding
                    # slot — nothing to dispatch this tick (re-admission
                    # restarts them next tick)
                    if self._tele is not None:
                        self._tele.set_active_slots(0)
                    return 0
        if b is not None:
            b.mark("state_push")
        if self._kv is not None:
            self._sync_block_table()
        # ragged mode: activations batched their tok/t/key updates —
        # push them, and the sentinel of every slot parked since the
        # last dispatch (_park_slot: released, rolled back, paused or
        # still prefilling, whose decode writes must null-redirect, not
        # land in the pages being filled), before the decode program
        self._flush_slot_state()
        if self._decode_jit is None:
            self._decode_jit = self._build_decode_step()
        if self._faults is not None:
            # chaos failure point: a dying decode tick is a SERVER-level
            # transient — the supervisor retries it (host state is
            # consistent: nothing was dispatched yet)
            self._faults.check(faults.DECODE_TICK)
        tele = self._tele
        n_active = int(self._active.sum())
        decode_fn = self._decode_jit
        if self._costs is not None:
            # the catalog's AOT executable is the SAME HLO the jit
            # cache would build (bit-identical tokens); calling it
            # charges the compiled program's FLOPs/bytes per dispatch.
            # Priced ONCE and cached: the decode signature is static
            # by construction (fixed slot count / cache geometry), so
            # the hot loop must not re-hash the caches pytree per tick
            if self._decode_prog is None:
                self._decode_prog = self._cost_program(
                    self._cost_op("decode"), self._decode_jit,
                    (self._tok, self._caches, self._t, self._keys))
            decode_fn = self._decode_prog
        if b is not None:
            # the host's until the call returns (enqueueing the tick),
            # then a wait for the tokens back on the host; the tick's
            # wall is both
            t_launch = b.mark("decode_dispatch")
        (self._tok, self._caches, self._t, self._keys,
         toks) = decode_fn(self._tok, self._caches, self._t,
                           self._keys)
        self._tick_dispatch("decode")
        if b is not None:
            b.mark("decode_wait")
        toks = np.asarray(toks)                    # [slots, tick_block]
        aux, toks = toks[:, self.tick_block:], toks[:, :self.tick_block]
        route, kept = (aux[:, :-1], aux[:, -1]) if self._select_k \
            else (aux, None)
        if b is not None:
            wall = b.mark("emit") - t_launch
        self._unawaited = 0
        # rows of slots holding no decoding request still ride the
        # program, parked on the idle sentinel (_park_slot): the paged
        # backend sends their writes to the null page, the dense one
        # drops them out of bounds, and attention, key selection and
        # the expert FFN skip them
        rows, live_rows = (self.max_slots * toks.shape[1],
                           n_active * toks.shape[1])
        self.stats["decode_ticks"] += 1
        self.stats["decode_rows"] += rows
        self.stats["decode_live_rows"] += live_rows
        if tele is not None:
            tele.on_decode_rows(rows, live_rows)
        if self._moe_k:
            self._count_routed(route, live_rows, live_rows)
        decoded = wasted = keys_ctx = keys_sel = 0
        lens = []          # valid tokens of each decoding slot's first row
        led = self._led
        if led is not None:
            led.add("null_redirect", rows - live_rows)
        for slot in range(self.max_slots):
            if not self._active[slot]:
                continue
            st = self._slots[slot]
            lens.append(st.prompt_len + len(st.emitted))
            if led is not None and self._kv is not None:
                led.add("skipped_page_dma", self._skipped_dma(
                    st.prompt_len + len(st.emitted)))
            if kept is not None:
                # the block's last row attended from position base +
                # block - 2: base + block - 1 keys in its context, and
                # in every layer; what it KEPT is the device's count
                keys_ctx += (st.prompt_len + len(st.emitted)
                             + toks.shape[1] - 1) * self._n_layers
                keys_sel += int(kept[slot])
            for j in range(toks.shape[1]):
                st.emitted.append(int(toks[slot, j]))
                if led is not None:
                    # a resumed slot's rows below its pre-preemption
                    # offset re-generate tokens the waiter already has
                    led.add("replay"
                            if len(st.emitted) <= len(st.replayed)
                            else "goodput", 1)
                if self._finished(st):
                    wasted += toks.shape[1] - (j + 1)
                    if led is not None:
                        led.add("block_waste", toks.shape[1] - (j + 1))
                    break              # later block tokens are waste
            decoded += min(j + 1, toks.shape[1])
            st.stream(self._deferred_cbs)
        if self._kv is not None and not self._select_k:
            self._count_decode_grid(np.asarray(lens), toks.shape[1])
        if keys_ctx:
            self.stats["attn_keys_context"] += keys_ctx
            self.stats["attn_keys_selected"] += keys_sel
            if tele is not None:
                tele.on_selected_keys(keys_ctx, keys_sel)
        if tele is not None:
            # np.asarray above synced the dispatch, so the tick time
            # covers host dispatch + device work
            tele.on_tick(wall, n_active, decoded)
            if wasted:
                tele.add_wasted_block_tokens(wasted)
            if self._kv is not None:
                # parked rows still step; each writes one zeroed row to
                # the null page (their t is past the table)
                tele.add_null_writes(rows - live_rows)
        if b is not None:
            b.mark("harvest")
        self._harvest()
        # end-of-tick admissions reserve only (ragged: their prefill
        # chunks run at the NEXT tick's single batched launch — the
        # token budget is per tick); the dense path prefills inline
        if b is not None:
            b.mark("admit")
        self._admit(run_prefill=False)
        n = int(self._active.sum())
        if tele is not None:
            tele.set_active_slots(n)
        return n

    def _count_decode_grid(self, lens, block):
        """Decode-kernel accounting of one tick: the steps its grid
        took and the pages the decoding slots' valid tokens spanned, a
        layer at a time. ``lens`` are the decoding slots' lengths at
        the block's first step (a parked slot has length 0 and no
        page); step ``j`` attends ``lens + j``, or nothing once that
        passes the table's span (the sentinel). Counted by
        ``decode_grid``, which sizes the grid on the device."""
        from ..ops.pallas.paged_attention import decode_grid
        steps = live = 0
        for j in range(block):
            at = lens + j
            pages, n = decode_grid(np.where(at <= self.max_cache_len, at, 0),
                                   self.page_size)
            steps += int(n)
            live += int(pages.sum())
        steps, live = steps * self._n_layers, live * self._n_layers
        self.stats["decode_grid_steps"] += steps
        self.stats["decode_live_pages"] += live
        if self._tele is not None:
            self._tele.on_decode_grid(steps, live)

    def _count_prefill_grid(self, t0, takes, width):
        """Prefill-kernel accounting of one launch: the steps its grid
        took and those that attended a page of a live query tile, a
        layer at a time, from the launch's offsets ``t0`` (the sentinel
        on a padding row) and real rows ``takes``. Counted by
        ``prefill_grid``, which sizes the grid on the device."""
        from ..ops.pallas.paged_attention import prefill_grid
        from ..ops.pallas.ragged_prefill import QUERY_TILE
        pages, steps = prefill_grid(t0, takes, width, QUERY_TILE,
                                    self.page_size,
                                    self.max_cache_len // self.page_size)
        steps = int(steps) * self._n_layers
        live = int(pages.sum()) * self._n_layers
        self.stats["prefill_grid_steps"] += steps
        self.stats["prefill_live_steps"] += live
        if self._tele is not None:
            self._tele.on_prefill_grid(steps, live)

    def _count_routed(self, route, live_rows, rows):
        """Expert-FFN accounting of one launch: ``rows`` computed (the
        rows of the slots that rode it live, chunk padding included),
        ``live_rows`` of them a live token's. ``route`` (decode ticks:
        ``[slots, layers * k]`` expert ids off the token read-back, or
        None) gives the distinct experts the live slots' rows chose,
        a layer at a time, AMONG those the model holds: a choice that
        fell on an expert of another share reads no weight here."""
        touched = routed = held = 0
        if route is not None and route.size:
            k = self._moe_k
            live = route[self._active].reshape(-1, route.shape[1] // k, k)
            mine = np.ones(live.shape, bool)
            if self._moe_held is not None:
                first, count = self._moe_held
                mine = (live >= first) & (live < first + count)
            routed, held = int(live.size), int(mine.sum())
            touched = sum(int(np.unique(live[:, l][mine[:, l]]).size)
                          for l in range(live.shape[1])) if live.size else 0
        self.stats["moe_rows"] += rows
        self.stats["moe_live_rows"] += live_rows
        self.stats["moe_experts_touched"] += touched
        self.stats["moe_pairs_routed"] += routed
        self.stats["moe_pairs_held"] += held
        if self._tele is not None:
            self._tele.on_moe_rows(rows, live_rows, touched)
            if routed:
                self._tele.on_moe_pairs(routed, held)

    def _busy_locked(self):
        """Work pending: queued requests, decoding slots, slots still
        mid-ragged-prefill (not yet _active but holding pages and owed
        their remaining prompt chunks), or preempted requests parked
        for re-admission (``stop(drain=True)`` keeps ticking until
        they finish too)."""
        return bool(self._queue or self._active.any()
                    or self._prefill_fifo or self._preempted)

    def _finished(self, st):
        if len(st.emitted) >= st.budget:
            return True
        return (self.eos_token_id is not None
                and st.emitted[-1] == self.eos_token_id)

    def _harvest(self):
        finished = False
        for slot in range(self.max_slots):
            st = self._slots[slot]
            if self._active[slot] and self._finished(st):
                out = np.asarray(st.emitted[:st.budget], np.int32)
                self._results[st.rid] = out
                self._release_slot(slot)   # paged: donates prompt pages
                if self._tele is not None:
                    self._tele.on_finish(st.rid, len(out))
                if self._rec is not None:
                    self._rec.record("finish", rid=st.rid,
                                     tokens=len(out))
                if st.journey is not None:
                    st.journey.event("finished", tokens=len(out))
                finished = True
        if finished:
            if self._tele is not None:
                self._pool_gauges()
            self._done_cv.notify_all()

    # ------------------------------------------------------- reliability
    def _expire_locked(self):
        """Fail queued requests whose deadline passed (BEFORE a prefill
        is wasted on them) and cancel expired mid-decode slots (their
        partial tokens become the recorded result). Reads the clock at
        most once, and only when some live request carries a deadline."""
        now = None
        notify = False
        if any(item.deadline is not None for item in self._queue):
            now = self._clock.now()
            keep = []
            for item in self._queue:
                if item.deadline is not None and now >= item.deadline:
                    err = DeadlineExceeded(
                        f"request {item.rid} expired in queue "
                        f"(deadline passed before admission)")
                    self._failures[item.rid] = err
                    notify = True
                    if self._tele is not None:
                        self._tele.on_deadline_expired("queued")
                        self._tele.on_admission_failure(item.rid, err)
                    if self._rec is not None:
                        self._rec.record("deadline", rid=item.rid,
                                         where="queued")
                    if item.journey is not None:
                        # NB "where" is a Journey reserved key (the
                        # hop label) — the expiry location is "at"
                        item.journey.event("expired", at="queued")
                else:
                    keep.append(item)
            if len(keep) != len(self._queue):
                self._queue[:] = keep
                if self._tele is not None:
                    self._tele.set_queue_depth(len(self._queue))
        for slot in range(self.max_slots):
            st = self._slots[slot]
            if st is None or st.deadline is None:
                continue
            if st.phase == "migrating":
                # its pages are in flight to a sibling: expiring the
                # slot here would tear down state migrate_finish/
                # migrate_abort still owns. The pause spans ONE
                # migration attempt; the deadline bites again the
                # moment the slot resumes (or on the target)
                continue
            if now is None:
                now = self._clock.now()
            if now >= st.deadline:
                # decoding (partial tokens kept) or mid-ragged-prefill
                # (empty partial) — either way the slot frees now
                if self._rec is not None:
                    self._rec.record("deadline", rid=st.rid,
                                     where="decoding")
                if st.journey is not None:
                    st.journey.event("expired", at="decoding")
                self._finish_partial_locked(slot)
                notify = True
                if self._tele is not None:
                    self._tele.on_deadline_expired("decoding")
                    self._tele.on_cancel(st.rid)
                    self._pool_gauges()
        if self._preempted:
            keep_p = []
            for rec in self._preempted:
                if rec.deadline is not None:
                    if now is None:
                        now = self._clock.now()
                    if now >= rec.deadline:
                        if self._rec is not None:
                            self._rec.record("deadline", rid=rec.rid,
                                             where="preempted")
                        if rec.journey is not None:
                            rec.journey.event("expired",
                                              at="preempted")
                        # deadline accounting holds ACROSS preemption:
                        # time parked counted against the same absolute
                        # deadline. Same promise as mid-decode expiry —
                        # the pre-preemption partial is the result, no
                        # decode is resumed, and its pages were already
                        # donated/freed at preemption
                        self._flush_parked_locked(rec)
                        notify = True
                        if self._tele is not None:
                            self._tele.on_deadline_expired("preempted")
                            self._tele.on_cancel(rec.rid)
                        continue
                keep_p.append(rec)
            if len(keep_p) != len(self._preempted):
                self._preempted[:] = keep_p
                self._preempt_gauge()
        if notify:
            self._done_cv.notify_all()

    def _fail_request_locked(self, rid, err):
        """Fail ONE request still LIVE (queued or in-flight) with
        ``err`` — the per-request channel the supervisor uses so a
        poisoned callback or injected per-request fault never takes the
        server down. A rid that is in neither place already settled
        (harvested — result recorded or even collected — or failed):
        e.g. the FINAL stream chunk's callback raised after harvest.
        Recording a failure then would leave a phantom ``failures``
        entry no wait() ever pops, so it is skipped."""
        found, journey = False, None
        for i, item in enumerate(self._queue):
            if item.rid == rid:
                del self._queue[i]
                found, journey = True, item.journey
                break
        if not found:
            for slot in range(self.max_slots):
                st = self._slots[slot]
                if st is not None and st.rid == rid:
                    self._release_slot(slot)
                    if self._tele is not None:
                        self._pool_gauges()
                    found, journey = True, st.journey
                    break
        if not found:
            for i, rec in enumerate(self._preempted):
                if rec.rid == rid:
                    del self._preempted[i]
                    self._preempt_gauge()
                    found, journey = True, rec.journey
                    break
        if not found:
            return
        # a failed request has no result: its undelivered stream chunks
        # must not fire later as if it were still live
        self._deferred_cbs = [c for c in self._deferred_cbs
                              if c[1] != rid]
        self._failures[rid] = err
        if self._tele is not None:
            self._tele.on_admission_failure(rid, err)
        self._note_request_failure_locked(rid, err, journey)
        self._done_cv.notify_all()

    def _note_request_failure_locked(self, rid, err, journey=None,
                                     bundle=True):
        """Observability funnel for one request FAILING (as opposed to
        finishing with a partial): journey phase, recorder event, and a
        postmortem bundle — "a request just died" is exactly the moment
        an operator wants the last N events and the pool state frozen.
        ``bundle=False`` skips the capture for EXPECTED sheds (the
        evict_oldest path runs on every overloaded submit(): paying a
        state snapshot there would tax the hot path and flood the
        bounded bundle store out of its genuinely interesting
        captures). The caller owns the actual failure bookkeeping."""
        if journey is not None:
            journey.event("failed", error=type(err).__name__)
        if self._rec is not None:
            self._rec.record("fail", rid=rid,
                             error=type(err).__name__)
            if bundle:
                self._postmortem_locked("request_failed", rid=rid,
                                        error=repr(err))

    def _postmortem_locked(self, reason, **extra):
        """Capture a postmortem bundle into the flight recorder: recent
        events plus the serving state an incident review needs — pool
        balance, block-table occupancy, radix-tree stats, the parked
        queue, live slots, queue depth, health, stats. Called under the
        server lock; returns the bundle (or None without a recorder)."""
        if self._rec is None:
            return None
        sections = {
            "health": self._health.state,
            "stats": dict(self.stats),
            # the phases that stalled, with what the host did meanwhile
            "slow_phases": list(self.slow_phases),
            "queue": [item.rid for item in self._queue],
            "slots": [{"slot": s, "rid": st.rid, "phase": st.phase,
                       "emitted": len(st.emitted),
                       "priority": st.priority}
                      for s, st in enumerate(self._slots)
                      if st is not None],
            "parked": [{"rid": rec.rid, "priority": rec.priority,
                        "preempts": rec.preempts,
                        "emitted": len(rec.emitted)}
                       for rec in self._preempted],
            # live KV-page migration state: the in-flight pauses an
            # incident interrupted plus the cumulative outcome split —
            # "did this replica hand its work off or flush it?" is the
            # first question a drain/crash review asks
            "migration": {
                "in_flight": sorted(self._migrating),
                "staging": sorted(self._staging),
                "migrations": self.stats["migrations"],
                "fallbacks": self.stats["migration_fallbacks"],
                "migrated_in": self.stats["migrated_in"],
                "handoff_pages_out": self.stats["handoff_pages_out"],
                "handoff_pages_in": self.stats["handoff_pages_in"]},
        }
        if self._kv is not None:
            # pool_balance() is the ONE definition of the balance the
            # chaos suites assert on (re-entrant lock: safe here) —
            # the bundle must never drift from it
            bal = self.pool_balance()
            sections["pool_balance"] = {
                "free": bal[0], "live": bal[1], "pinned": bal[2],
                "cached": bal[3], "preempted": bal.preempted,
                "preemptions": bal.preemptions,
                "num_shards": bal.num_shards,
                "per_shard": list(bal.per_shard),
                "shard_page_bytes": bal.shard_page_bytes,
                "host": bal.host, "host_bytes": bal.host_bytes}
            sections["block_table"] = self._kv.occupancy(
                num_shards=self._pool_shards, host_tier=self._host)
            sections["prefix_cache"] = self._prefix.stats()
        if self._led is not None:
            # how much of the hardware's recent work was useful is
            # exactly what an incident review wants next to the pool
            # state ("were we thrashing before this died?")
            sections["goodput"] = self._led.snapshot()
        if self._costs is not None:
            # per-op FLOPs/bytes totals, compile counts, and the last
            # tick's phase breakdown — "was it host-bound" answerable
            # from the crash scene without a live server
            sections["costs"] = self._costs.snapshot()
        sections.update(extra)
        return self._rec.postmortem(reason, **sections)

    def postmortems(self):
        """Captured postmortem bundles, oldest first (empty without a
        recorder) — served over ``/debug/postmortem`` via
        ``serving.serve_metrics``."""
        return [] if self._rec is None else self._rec.postmortems()

    def journey(self, rid):
        """Timeline of a SELF-MINTED journey (standalone server
        constructed with ``journeys=``): the request's phase events in
        arrival order, or None without a journey recorder / for an
        unknown-evicted rid / for a request whose journey was minted
        by a router (query the router for those — its id space, its
        timeline). Served over ``/debug/journey/<rid>`` via
        ``serving.serve_metrics``."""
        if self._jrec is None:
            return None
        return self._jrec.journey(f"s{int(rid)}")

    def goodput(self):
        """The goodput ledger's cumulative snapshot (``{"tokens":
        {kind: n}, "goodput_ratio": ...}``), or None without an
        enabled ledger — also ``/stats["goodput"]`` via
        ``serving.serve_metrics`` and the ``goodput`` postmortem
        section."""
        return None if self._led is None else self._led.snapshot()

    def device_costs(self):
        """The cost catalog's cumulative snapshot (per-op FLOPs/HBM
        bytes, compile counts, recompiles/warmup state, MFU/roofline,
        last tick's phase breakdown), or None without an enabled
        catalog — also ``/stats["costs"]`` via
        ``serving.serve_metrics`` and the ``costs`` postmortem
        section."""
        return None if self._costs is None else self._costs.snapshot()

    def utilization(self):
        """Per-replica utilization digest for routing-side views: the
        goodput ratio (ledger) and MFU (cost catalog) — whatever is
        wired. Rides remote heartbeat digests (``inference.remote``)
        so ``/fleet`` and the router see per-replica utilization
        without a registry pull; cheap enough for a heartbeat cadence
        (one short ledger lock, one attribute read)."""
        util = {}
        if self._led is not None:
            util["goodput_ratio"] = self._led.goodput_ratio()
        if self._costs is not None:
            util["mfu"] = self._costs.mfu()
        return util

    def _fail_all_locked(self, cause):
        """Breaker-open path: fail EVERY queued and in-flight request
        with a ``CircuitOpenError`` so no waiter wedges on a server
        that cannot currently tick."""
        thresh = self._sup.breaker.failure_threshold
        for item in self._queue:
            if item.journey is not None:
                item.journey.event("failed", error="CircuitOpenError")
        for rec in self._preempted:
            if rec.journey is not None:
                rec.journey.event("failed", error="CircuitOpenError")
        for st in self._slots:
            if st is not None and st.journey is not None:
                st.journey.event("failed", error="CircuitOpenError")
        rids = [item.rid for item in self._queue]
        self._queue.clear()
        rids += [rec.rid for rec in self._preempted]
        self._preempted.clear()
        self._preempt_gauge()
        for slot in range(self.max_slots):
            if self._slots[slot] is not None:
                rids.append(self._slots[slot].rid)
                self._release_slot(slot)
        # chunks queued by the failed tick belong to rids that now have
        # no result — firing them after recovery would stream tokens
        # for requests whose wait() already raised
        self._deferred_cbs.clear()
        for rid in rids:
            err = CircuitOpenError(
                f"request {rid} aborted: circuit breaker opened after "
                f"{thresh} consecutive tick failures")
            err.__cause__ = cause
            self._failures[rid] = err
            if self._tele is not None:
                self._tele.on_admission_failure(rid, err)
        if self._tele is not None:
            self._tele.set_queue_depth(0)
            self._tele.set_active_slots(0)
            self._pool_gauges()
        self._done_cv.notify_all()

    @property
    def health(self):
        """Current health state: ``healthy`` / ``degraded`` /
        ``draining`` / ``dead`` (see reliability.health). Lock-free
        read of a plain-string attribute — /healthz must answer while
        a tick (or its first jit compile) holds the serve lock, or the
        readiness probe times out exactly when the server warms up."""
        return self._health.state

    def _publish_health(self, state, code):
        if self._tele is not None:
            self._tele.set_health(state)
        if self._rec is not None:
            self._rec.record("health", state=state)

    def run(self, max_ticks=100000):
        """Drive until queue and slots drain; returns {rid: new_tokens}.
        Requests whose admission failed are left out — their exceptions
        are drained into ``failures`` (per run, so records never
        accumulate across runs)."""
        ticks = 0
        while ticks < max_ticks:
            with self._lock:
                if not self._busy_locked():
                    break
                self._step_locked()
                if self._prefix is not None:
                    self._prefix.flush_sketch()
            self._fire_callbacks()
            ticks += 1
        with self._lock:
            out, self._results = self._results, {}
            self._run_failures, self._failures = self._failures, {}
        return out

    # ------------------------------------------------------ serve thread
    def start(self, idle_sleep=0.005):
        """Run the decode loop on a SUPERVISED background thread:
        submit()/cancel() from any thread; collect results with
        ``wait(rid)``.

        Supervision (reliability.ServeSupervisor): a failing tick is
        retried with exponential backoff (``retry_policy``); a failing
        REQUEST (poisoned on_token callback, injected per-request fault)
        is failed individually through the per-rid failures channel
        while every other slot keeps decoding; after
        ``breaker.failure_threshold`` consecutive tick failures the
        circuit breaker opens — in-flight waiters are unblocked with
        ``CircuitOpenError``, health flips to ``degraded``, and after
        the cooldown a half-open probe tick restores ``healthy``. The
        thread itself survives everything short of interpreter
        shutdown."""
        if self._thread is not None:
            raise RuntimeError("server already started")
        self._stop.clear()
        self._thread_error = None
        with self._lock:
            self._accepting = True
            self._draining = False
            if self._health.state != HEALTHY:
                self._health.reset()   # explicit restart after stop()

        def loop():
            import time as _time
            sup = self._sup
            try:
                while True:
                    with self._lock:
                        busy = self._busy_locked()
                    if self._stop.is_set():
                        if not (self._draining and busy):
                            break
                    if not busy:
                        if (sup.breaker.state != sup.breaker.CLOSED
                                and sup.allow()):
                            # cooldown elapsed with nothing failing:
                            # close the breaker so an IDLE server does
                            # not stay degraded (and alerting) forever
                            sup.success()
                            self._recover_health()
                        # nothing to do: with telemetry on the wait is
                        # a phase like any other (no tick number, and
                        # nothing for the cost catalog, whose phases
                        # split a tick)
                        wait = None if self._tele is None else \
                            TickBoundary(None, self._tele, "idle_wait")
                        _time.sleep(idle_sleep)
                        if wait is not None:
                            wait.close()
                        continue
                    if not sup.allow():          # breaker cooldown
                        with self._lock:
                            # deadlines keep their promise even while
                            # the breaker gates ticks: expire queued/
                            # decoding requests during the cooldown
                            self._expire_locked()
                        _time.sleep(idle_sleep)
                        continue
                    try:
                        with self._lock:
                            if self._busy_locked():
                                self._step_locked()
                            if self._prefix is not None:
                                self._prefix.flush_sketch()
                        self._fire_callbacks()
                    except CallbackError as ce:
                        # the ENGINE is fine — fail exactly the
                        # requests whose streams are poisoned (typed,
                        # so wait(rid) raises it directly)
                        with self._lock:
                            for rid, err in ce.errors:
                                self._fail_request_locked(
                                    rid, CallbackError(
                                        [(rid, err)],
                                        what="on_token callback"))
                        sup.success()
                        self._recover_health()
                    except Exception as e:
                        self._on_tick_failure(e)
                    else:
                        sup.success()
                        self._recover_health()
            except BaseException as e:   # surface to waiters, don't wedge
                with self._lock:
                    self._thread_error = e
                    self._health.to(DEAD)
                    self._done_cv.notify_all()

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    def _on_tick_failure(self, e):
        """Supervised-tick failure path (called WITHOUT the lock — the
        retry backoff sleeps here)."""
        if self._tele is not None:
            self._tele.on_tick_retry()
        if self._rec is not None:
            self._rec.record("tick_retry", error=type(e).__name__)
        if self._sup.failure(e) == "open":
            with self._lock:
                self._health.to(DEGRADED)
                if self._rec is not None:
                    self._rec.record("breaker", state="open",
                                     error=type(e).__name__)
                    # capture BEFORE the teardown: the bundle freezes
                    # the parked queue / pool balance / slots as they
                    # were at the moment retries ran out
                    self._postmortem_locked("breaker_open",
                                            error=repr(e))
                self._fail_all_locked(e)
            if self._tele is not None:
                self._tele.on_breaker_open()

    def _recover_health(self):
        with self._lock:
            if self._health.state == DEGRADED:
                self._health.to(HEALTHY)

    def stop(self, timeout=60.0, drain=False):
        """Stop the serve thread. ``drain=True`` is the graceful path:
        admission closes immediately (submits raise ``ServerClosed``),
        health goes ``draining``, the loop keeps ticking until every
        queued and in-flight request has finished (results/failures
        flushed to their waiters), then the thread exits. ``drain=False``
        stops after the current tick; still-pending requests are failed
        with ``ServerClosed`` so no waiter wedges. Either way the server
        ends ``dead`` (503 on /healthz) until ``start()`` is called
        again."""
        with self._lock:
            self._accepting = False
            if drain and self._thread is not None:
                self._draining = True
                self._health.to(DRAINING)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                raise RuntimeError(
                    f"serve thread did not stop within {timeout}s (a "
                    f"tick/compile may still be running); call stop() "
                    f"again to re-join")
            self._thread = None
        with self._lock:
            self._draining = False
            if not drain:
                # hard stop: flush partials for in-flight slots (mid-
                # prefill ones record an empty partial) AND for parked
                # preempted requests (their pre-preemption partial is
                # the result), fail what never ran — every waiter
                # unblocks
                for slot in range(self.max_slots):
                    if self._slots[slot] is not None:
                        self._finish_partial_locked(slot)
                for rec in self._preempted:
                    self._flush_parked_locked(rec)
                self._preempted.clear()
                self._preempt_gauge()
                for item in self._queue:
                    self._failures[item.rid] = ServerClosed(
                        f"request {item.rid} was still queued when the "
                        f"server stopped")
                self._queue.clear()
                self._deferred_cbs.clear()   # nobody will fire them
            self._health.to(DEAD)
            self._done_cv.notify_all()

    # ---------------------- multi-replica front door (inference/router.py)
    def queue_depth(self):
        """Requests waiting for a slot — the router's least-loaded
        signal (with ``in_flight`` and ``pool_balance``). LOCK-FREE
        read of a point-in-time length: a serve thread holds the lock
        for whole ticks, and a router picking a destination must not
        queue behind one — a slightly stale load reading only costs
        placement quality, never correctness."""
        return len(self._queue)

    def in_flight(self):
        """Slots holding a live request (decoding or mid-ragged-
        prefill). Lock-free, same contract as ``queue_depth``."""
        return sum(1 for st in self._slots if st is not None)

    def preempt_pressure(self):
        """Requests parked on the preempted queue — displaced in-flight
        work this replica must REPLAY before it makes progress on new
        traffic. The router folds it into its load score (weighted
        above plain queue depth: a thrashing pool costs every resident
        request, not just the parked ones) so the fleet sheds load away
        from replicas losing the optimistic-admission gamble. Always 0
        under ``admission="reserve"``. Lock-free, same contract as
        ``queue_depth``."""
        return len(self._preempted)

    def abandon(self, rid, err):
        """Record a typed failure for ``rid`` on behalf of a caller
        that HOLDS the request outside this server (the multi-replica
        router: a foreign rid harvested off this replica's queue that
        no route ever claimed) — its waiter's ``wait(rid)`` raises
        ``err`` promptly instead of running out its timeout. No-op
        (returns False) when the rid already settled here."""
        with self._lock:
            if rid in self._results or rid in self._failures:
                return False
            self._failures[rid] = err
            self._done_cv.notify_all()
        return True

    def prefix_sketch(self):
        """Fingerprint set of this replica's radix-tree contents
        (``PrefixCache.sketch()``) — the router's prefix-affinity
        signal. Host-side only, no device reads, and LOCK-FREE: the
        cache maintains the sketch incrementally and publishes an
        immutable snapshot. Empty for the dense backend (no page cache
        to be affine to)."""
        prefix = self._prefix
        return frozenset() if prefix is None else prefix.sketch()

    def evacuate(self, flush_partials=False):
        """Harvest every QUEUED request off this replica and hand it to
        the caller (a router requeues them on sibling replicas). The
        harvested entries carry everything a resubmit needs — prompt,
        budget, the resolved sampling seed (so a sibling draws the
        identical chain), callback, and the ABSOLUTE deadline (time
        already spent queued here keeps counting against it). Nothing
        is recorded in ``failures`` for harvested rids: the caller owns
        them now.

        ``flush_partials=True`` (a DEAD replica being evacuated)
        additionally flushes every in-flight slot's partial tokens to
        its waiter exactly as ``stop(drain=False)`` does — mid-decode
        work is not replayable (the sibling would re-decode from
        scratch and double-stream), so the partial is the result. With
        the default False (e.g. a DRAINING replica) in-flight slots
        keep decoding to completion."""
        with self._lock:
            harvested = list(self._queue)
            self._queue.clear()
            if self._rec is not None:
                self._rec.record("evacuate", harvested=len(harvested),
                                 flush_partials=bool(flush_partials))
            if self._tele is not None:
                # the harvested rids leave THIS replica for good: close
                # their lifecycle spans here (the router re-counts them
                # on whatever sibling they land on)
                for item in harvested:
                    self._tele.on_cancel(item.rid)
            if flush_partials:
                for slot in range(self.max_slots):
                    if self._slots[slot] is not None:
                        st = self._finish_partial_locked(slot)
                        if self._tele is not None:
                            self._tele.on_cancel(st.rid)
                # a dead replica's parked preempted requests are
                # mid-decode work too: not replayable elsewhere without
                # double-streaming, so their partials flush to waiters
                for rec in self._preempted:
                    self._flush_parked_locked(rec)
                    if self._tele is not None:
                        self._tele.on_cancel(rec.rid)
                self._preempted.clear()
                self._preempt_gauge()
                # nobody will fire chunks on a dead replica, and every
                # live rid was just flushed
                self._deferred_cbs.clear()
                if self._tele is not None:
                    # every slot was just torn down — a dead replica
                    # must not report phantom load
                    self._tele.set_active_slots(0)
            if self._prefix is not None:
                self._prefix.flush_sketch()   # flushed slots donated
            if self._tele is not None:
                self._tele.set_queue_depth(0)
                self._pool_gauges()
            self._done_cv.notify_all()
        return harvested

    # ------------------------------------------- live KV-page migration
    def migrate_out(self, rid, partial=False, from_page=0):
        """Gather a live request's FULL resumable state so a sibling
        replica can continue it without re-prefilling: the written pool
        pages (per-shard gathers on a mesh — the ``_spill_payload``
        path the host tier proved), the resolved sampling seed, the
        emitted-token log, and the stream offset. Returns
        ``(state, payloads)`` — ``state`` is a JSON-able dict (page
        payloads carry their sha256 so the target verifies END TO END,
        not just per wire frame), ``payloads`` is one ``[k, v]``
        host-array pair per page.

        Mid-DECODE slots migrate as before. A slot still mid-PREFILL
        migrates too (ISSUE 20): a migration of a slot whose
        ``emitted`` is empty is exactly a disaggregated prefill
        handoff — the state ships ``phase="prefill"`` and
        ``filled`` (rows actually written), the target scatters the
        finished prompt pages and prefills ONLY the remainder from
        ``fill_pos``, and its own activation samples the first token
        from the resolved seed — bit-exact, zero re-prefilled rows.

        ``partial=True`` is the non-pausing PIPELINED half: ship the
        complete, not-yet-shipped prompt pages of a mid-prefill slot
        as one bounded batch and keep prefilling. Returns a fragment
        dict (``base`` page index, ``fill_pos`` progress, ``phase``)
        plus the batch; a slot already past activation returns its
        phase with no payloads, which tells a handoff pump to settle
        with a full ``migrate_out``. Partial ships never pause and
        never leak — ``migrate_abort`` resets the shipped-page cursor
        so a later full handoff re-ships everything.

        ``from_page`` skips pages the target already holds (the pump's
        closing call after partial batches landed).

        The full path PAUSES the slot, not tears it down: stepping
        (decode) or chunking (prefill) stops and its pages stay pinned
        until the caller settles the handoff with ``migrate_finish``
        (target committed — release here, donate the prompt prefix as
        usual) or ``migrate_abort`` (anything failed — resume here
        bit-exactly). Raises ``MigrationError`` when the request is
        not migratable (unknown rid, dense backend, already in
        flight); an injected ``migrate.gather`` fault fires BEFORE the
        pause, so a faulted attempt leaves the slot untouched — never
        a leak."""
        self._refuse_slot_state("migration (send_pages ships a "
                                "request's pages and nothing else)")
        from .kv_tier import _sha256
        with self._lock:
            if self._kv is None:
                raise MigrationError(
                    "cache_backend='dense' has no page pool to migrate "
                    f"(request {rid})")
            slot = next((s for s in range(self.max_slots)
                         if self._slots[s] is not None
                         and self._slots[s].rid == rid), None)
            if slot is None:
                raise MigrationError(
                    f"request {rid} holds no slot here (queued, parked, "
                    f"finished, or foreign rids are not migratable — "
                    f"evacuate/replay covers them)")
            st = self._slots[slot]
            if st.phase not in ("decode", "prefill"):
                raise MigrationError(
                    f"request {rid} is mid-{st.phase} — only decoding "
                    f"or prefilling slots migrate")
            if st.phase == "decode" and not st.emitted:
                # unobservable in practice (activation samples the
                # first token atomically with the final prefill chunk)
                # but keep the invariant typed
                raise MigrationError(
                    f"request {rid} has no resumable decode state yet")
            if rid in self._migrating:
                raise MigrationError(
                    f"request {rid} already has a migration in flight")
            if partial:
                # non-pausing: no gather fault either — a pump polls
                # this dozens of times per handoff and chaos belongs
                # on the wire (net.page_send), not on every poll
                return self._migrate_partial_locked(slot, st)
            if self._faults is not None:
                self._faults.check(faults.MIGRATE_GATHER, rid=rid)
            t0 = self._tele.migration_started() \
                if self._tele is not None else None
            if st.phase == "decode":
                # the LAST emitted token is the decode program's
                # pending input — sampled but not yet written, so the
                # target rewrites nothing and re-prefills nothing
                written = st.prompt_len + len(st.emitted) - 1
            else:
                # empty-`emitted` prefill handoff: everything below
                # `filled` is final (chunk boundaries don't change the
                # rows); the target resumes chunking at fill_pos
                written = st.filled
            npages = self._npages_for(written)
            base = max(0, min(int(from_page), npages))
            pages = self._kv.slot_pages(slot)[base:npages]
            payloads = [self._spill_payload(p) for p in pages]
            if self._costs is not None:
                self._charge_transfer(
                    "page_migrate",
                    2 * len(payloads) * self._kv.page_size
                    * self._row_nbytes())
            remaining = None if st.deadline is None else \
                max(0.0, st.deadline - self._clock.now())
            state = {
                "rid": rid,
                "ids": [int(t) for t in st.ids],
                "prompt_len": int(st.prompt_len),
                "budget": int(st.budget),
                "seed": int(st.seed),
                "emitted": [int(t) for t in st.emitted],
                "replayed": [int(t) for t in st.replayed],
                "streamed": int(st.streamed),
                "preempts": int(st.preempts),
                "priority": int(st.priority),
                "n_pre": int(st.n_pre),
                "deadline_s": remaining,
                "page_size": int(self._kv.page_size),
                "written": int(written),
                "phase": st.phase,
                "fill_pos": int(st.fill_pos),
                "filled": int(st.filled),
                "base": int(base),
                "sha256": [_sha256(p) for p in payloads],
            }
            # pause: the decode tick skips inactive rows, the ragged
            # prefill planner skips slots out of the fifo, and the
            # device write cursor parks on the null page —
            # resume re-pushes tok/t/key exactly as _activate does (or
            # re-queues the fifo for a prefill slot), so nothing the
            # device scribbles while paused is ever read
            prior = st.phase
            self._park_slot(slot)
            st.phase = "migrating"
            if prior == "prefill" and slot in self._prefill_fifo:
                self._prefill_fifo.remove(slot)
            self._migrating[rid] = (slot, t0, prior)
            if self._rec is not None:
                self._rec.record("migrate_out", rid=rid,
                                 pages=npages - base, phase=prior,
                                 tokens=len(st.emitted))
            if st.journey is not None:
                if prior == "prefill":
                    st.journey.event("handoff", at="source",
                                     pages=npages - base,
                                     filled=int(st.filled))
                else:
                    st.journey.event("migrating", at="source",
                                     pages=npages,
                                     tokens=len(st.emitted))
            return state, payloads

    def _migrate_partial_locked(self, slot, st):
        """One bounded, NON-pausing batch of a mid-prefill slot's
        complete, not-yet-shipped pages (``migrate_out(partial=True)``
        body). The fragment's ``base``/``fill_pos``/``phase`` tell the
        handoff pump where the stream stands; the slot keeps
        prefilling throughout, so a dead pump costs nothing here."""
        from .kv_tier import _sha256
        frag = {"rid": int(st.rid), "partial": True,
                "phase": st.phase,
                "page_size": int(self._kv.page_size),
                "prompt_len": int(st.prompt_len),
                "fill_pos": int(st.fill_pos),
                "filled": int(st.filled),
                "base": int(st.sent_pages),
                "sha256": []}
        if st.phase != "prefill":
            # past activation: nothing streams mid-decode — the full
            # migrate_out ships the balance (and the page beyond
            # sent_pages that activation may have completed)
            return frag, []
        whole = st.filled // self._kv.page_size
        base = st.sent_pages
        if whole <= base:
            return frag, []
        pages = self._kv.slot_pages(slot)[base:whole]
        payloads = [self._spill_payload(p) for p in pages]
        if self._costs is not None:
            self._charge_transfer(
                "page_migrate",
                2 * len(payloads) * self._kv.page_size
                * self._row_nbytes())
        st.sent_pages = whole
        self.stats["handoff_pages_out"] += len(payloads)
        frag["sha256"] = [_sha256(p) for p in payloads]
        if self._rec is not None:
            self._rec.record("handoff_partial", rid=st.rid, base=base,
                             pages=len(payloads))
        if st.journey is not None:
            st.journey.event("handoff", at="source", base=base,
                             pages=len(payloads))
        return frag, payloads

    def migrate_finish(self, rid):
        """Commit a migration: the target restored ``rid`` (and owns its
        waiter now), so release the paused slot's pages here — through
        the normal teardown, so the written prompt prefix is DONATED to
        the prefix cache exactly like a finished request's. Counts
        ``server_migrations_total{result="ok"}`` with the pause-to-
        commit wall in ``serving_migration_seconds``. Nothing lands in
        results or failures: like an evacuated rid, the caller owns the
        request now."""
        with self._lock:
            ent = self._migrating.pop(rid, None)
            if ent is None:
                raise MigrationError(
                    f"request {rid} has no migration in flight")
            slot, t0 = ent[0], ent[1]
            st = self._slots[slot]
            if st is not None and st.rid == rid:
                if st.journey is not None:
                    st.journey.event("migrating", at="source",
                                     handoff=True)
                self._release_slot(slot)
            if self._rec is not None:
                self._rec.record("migrate_done", rid=rid)
            self.stats["migrations"] += 1
            if self._tele is not None:
                self._tele.on_migration("ok", t0)
                self._tele.on_cancel(rid)   # lifecycle closed HERE; the
                #                             target counts nothing (no
                #                             submit/admit there either)
                self._pool_gauges()
            self._done_cv.notify_all()

    def migrate_abort(self, rid):
        """Abort a migration and RESUME the paused slot bit-exactly.
        A mid-decode pause re-pushes the pending token, write position,
        and the PRNG key recomputed from the resolved seed
        (``PRNGKey(seed)`` advanced one split per emitted token — the
        identical chain the device carried), exactly as ``_activate``
        primes a fresh slot. A mid-PREFILL pause (empty-``emitted``
        handoff) simply re-queues the slot on the ragged fifo: the
        planner resumes chunking at ``fill_pos`` and activation fires
        here as if no handoff was ever attempted (the shipped-page
        cursor resets so a later handoff re-ships everything). The
        caller degrades to evacuate+replay or simply lets the slot
        keep going here; either way zero pages moved and zero leaked.
        Counts ``{result="fallback"}`` and freezes a postmortem (its
        ``migration`` section carries the in-flight/outcome state).
        Returns False when nothing was in flight for ``rid``."""
        with self._lock:
            ent = self._migrating.pop(rid, None)
            if ent is None:
                return False
            slot, t0, prior = ent
            st = self._slots[slot]
            if st is None or st.rid != rid:
                return False   # torn down behind the pause (hard stop)
            st.sent_pages = 0
            if prior == "prefill":
                st.phase = "prefill"
                if slot not in self._prefill_fifo:
                    self._prefill_fifo.append(slot)
                # stays parked until activation, like any admitted
                # mid-prefill slot
                self._park_slot(slot)
            else:
                st.phase = "decode"
                key = jax.random.PRNGKey(st.seed)
                if self.do_sample:
                    for _ in range(len(st.emitted)):
                        key, _ = jax.random.split(key)
                self._pending_key[slot] = key
                self._pending_tok[slot] = int(st.emitted[-1])
                self._pending_t[slot] = \
                    st.prompt_len + len(st.emitted) - 1
                self._active[slot] = True
            self.stats["migration_fallbacks"] += 1
            if self._rec is not None:
                self._rec.record("migrate_fallback", rid=rid)
                self._postmortem_locked("migration_fallback")
            if st.journey is not None:
                st.journey.event("migrating", at="source", fallback=True)
            if self._tele is not None:
                self._tele.on_migration("fallback", t0)
            return True

    def _check_restore_state(self, state):
        """Shared ``migrate_in``/``migrate_in_commit`` validation:
        page-size and role gates, phase-aware written-row accounting.
        Returns ``(phase, emitted, prompt_len, budget, written)``;
        every refusal is a typed ``MigrationError`` raised BEFORE any
        allocation."""
        if int(state.get("page_size", self.page_size)) \
                != self.page_size:
            raise MigrationError(
                f"page-size mismatch: source pages are "
                f"{state.get('page_size')} tokens, this pool's are "
                f"{self.page_size} — migration ships pages whole")
        emitted = [int(t) for t in state.get("emitted") or ()]
        prompt_len = int(state["prompt_len"])
        budget = int(state["budget"])
        phase = str(state.get("phase") or "decode")
        if phase == "decode":
            if self.role == "prefill":
                raise MigrationError(
                    "replica role 'prefill' refuses decode-phase "
                    "admissions — hand mid-decode state to a decode "
                    "or hybrid replica")
            if not emitted or len(emitted) >= budget:
                raise MigrationError(
                    "only mid-decode state restores (source sends "
                    "nothing for queued/finished requests)")
            written = prompt_len + len(emitted) - 1
        elif phase == "prefill":
            # the empty-`emitted` handoff (ISSUE 20): a slot still
            # prefilling ships its written prompt prefix; the
            # remaining rows prefill HERE and activation samples the
            # first token from this replica's own ragged launch —
            # bit-exact, because chunk boundaries never change the
            # written rows and the resolved seed travels with them
            if emitted:
                raise MigrationError(
                    "a prefill-phase handoff cannot carry emitted "
                    "tokens (activation would have flipped the slot "
                    "to decode)")
            written = int(state.get("filled") or 0)
            if not 0 <= written <= prompt_len:
                raise MigrationError(
                    f"filled={written} rows outside the prompt "
                    f"({prompt_len} tokens)")
        else:
            raise MigrationError(
                f"phase {phase!r} state does not restore (sources "
                f"send decoding or prefilling slots only)")
        return phase, emitted, prompt_len, budget, written

    def _restore_slot_locked(self, slot, state, phase, emitted,
                             prompt_len, budget, written,
                             on_token, journey):
        """Build and prime the restored ``_Slot`` (the shared tail of
        ``migrate_in`` and ``migrate_in_commit``): a decode-phase
        restore resumes the chain exactly where the source paused it;
        a prefill-phase restore re-queues the ragged fifo at
        ``fill_pos`` so the planner finishes the prompt and activation
        fires HERE. Returns the request's NEW rid."""
        rid = self._next_rid
        self._next_rid += 1
        dl = state.get("deadline_s")
        st = _Slot(rid, np.asarray(state["ids"], np.int32),
                   prompt_len, budget, on_token,
                   None if dl is None
                   else self._clock.now() + float(dl))
        st.seed = int(state["seed"])
        st.emitted = list(emitted)
        st.streamed = int(state.get("streamed", 0))
        st.replayed = tuple(int(t) for t in
                            state.get("replayed", ()))
        st.preempts = int(state.get("preempts", 0))
        st.priority = int(state.get("priority", 0))
        st.n_pre = int(state.get("n_pre", 0))
        st.journey = journey
        self._slots[slot] = st
        if phase == "prefill":
            # remaining prompt rows prefill here; the ragged planner
            # picks the slot up next tick and _activate samples the
            # first token from PRNGKey(seed) — the identical chain
            st.phase = "prefill"
            st.fill_pos = st.filled = written
            self._prefill_fifo.append(slot)
            # parked until activation, like any admitted mid-prefill slot
            self._park_slot(slot)
        else:
            # prime the decode chain exactly where the source paused
            # it: pending input = last emitted token, write position =
            # the first unwritten row, PRNG key = seed advanced one
            # split per emitted token (greedy never consumes it)
            key = jax.random.PRNGKey(st.seed)
            if self.do_sample:
                for _ in range(len(emitted)):
                    key, _ = jax.random.split(key)
            self._pending_key[slot] = key
            self._pending_tok[slot] = int(emitted[-1])
            self._pending_t[slot] = written
            self._active[slot] = True
        self.stats["migrated_in"] += 1
        if journey is not None:
            if phase == "prefill":
                journey.event("handoff", at="target", slot=slot,
                              filled=written)
            else:
                journey.event("migrating", at="target", slot=slot,
                              tokens=len(emitted))
        if self._tele is not None:
            self._pool_gauges()
        self._done_cv.notify_all()
        return rid

    def migrate_in(self, state, payloads, on_token=None, journey=None):
        """Restore a migrated request into THIS replica and resume it
        mid-chain: fresh pool pages through the normal ``admit_slot``
        path, one batched scatter of the received page payloads, and
        the slot primed exactly as ``_activate`` would have left it at
        this point of the chain — so the token stream continues
        bit-exactly, greedy or seeded-sampled, with ZERO re-prefill
        dispatches for the shipped rows (the scatter is priced as
        ``page_migrate`` bytes, never counted as a prefill).
        Decode-phase state resumes decoding; prefill-phase state (the
        ISSUE-20 empty-``emitted`` handoff) resumes CHUNKING at
        ``fill_pos`` — only the unshipped remainder of the prompt ever
        prefills here. Returns the request's NEW rid (``wait`` on it
        as usual).

        Every refusal is typed and leak-free: an injected
        ``migrate.restore`` fault, a page failing its end-to-end sha256
        check, a geometry/role mismatch, or a pipelined-stream state
        (``base`` > 0 restores through ``migrate_in_begin``/
        ``migrate_in_pages``/``migrate_in_commit``) raises
        ``MigrationError`` BEFORE any allocation; ``OutOfPages`` (no
        free slot / pool exhausted) propagates from the admit; a
        scatter failure rolls the fresh pages back. The source aborts
        and the caller replays — never a request failure."""
        self._refuse_slot_state("migration (send_pages ships a "
                                "request's pages and nothing else)")
        from .kv_tier import _sha256
        with self._lock:
            if self._kv is None:
                raise MigrationError(
                    "cache_backend='dense' has no page pool to restore "
                    "migrated pages into")
            if not self._accepting:
                raise MigrationError(
                    "replica is draining/stopped — not accepting "
                    "migrated requests")
            if self._faults is not None:
                self._faults.check(faults.MIGRATE_RESTORE,
                                   rid=state.get("rid"))
            if int(state.get("base") or 0):
                raise MigrationError(
                    "state carries a page base — a pipelined partial "
                    "stream restores through migrate_in_begin/"
                    "migrate_in_pages/migrate_in_commit, not a "
                    "one-shot migrate_in")
            phase, emitted, prompt_len, budget, written = \
                self._check_restore_state(state)
            if len(payloads) != self._npages_for(written):
                raise MigrationError(
                    f"page-count mismatch: {len(payloads)} payloads for "
                    f"{written} written rows "
                    f"(expected {self._npages_for(written)})")
            for i, want in enumerate(state.get("sha256") or ()):
                if _sha256(payloads[i]) != want:
                    raise MigrationError(
                        f"migrated page {i}/{len(payloads)} failed its "
                        f"end-to-end sha256 check")
            slot = next((s for s in range(self.max_slots)
                         if self._slots[s] is None), None)
            if slot is None:
                raise OutOfPages(
                    f"no free slot for a migrated request "
                    f"(all {self.max_slots} busy)")
            remaining = budget - len(emitted)
            # a prefill restore sizes its extent off the FULL prompt
            # (the unshipped remainder still needs rows), a decode
            # restore off the written rows — both grow as usual under
            # optimistic admission
            extent = self._extent_tokens(
                prompt_len if phase == "prefill" else written,
                remaining)
            own = self._kv.admit_slot(slot, max(written, extent))
            if payloads:
                try:
                    self._write_pages(own[:len(payloads)], payloads)
                except Exception:
                    self._kv.free_slot(slot)
                    raise
            if self._costs is not None and payloads:
                # priced like spill/restore — bytes both ways, zero
                # FLOPs, and NOT a prefill dispatch: the acceptance
                # counter (stats["prefill_dispatches"]) stays frozen
                self._charge_transfer(
                    "page_migrate",
                    2 * len(payloads) * self.page_size
                    * self._row_nbytes())
            rid = self._restore_slot_locked(
                slot, state, phase, emitted, prompt_len, budget,
                written, on_token, journey)
            if self._rec is not None:
                self._rec.record("migrate_in", rid=rid,
                                 pages=len(payloads), phase=phase,
                                 tokens=len(emitted))
            return rid

    # --------------------- pipelined (staged) prefill-handoff restore
    def migrate_in_begin(self, state):
        """Open a PIPELINED restore (disaggregated prefill handoff,
        ISSUE 20): allocate the slot and its full page extent NOW so
        page batches scatter as the source's chunks complete
        (``migrate_in_pages``) and the first decode tick launches the
        moment the commit lands (``migrate_in_commit``) instead of
        after a monolithic gather. ``state`` needs ``ids``/
        ``prompt_len``/``budget``/``page_size``/``seed`` — the
        commit's full state re-verifies everything that matters.
        Returns an opaque transfer handle; ``migrate_in_abort``
        releases every page if the handoff dies mid-stream, so zero
        leaks either way. The placeholder slot counts toward
        ``in_flight`` (it holds real pool pages) but never ticks: it
        is not active, not on the prefill fifo, and has no deadline
        until commit."""
        self._refuse_slot_state("migration (send_pages ships a "
                                "request's pages and nothing else)")
        with self._lock:
            if self._kv is None:
                raise MigrationError(
                    "cache_backend='dense' has no page pool to restore "
                    "migrated pages into")
            if not self._accepting:
                raise MigrationError(
                    "replica is draining/stopped — not accepting "
                    "migrated requests")
            if self._faults is not None:
                self._faults.check(faults.MIGRATE_RESTORE,
                                   rid=state.get("rid"))
            if int(state.get("page_size", self.page_size)) \
                    != self.page_size:
                raise MigrationError(
                    f"page-size mismatch: source pages are "
                    f"{state.get('page_size')} tokens, this pool's "
                    f"are {self.page_size} — migration ships pages "
                    f"whole")
            if self.role == "prefill" and \
                    str(state.get("phase") or "decode") == "decode":
                raise MigrationError(
                    "replica role 'prefill' refuses decode-phase "
                    "admissions — hand mid-decode state to a decode "
                    "or hybrid replica")
            prompt_len = int(state["prompt_len"])
            budget = int(state["budget"])
            slot = next((s for s in range(self.max_slots)
                         if self._slots[s] is None), None)
            if slot is None:
                raise OutOfPages(
                    f"no free slot for a staged restore "
                    f"(all {self.max_slots} busy)")
            own = self._kv.admit_slot(
                slot, self._extent_tokens(prompt_len, budget))
            rid = self._next_rid
            self._next_rid += 1
            st = _Slot(rid, np.asarray(state["ids"], np.int32),
                       prompt_len, budget)
            st.phase = "staging"
            st.fill_pos = st.filled = 0
            st.seed = int(state.get("seed", 0))
            self._slots[slot] = st
            self._park_slot(slot)
            handle = self._next_xfer
            self._next_xfer += 1
            self._staging[handle] = {"slot": slot, "own": list(own),
                                     "rid": rid, "got": set()}
            if self._rec is not None:
                self._rec.record("handoff_begin", rid=rid, slot=slot,
                                 pages=len(own))
            if self._tele is not None:
                self._pool_gauges()
            return handle

    def migrate_in_pages(self, handle, base, payloads, sha256=None):
        """Scatter one pipelined page batch at page index ``base`` of
        the staged restore ``handle`` — the target half of
        ``migrate_out(partial=True)``. Batches may arrive in any
        order; the commit verifies full coverage. Raises
        ``MigrationError`` (unknown handle, sha256 failure, pages
        outside the staged extent) with the staging KEPT — the caller
        decides between retrying and ``migrate_in_abort``."""
        from .kv_tier import _sha256
        with self._lock:
            ent = self._staging.get(handle)
            if ent is None:
                raise MigrationError(
                    f"no staged restore open for handle {handle!r}")
            if sha256:
                for i, want in enumerate(sha256):
                    if _sha256(payloads[i]) != want:
                        raise MigrationError(
                            f"staged page {int(base) + i} failed its "
                            f"end-to-end sha256 check")
            own = ent["own"]
            base = int(base)
            if base < 0 or base + len(payloads) > len(own):
                raise MigrationError(
                    f"staged pages [{base}, {base + len(payloads)}) "
                    f"fall outside the slot's {len(own)}-page extent")
            if payloads:
                self._write_pages(own[base:base + len(payloads)],
                                  payloads)
                if self._costs is not None:
                    self._charge_transfer(
                        "page_migrate",
                        2 * len(payloads) * self.page_size
                        * self._row_nbytes())
                ent["got"].update(range(base, base + len(payloads)))
                self.stats["handoff_pages_in"] += len(payloads)
            if self._rec is not None:
                self._rec.record("handoff_pages", rid=ent["rid"],
                                 base=base, pages=len(payloads))
            return len(payloads)

    def migrate_in_commit(self, handle, state, payloads=(),
                          on_token=None, journey=None):
        """Close a pipelined restore: scatter the closing batch (the
        full ``migrate_out(..., from_page=...)`` balance, page base in
        ``state["base"]``), verify every page of the written extent
        arrived, and flip the placeholder into a live slot exactly as
        ``migrate_in`` would — prefill-phase state re-queues the
        ragged fifo at ``fill_pos``, decode-phase state resumes the
        chain. Returns the request's NEW rid. Any refusal (coverage
        gap, sha256, role/geometry mismatch, ids drift from the
        ``migrate_in_begin`` state) raises typed with the staging
        kept, so the caller can still ``migrate_in_abort`` — zero
        leaks."""
        from .kv_tier import _sha256
        with self._lock:
            ent = self._staging.get(handle)
            if ent is None:
                raise MigrationError(
                    f"no staged restore open for handle {handle!r}")
            phase, emitted, prompt_len, budget, written = \
                self._check_restore_state(state)
            slot, own = ent["slot"], ent["own"]
            ph = self._slots[slot]
            if ph is None or ph.rid != ent["rid"]:
                raise MigrationError(
                    "staged slot was torn down behind the transfer "
                    "(hard stop) — nothing to commit")
            if prompt_len != ph.prompt_len or budget != ph.budget \
                    or not np.array_equal(
                        np.asarray(state["ids"], np.int32), ph.ids):
                raise MigrationError(
                    "commit state does not match the migrate_in_begin "
                    "request (ids/prompt_len/budget drift)")
            need = self._npages_for(written)
            base = int(state.get("base") or 0)
            if need > len(own):
                raise MigrationError(
                    f"{need} written pages exceed the staged "
                    f"{len(own)}-page extent")
            if base + len(payloads) != need:
                raise MigrationError(
                    f"closing batch [{base}, {base + len(payloads)}) "
                    f"does not reach the written extent ({need} "
                    f"pages)")
            missing = sorted(set(range(base)) - ent["got"])
            if missing:
                raise MigrationError(
                    f"staged restore incomplete: pages {missing} "
                    f"never arrived before the commit")
            for i, want in enumerate(state.get("sha256") or ()):
                if _sha256(payloads[i]) != want:
                    raise MigrationError(
                        f"closing page {base + i} failed its "
                        f"end-to-end sha256 check")
            if payloads:
                self._write_pages(own[base:base + len(payloads)],
                                  payloads)
                if self._costs is not None:
                    self._charge_transfer(
                        "page_migrate",
                        2 * len(payloads) * self.page_size
                        * self._row_nbytes())
            # flip the placeholder into the live slot: _restore_slot
            # mints the rid the waiter sees (the placeholder rid was
            # never returned to anyone)
            self._slots[slot] = None
            self._staging.pop(handle)
            rid = self._restore_slot_locked(
                slot, state, phase, emitted, prompt_len, budget,
                written, on_token, journey)
            if self._rec is not None:
                self._rec.record("handoff_commit", rid=rid,
                                 pages=need, phase=phase)
            return rid

    def migrate_in_abort(self, handle):
        """Tear down a staged restore that will never commit (source
        died, pump failed, router fell back): release every staged
        page straight back to the allocator — no donation, the rows
        may be half-written — and drop the placeholder. Returns False
        when nothing was staged for ``handle`` (idempotent, like
        ``migrate_abort``)."""
        with self._lock:
            ent = self._staging.pop(handle, None)
            if ent is None:
                return False
            slot = ent["slot"]
            st = self._slots[slot]
            if st is not None and st.rid == ent["rid"]:
                self._slots[slot] = None
                self._park_slot(slot)
                pages = self._kv.detach_slot(slot)
                if pages:
                    self._kv.release(pages)
            if self._rec is not None:
                self._rec.record("handoff_abort", rid=ent["rid"])
            if self._tele is not None:
                self._pool_gauges()
            return True

    def kill(self, timeout=60.0):
        """Simulate a replica crash (failover drills, chaos suites):
        stop the serve thread NOW and mark the server ``dead``, but —
        unlike ``stop()`` — leave the queue and in-flight slots exactly
        as they are: no failures recorded, no partials flushed. That is
        the state a router finds after a real crash and harvests with
        ``evacuate(flush_partials=True)``. ``start()`` restarts as
        usual."""
        with self._lock:
            self._accepting = False
            self._draining = False
            if self._rec is not None:
                self._rec.record("killed")
                # the crash-scene snapshot the router's harvest will
                # tear apart: queue + slots exactly as the "crash" left
                # them
                self._postmortem_locked("killed")
            self._health.to(DEAD)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                raise RuntimeError(
                    f"serve thread did not stop within {timeout}s (a "
                    f"tick/compile may still be running); call kill() "
                    f"again to re-join")
            self._thread = None
        with self._lock:
            self._done_cv.notify_all()

    def wait(self, rid, timeout=120.0):
        """Block until ``rid`` finishes (requires start()); returns its
        new tokens. Typed reliability failures (``DeadlineExceeded``,
        ``QueueFullError``, ``CircuitOpenError``, ...) are raised
        directly; other per-request errors are wrapped in a
        ``RuntimeError``; a dead serve thread raises for every
        waiter."""
        import time as _time
        deadline = _time.monotonic() + timeout
        with self._done_cv:
            while True:
                if rid in self._results:
                    return self._results.pop(rid)
                if rid in self._failures:
                    e = self._failures.pop(rid)
                    if isinstance(e, ReliabilityError):
                        raise e
                    raise RuntimeError(
                        f"request {rid} failed at admission: {e}") from e
                if self._thread_error is not None:
                    raise RuntimeError(
                        "serve thread died") from self._thread_error
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"request {rid} not finished in {timeout}s")
                self._done_cv.wait(timeout=min(remaining, 1.0))

    @property
    def failures(self):
        """{rid: exception} for requests whose admission failed:
        pending ones (start()/wait() mode — ``wait(rid)`` pops and
        raises each) plus those drained by the last ``run()``."""
        with self._lock:
            return {**self._run_failures, **self._failures}
