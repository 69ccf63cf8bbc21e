"""Continuous-batching serving engine (paper §5.2 made servable).

``serve_loop.generate`` — the parity oracle — prefills token-by-token in
a Python loop and only handles one batch of equal-length prompts.  This
engine turns the same pruned/packed weights into a subsystem that keeps
the accelerator saturated across ragged, continuously-arriving requests:

  * **paged KV cache** (default) — K/V lives in a SHARED page pool
    ``(layers, n_pages, page_size, kv, hd)`` with a host-side free-list
    allocator (serving/pages.py); each lane maps logical cache slots to
    pool pages through a ``(max_pages,)`` block table carried on-device
    through the decode slab. Attention gathers ONLY a lane's first
    ``read_pages`` pages — bucketed to the next power of two of the live
    frontier so the jit cache stays O(log max_pages) — so per-token
    attention bytes scale with ``ceil(frontier / page_size)`` instead of
    ``max_len``. Total servable context is bounded by POOL PAGES, not
    ``max_batch × max_len``: ``max_len`` can be set far beyond what a
    contiguous ``(B, max_len)`` slab could ever hold, and one lane may
    take nearly the whole pool. Greedy decode through the paged path is
    bitwise-identical to the contiguous one (``paged=False``) — the slot
    numbering, rope, and masking are shared; only the storage moves;
  * **lanes** — ``max_batch`` batch rows; a completed sequence frees its
    lane (and pages) for the next queued request (slot reuse);
  * **per-lane frontiers** — every lane carries its OWN cache-slot write
    position (a ``(max_batch,)`` vector, not a shared scalar), so a
    freed lane resets its frontier to 0 and admits a new prompt
    immediately instead of leaking cache slots until the batch drains;
  * **decode slabs** — the token loop runs ON-DEVICE: one jitted
    ``lax.scan`` over ``slab_k`` greedy steps (serving/step.py) carries
    per-lane pending token / frontier / remaining budget / live flags
    (+ block tables) and emits a ``(max_batch, slab_k)`` token block, so
    the host syncs once per slab instead of once per token; lanes that
    hit eos, their budget, or the cache end mid-slab are masked out
    on-device and their trailing tokens discarded on the host — greedy
    decode stays bitwise-identical to the per-token path and the oracle;
  * **persistent device state** — pending/frontier/offsets/remaining/
    live (and block tables) live on the accelerator between slabs; the
    host re-uploads them only at admission/eviction events;
  * **donated KV cache** — every jitted call that takes the cache and
    returns it (slab, mixed step, prefill chunk, page copy, page
    upload) donates it, so the device updates it in place and one pool
    lives at a time; ``self.cache`` is rebound to the result and the
    arrays passed in are deleted (serving/recovery.py says what a call
    that dies after its dispatch leaves behind);
  * **right-aligned ragged prompts** — prompts admitted together are
    prefilled as one group in slots ``[0, W)`` (``W`` = longest prompt
    in the group); the left-pad ``offset = W - plen`` feeds rope/masking
    the true logical positions (models/attention.py
    ``_cache_positions``);
  * **chunked batched prefill** — prompts enter through
    ``registry.prefill_chunk`` / ``paged_prefill_chunk`` in whole
    ``(B, C)`` chunks per jitted call; running lanes are shielded from
    the writes by ``lane_mask``;
  * **admission** — ``scheduler.FIFOScheduler``: any free lane takes the
    head request; paged engines additionally gate the admission group on
    FREE PAGES (a group that would overdraw the pool waits — strict
    FIFO, head-of-line blocking by design). Pages for a request's whole
    extent (group width + decode budget, capped at ``max_len``) are
    pinned at admission, so a slab can never run out of pages mid-slab;
  * **mixed batching** (``mixed=True``, paged only) — the phased loop
    above still STALLS decode during admission: ``_admit`` runs a
    blocking chunked-prefill loop, during which every running lane
    waits. The mixed engine fuses the two into one token-budgeted
    jitted step (serving/step.py ``make_mixed_step``): running lanes
    contribute ONE decode token each and admitting lanes contribute a
    prefill chunk, as per-lane variable-length query runs through the
    same transformer stack — decode throughput is never zeroed by an
    arriving prompt, and the tails of several prefix-cached admissions
    coalesce into one call. The scheduler becomes token-budgeted
    (``prefill_token_budget``): decode tokens are spent first, the
    remainder is split chunk-granularly across admitting prompts, so a
    long prompt is prefilled incrementally instead of monopolizing a
    step. When no prompt is in flight the engine drops back to decode
    slabs (one host sync per ``slab_k`` tokens). Greedy tokens are
    bitwise-identical to the phased engine and the oracle — the phased
    path (``mixed=False``, the default) is the parity baseline;
  * **prefix cache** (``prefix_cache=True``, paged only) — a host-side
    radix tree over token IDs (serving/prefix_cache.py) shares pool
    pages across requests: at admission the prompt's longest cached
    prefix is matched, the matched pages are REFCOUNT-pinned and dropped
    straight into the lane's block table (zero prefill compute, zero KV
    writes for them), and only the uncovered tail is chunk-prefilled; a
    partially-filled boundary page is COPY-ON-WRITE duplicated first, so
    decode never writes a page with refcount > 1. Finished sequences are
    inserted back into the tree (their pages park as cached-idle —
    reclaimed LRU-first under pool pressure), and the admission gate
    sees the EFFECTIVE page cost: shared pages are free, capacity is
    free + reclaimable-cached. Prefix-cached admissions prefill per-lane
    at ``offset == 0`` (sharing is positional: a pool page holds rope'd
    K at canonical positions), instead of as one right-aligned group —
    greedy tokens stay bitwise-identical either way.

Greedy decode only (the paper's serving benchmark); temperature sampling
stays on the ``serve_loop`` oracle path.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import registry
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.serving.faults import (BackpressureError, DeadlineExceededError,
                                  LaneFaultError, OffloadCapacityError,
                                  OffloadCorruptionError,
                                  RequestCancelledError)
from repro.serving.offload import HostKVStore
from repro.serving.pages import PagePool
from repro.serving.prefix_cache import Match, PrefixCache
from repro.serving.scheduler import FIFOScheduler, Request
from repro.serving.step import (make_copy_pages_step,
                                make_decode_slab_step,
                                make_gather_pages_step,
                                make_mixed_step,
                                make_paged_decode_slab_step,
                                make_paged_prefill_chunk_step,
                                make_prefill_chunk_step,
                                make_scatter_pages_step)


@dataclasses.dataclass
class GenResult:
    """Finished request: prompt + generated tokens (greedy).

    A request that FAILED (quarantined lane, cancellation, deadline,
    corrupted offload record) still flows out through the same channel,
    with the structured exception in ``error`` and ``generated``
    holding whatever tokens it emitted before failing — the engine
    never silently drops a submitted uid."""
    uid: int
    prompt: np.ndarray
    generated: np.ndarray
    truncated: bool = False    # hit the lane's slot cap before budget
    ttft_s: float = 0.0        # submit -> first token (monotonic clock)
    error: Exception | None = None

    @property
    def ok(self) -> bool:
        return self.error is None

    @property
    def tokens(self) -> np.ndarray:
        return np.concatenate([self.prompt, self.generated])


@dataclasses.dataclass
class _Lane:
    req: Request
    offset: int                # left-pad: group width - plen
    generated: list[int]
    pages: list[int] = dataclasses.field(default_factory=list)
    # host-sync timestamp of each generated token (TTFT / inter-token
    # latency observability; tokens folded at one sync share it)
    token_times: list[float] = dataclasses.field(default_factory=list)
    # weight GENERATION the lane was admitted under (serving/hotswap.py):
    # the lane decodes with exactly these params until it finishes, so a
    # mid-stream hot-swap never changes an in-flight request's numerics
    gen: int = 0


@dataclasses.dataclass
class _Preempted:
    """A lane frozen off-device: everything needed to resume decode at
    the saved frontier with zero re-prefill. Exclusively owned pages
    went to the host offload store (keyed by ``req.uid``);
    prefix-shared pages stayed pinned on-device (``pinned``: logical
    block-table index -> pool page, reference HELD through the
    preemption)."""
    req: Request
    offset: int
    generated: list[int]
    token_times: list[float]
    pending: int               # next token to feed (KV not yet written)
    frontier: int              # cache slot decode resumes at
    remaining: int             # decode budget left
    n_pages: int               # logical pages the block table covered
    pinned: dict[int, int]
    # crash-salvaged (serving/recovery.py) rather than preempted: its
    # restore counts toward recovered_zero_reprefill
    recovered: bool = False
    # weight generation the lane decoded under; restore re-pins it so a
    # hot-swap while the lane was frozen cannot change its numerics
    gen: int = 0


def _pow2_bucket(n: int, cap: int) -> int:
    """Smallest power of two >= n, clipped to [1, cap] — the paged
    attention read width (bounds the jit cache to O(log cap) entries)."""
    return max(1, min(cap, 1 << max(0, (n - 1).bit_length())))


# every engine stat, declared ONCE with its kind (obs/metrics.py):
# reset_stats / snapshot / Prometheus exposition all derive from the
# registry, so adding a metric here is the whole job — there is no
# second list to forget (the drift bug class that bit PR 6 and PR 7)
_METRICS = [
    ("counter", "prefill_chunks", "jitted prefill chunk calls"),
    ("counter", "prefill_tokens", "prompt tokens actually computed"),
    ("counter", "decode_slabs", "on-device decode slab calls"),
    ("counter", "decode_steps", "decode steps (slab_k per slab)"),
    ("counter", "decode_tokens", "tokens emitted by decode"),
    ("counter", "generated_tokens", "all tokens emitted"),
    ("counter", "prefill_s", "seconds in prefill calls"),
    ("counter", "decode_s", "seconds in decode slabs"),
    ("counter", "admitted", "requests admitted to lanes"),
    ("counter", "evicted", "lanes freed (finish or failure)"),
    ("counter", "truncated", "requests that hit the slot cap"),
    # mixed batching: fused decode+prefill calls, the time spent in
    # them, and the stall counter — a stalled decode step is one
    # blocking prefill call that ran while live decode lanes waited
    # (phased admission; structurally 0 when mixed)
    ("counter", "mixed_steps", "fused decode+prefill calls"),
    ("counter", "mixed_s", "seconds in fused mixed calls"),
    ("counter", "stalled_decode_steps",
     "blocking prefill calls that stalled live decode lanes"),
    # paged attention read accounting (page units): what the
    # block-table gather touched vs a dense max_len read
    ("counter", "pages_read", "pages the paged attention gathered"),
    ("counter", "pages_read_dense_equiv",
     "pages a dense max_len read would have touched"),
    ("gauge", "peak_kv_pages", "page pool in-use high-water"),
    # scheduler observability: queue depth high-water, page-gate
    # rejections, request queued time
    ("gauge", "queue_depth_peak", "admission queue depth high-water"),
    ("counter", "admission_rejections",
     "distinct queue heads blocked by the page gate"),
    ("counter", "queued_s_total", "total seconds requests queued"),
    ("gauge", "queued_s_max", "longest single queued wait"),
    # prefix-cache accounting: prompt_tokens is the demand,
    # prefill_tokens what was computed, the difference the radix hits
    ("counter", "prompt_tokens", "prompt tokens submitted"),
    ("counter", "prefix_hits", "admissions with a radix-tree match"),
    ("counter", "prefix_misses", "admissions with no match"),
    ("counter", "prefill_tokens_skipped",
     "prompt tokens covered by shared prefix pages"),
    ("counter", "cow_copies", "boundary pages copy-on-write duplicated"),
    ("counter", "cache_evicted_pages",
     "cached-idle pages reclaimed under pressure"),
    # preemption/offload accounting: lanes frozen and resumed, pages
    # round-tripped through host RAM (vs pinned-shared pages that
    # never left), and the host store's bytes high-water
    ("counter", "preemptions", "lanes frozen off-device"),
    ("counter", "restores", "preempted lanes resumed"),
    ("counter", "offloaded_pages", "pages downloaded to the host store"),
    ("counter", "restored_pages", "pages scattered back on restore"),
    ("counter", "preempt_pinned_pages",
     "shared pages that stayed pinned through preemption"),
    ("gauge", "offload_bytes_peak",
     "host offload store bytes high-water"),
    # page-gate accounting: distinct blocked heads
    # (admission_rejections) vs blocked steps
    ("counter", "admission_rejected_steps",
     "admission attempts a blocked head held off"),
    # fault tolerance: injected faults that fired, lanes quarantined
    # (non-finite logits or a corrupted offload record), watchdog
    # recoveries (crashes + hangs, split out), lanes that came back
    # from offloaded KV with ZERO re-prefill, tokens re-prefilled by
    # relaunches, and requests shed/cancelled before or during decode
    ("counter", "faults_injected", "injected faults that fired"),
    ("counter", "lanes_quarantined", "lanes torn down as untrusted"),
    ("counter", "recoveries", "supervisor recoveries completed"),
    ("counter", "recovered_zero_reprefill",
     "crash-salvaged lanes restored with zero re-prefill"),
    ("counter", "re_prefilled_tokens",
     "tokens re-prefilled by relaunches"),
    ("counter", "shed_requests", "submits shed by the queue bound"),
    ("counter", "cancelled", "requests cancelled (any stage)"),
    ("counter", "deadline_cancelled", "cancelled by SLA deadline"),
    ("counter", "watchdog_hangs", "hung steps the watchdog condemned"),
    ("counter", "engine_crashes", "engine-thread crashes recovered"),
    # weight hot-swap (serving/hotswap.py): swaps flipped, canary-gate
    # rejections (no flip happened), automatic post-flip rollbacks,
    # canary decode cost, and the generation bookkeeping gauges
    ("counter", "weight_swaps", "weight hot-swaps flipped"),
    ("counter", "swap_canary_failures", "swaps rejected by the canary "
     "gate before flipping"),
    ("counter", "swap_rollbacks", "flipped swaps rolled back"),
    ("counter", "swap_canary_tokens", "tokens decoded by swap canaries"),
    ("counter", "swap_quarantines",
     "new-generation lanes quarantined inside a swap monitor window"),
    ("gauge", "weight_generation", "current weight generation id"),
    ("gauge", "weight_generations_held",
     "distinct param generations held on device"),
    # per-request latency samples (monotonic clock): TTFT and
    # inter-token gaps, folded into p50/p95 by finalize_stats
    ("histogram", "ttft_s", "submit -> first token seconds"),
    ("histogram", "itl_s", "inter-token gap seconds"),
]


class Engine:
    """Continuous-batching greedy generation over pruned/packed weights.

    >>> eng = Engine(cfg, params, max_batch=4, max_len=64, slab_k=8)
    >>> uid = eng.submit(prompt_ids, max_new_tokens=32)
    >>> results = eng.run()          # {uid: GenResult}

    ``slab_k`` is the number of decode steps per jitted slab (host syncs
    once per slab); ``slab_k=1`` is the per-token baseline.

    ``paged=True`` (default) stores K/V in the shared page pool:
    ``page_size`` slots per page, ``n_pages`` pool pages (default sized
    to the contiguous cache's ``max_batch × max_len`` so the two modes
    are memory-comparable; shrink it to serve with less, or grow
    ``max_len`` far past contiguous reach). ``paged=False`` keeps the
    dense ``(B, max_len)`` slab — the parity baseline.
    ``attn_backend`` picks the paged decode attention implementation:
    'xla' (gather, the oracle), 'pallas' (blocked-gather TPU kernel), or
    'pallas_interp' (kernel in interpret mode, CPU tests).

    ``prefix_cache=True`` (paged only) shares prompt-prefix KV pages
    across requests through a refcounted radix tree
    (serving/prefix_cache.py): matched pages skip prefill entirely, a
    shared boundary page is copy-on-write duplicated before the lane
    may write it, and finished sequences are re-inserted for future
    hits (LRU-evicted under pool pressure). Greedy tokens are
    bitwise-identical with sharing on or off.

    ``mixed=True`` (paged only) fuses chunked prefill INTO the decode
    step under a token budget (``prefill_token_budget``, default
    ``max_batch + prefill_chunk``: a full decode batch plus one full
    chunk per step): admission never stalls running lanes
    (``stats["stalled_decode_steps"] == 0``), prompts are admitted
    chunk-granularly, and requests are admitted per-lane at
    ``offset == 0`` (no group right-alignment — per-lane query runs
    make the padding pointless, and a lane keeps its full ``max_len``
    headroom). ``mixed=False`` keeps the phased admit-then-decode loop
    as the parity oracle.
    """

    def __init__(self, cfg, params, *, max_batch: int, max_len: int,
                 prefill_chunk: int = 16, slab_k: int = 8,
                 eos_id: int | None = None, dist=None,
                 scheduler: FIFOScheduler | None = None,
                 paged: bool = True, page_size: int = 16,
                 n_pages: int | None = None, attn_backend: str = "xla",
                 prefix_cache: bool = False, mixed: bool = False,
                 prefill_token_budget: int | None = None,
                 preempt: bool = False, offload_store=None,
                 offload_capacity_bytes: int | None = None,
                 admission_queue_limit: int | None = None,
                 enforce_deadlines: bool = False, faults=None,
                 tracer=None):
        if not registry.supports_prefill_chunk(cfg):
            raise NotImplementedError(
                f"family {cfg.family!r} is not KV-cache servable by the "
                "engine; use serve_loop.generate")
        if paged and not registry.supports_paged(cfg):
            raise NotImplementedError(
                f"family {cfg.family!r} has no paged KV cache; pass "
                "paged=False")
        if prefix_cache and not paged:
            raise ValueError("prefix_cache=True requires paged=True "
                             "(pages are the unit of sharing)")
        if mixed and not paged:
            raise ValueError("mixed=True requires paged=True (the mixed "
                             "step writes per-lane query runs through "
                             "block tables)")
        if mixed and not registry.supports_mixed(cfg):
            raise NotImplementedError(
                f"family {cfg.family!r} has no mixed decode+prefill "
                "step; pass mixed=False")
        if preempt and not paged:
            raise ValueError("preempt=True requires paged=True (pages "
                             "are the unit of offload)")
        assert slab_k >= 1
        # NOT ``tracer or ...``: same falsy-default bug class as the
        # scheduler below — a fresh Tracer with an empty ring is truthy
        # today, but the guard costs nothing and documents the intent
        self.tracer = NULL_TRACER if tracer is None else tracer
        # a device trace names each op's layer by the named scopes in
        # its executable's op metadata (obs.trace.SCOPES): key the
        # persistent compile cache by that metadata too, or a program
        # compiled under other scopes (or none) comes back under these
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        self.metrics = MetricsRegistry()
        for kind, name, help in _METRICS:
            getattr(self.metrics, kind)(name, help)
        # the backward-compatible dict view: every existing
        # ``self.stats[...]`` read/write lands on a typed metric
        self.stats = self.metrics.view()
        self.cfg = cfg
        self.dist = dist     # hotswap canaries rebuild decode with it
        self.params = params
        # generational weights (serving/hotswap.py): ``_gen`` is the
        # generation NEW admissions decode under, ``_gen_params`` every
        # param set still referenced by some in-flight lane (old
        # generations are freed by ``_gc_generations`` when their last
        # lane retires), ``_gen_pins`` uid -> generation for crash
        # relaunches that must resume on their admission-time weights,
        # and ``_swap_monitor`` the post-flip rollback watcher
        self._gen = 0
        self._gen_params: dict[int, object] = {0: params}
        self._gen_pins: dict[int, int] = {}
        self._swap_monitor = None
        self.max_batch = max_batch
        self.max_len = max_len
        self.chunk = max(1, min(prefill_chunk, max_len))
        self.slab_k = slab_k
        self.eos_id = eos_id
        self.paged = paged
        self.mixed = mixed
        # NOT ``scheduler or ...``: schedulers define __len__, and an
        # empty (freshly built) one is falsy — ``or`` would silently
        # swap a caller's SLAScheduler for a new FIFO
        self.scheduler = (scheduler if scheduler is not None
                          else FIFOScheduler(
                              max_batch, max_len,
                              prefill_token_budget=prefill_token_budget))
        self.scheduler.tracer = self.tracer
        if prefill_token_budget is not None:
            self.scheduler.prefill_token_budget = prefill_token_budget
        elif getattr(self.scheduler, "prefill_token_budget", None) is None:
            # one full decode batch + one full prefill chunk per step
            self.scheduler.prefill_token_budget = max_batch + self.chunk
        # lanes whose prompt is still being (chunk-)prefilled across
        # steps: lane -> next prompt position (admission order — the
        # token-budget planner hands chunks out FIFO). Mixed mode only;
        # the phased engine drains tails inside admission.
        self._prefilling: dict[int, int] = {}
        self.lanes: list[_Lane | None] = [None] * max_batch
        # host mirror of the on-device per-lane state; uploaded to the
        # device ONLY when admission/eviction edits it (self._dirty)
        self._mirror = {
            "pending": np.zeros(max_batch, np.int32),
            "frontier": np.zeros(max_batch, np.int32),
            "offsets": np.zeros(max_batch, np.int32),
            "remaining": np.zeros(max_batch, np.int32),
            "live": np.zeros(max_batch, bool),
            # fault containment (serving/step.py _run_slab): poison is
            # the injection port (added to the first in-slab step's
            # logits, normally all zero), faulted the device-side
            # per-lane finite-check verdict the host quarantines on
            "poison": np.zeros(max_batch, np.float32),
            "faulted": np.zeros(max_batch, bool),
        }
        # load shedding + SLA enforcement + failure routing
        self.admission_queue_limit = admission_queue_limit
        self.enforce_deadlines = enforce_deadlines
        self._finish_times: deque[float] = deque(maxlen=32)
        # uid -> (original prompt, tokens emitted before a crash
        # relaunch): a relaunched request decodes over prompt+emitted,
        # but its GenResult must report the ORIGINAL split
        self._recovered_prefix: dict[int, tuple[np.ndarray, list[int]]] = {}
        # failure results harvested outside step()'s return (cancel,
        # corrupted restore, recovery) — drained at the next step
        self._pending_results: list[GenResult] = []
        self._step_idx = 0
        # set by the watchdog/supervisor to abort a wedged device call
        # (the injected-stall hook polls it; a real deployment would
        # map this to killing the device stream)
        self._condemned = threading.Event()
        self._faults = None
        # optional callback ``{uid: last-position logits}`` after each
        # prefill: lets a check against a reference read the logits the
        # engine itself turned into each request's first token
        self.prefill_logits_hook = None
        self.pcache: PrefixCache | None = None
        # lanes frozen off-device by preemption, awaiting restore (any
        # paged engine can be preempted explicitly via ``preempt()``;
        # ``preempt=True`` additionally lets admission preempt
        # lower-priority lanes for a page-blocked urgent head)
        self.preempt_enabled = preempt
        self._preempted: list[_Preempted] = []
        if paged:
            self.page_size = page_size
            per_lane = -(-max_len // page_size)
            self.n_pages = (max_batch * per_lane if n_pages is None
                            else n_pages)
            self.max_pages = min(per_lane, self.n_pages)
            self.pool = PagePool(self.n_pages, page_size)
            self.cache = registry.init_paged_cache(cfg, self.n_pages,
                                                   page_size)
            if prefix_cache:
                self.pcache = PrefixCache(self.pool)
                self._copy_pages = jax.jit(make_copy_pages_step(),
                                           donate_argnums=(0,))
            self._mirror["bt"] = np.zeros((max_batch, self.max_pages),
                                          np.int32)
            # preemption plumbing: host store for offloaded page KV and
            # the jitted device<->host page movers (pow2-padded index
            # vectors keep the jit cache O(log max_pages))
            self._offload = (offload_store if offload_store is not None
                             else HostKVStore(offload_capacity_bytes))
            self._offload.tracer = self.tracer
            self._gather = jax.jit(make_gather_pages_step())
            self._scatter = jax.jit(make_scatter_pages_step(),
                                    donate_argnums=(0,))
            # page-unit feasibility moves INTO the scheduler's submit
            # gate so slot- and page-infeasible requests both reject
            # synchronously at submit with a consistent error
            self.scheduler.feasibility = self._check_feasible
            self._prefill = jax.jit(
                make_paged_prefill_chunk_step(cfg, dist=dist),
                static_argnames=("read_pages",), donate_argnums=(1,))
            # one fused decode+prefill call (mixed engine steps AND the
            # phased engine's batched cross-request tail prefill)
            self._mixed_fn = jax.jit(make_mixed_step(cfg, dist=dist),
                                     static_argnames=("read_pages",),
                                     donate_argnums=(1,))
            # query-width bucket cap: smallest power of two >= chunk
            self._wcap = 1 << max(0, (self.chunk - 1).bit_length())
            self._slab = jax.jit(
                make_paged_decode_slab_step(
                    cfg, slab_k, max_len, page_size, eos_id=eos_id,
                    dist=dist, attn_backend=attn_backend),
                static_argnames=("read_pages",), donate_argnums=(1,))
        else:
            self.cache = registry.init_cache(cfg, max_batch, max_len)
            self._prefill = jax.jit(make_prefill_chunk_step(cfg,
                                                            dist=dist),
                                    donate_argnums=(1,))
            self._slab = jax.jit(make_decode_slab_step(
                cfg, slab_k, max_len, eos_id=eos_id, dist=dist),
                donate_argnums=(1,))
        self._dstate = None
        self._dirty = True
        self._uid = 0
        self.reset_stats()
        if faults is not None:
            self.install_faults(faults)

    def install_faults(self, plan) -> None:
        """Wire a seeded ``FaultPlan`` (serving/faults.py) into every
        injection point: the step hooks, the page allocator, and the
        offload store. Chaos-test plumbing — a production engine runs
        with no plan installed and every hook is a no-op."""
        self._faults = plan
        plan._engine = self
        if self.paged:
            self.pool.fault_hook = plan.on_alloc
            self._offload.fault_hook = plan.on_offload_save

    def reset_stats(self):
        """Zero every registered metric — DERIVED from the registry
        (obs/metrics.py), so a metric added to ``_METRICS`` (or
        auto-registered through the view) can never be missed here;
        the old hand-listed dict rebuild is gone."""
        self.metrics.reset()
        if hasattr(self.scheduler, "reset_stats"):
            self.scheduler.reset_stats()
        if getattr(self, "pool", None) is not None:
            self.pool.reset_peaks()
        if getattr(self, "_offload", None) is not None:
            self._offload.reset_peaks()

    # raw latency sample lists, now registry histograms (reset() clears
    # them in place); exposed under the old names so existing callers
    # and tests keep appending/reading plain lists
    @property
    def _ttft(self) -> list[float]:
        return self.metrics.histogram("ttft_s").samples

    @property
    def _itl(self) -> list[float]:
        return self.metrics.histogram("itl_s").samples

    # ------------------------------------------------------------- memory
    @property
    def page_bytes(self) -> int:
        """Bytes of ONE pool page across all layers, K+V."""
        k = self.cache["k"]
        layers, kv, hd = k.shape[0], k.shape[-2], k.shape[-1]
        return 2 * layers * self.page_size * kv * hd * k.dtype.itemsize

    @property
    def kv_bytes_peak(self) -> int:
        """Peak bytes of live KV data: pages actually pinned (paged) or
        the whole dense slab (contiguous)."""
        if self.paged:
            return self.pool.peak_in_use * self.page_bytes
        return self.cache["k"].nbytes + self.cache["v"].nbytes

    @property
    def kv_bytes_contiguous_equiv(self) -> int:
        """What a dense (B, max_len) cache of this config would hold."""
        k = self.cache["k"]
        layers, kv, hd = k.shape[0], k.shape[-2], k.shape[-1]
        return (2 * layers * self.max_batch * self.max_len * kv * hd
                * k.dtype.itemsize)

    # ------------------------------------------------------------ submit
    def submit(self, prompt, max_new_tokens: int = 32,
               uid: int | None = None, *, priority: int = 0,
               deadline_s: float | None = None) -> int:
        """Queue one request. ``priority`` is the SLA class (smaller =
        more urgent; only ordering-relevant when the engine runs an
        ``SLAScheduler``) and ``deadline_s`` an optional target latency
        — see serving/scheduler.py. Infeasible requests (no decode
        headroom under ``max_len``, or a paged extent the pool could
        never hold) raise ``ValueError`` HERE, synchronously: the
        scheduler's submit gate runs both checks (``_check_feasible``
        is installed as its feasibility hook), so a request never
        queues only to surface an error later.

        With ``admission_queue_limit`` set, a submit that would push the
        queue past the bound is SHED instead of queued unboundedly:
        ``BackpressureError`` carries a retry-after hint derived from
        the recent request-completion rate — already-admitted work keeps
        its latency; new arrivals are told when capacity is likely."""
        if (self.admission_queue_limit is not None
                and len(self.scheduler) >= self.admission_queue_limit):
            self.stats["shed_requests"] += 1
            self.tracer.event("request.shed",
                              queue_depth=len(self.scheduler))
            raise BackpressureError(len(self.scheduler),
                                    self.admission_queue_limit,
                                    self._retry_after_hint())
        uid = self._uid if uid is None else uid
        self._uid = max(self._uid, uid) + 1
        req = Request(uid, np.asarray(prompt), max_new_tokens,
                      priority=priority, deadline_s=deadline_s)
        self.scheduler.submit(req)
        if self.tracer.enabled:
            self.tracer.event("request.queued", t=req.queued_at,
                              uid=uid, prompt_len=req.prompt_len,
                              max_new_tokens=max_new_tokens,
                              priority=priority)
        self.stats["queue_depth_peak"] = max(
            self.stats["queue_depth_peak"], len(self.scheduler))
        return uid

    def _retry_after_hint(self) -> float:
        """Seconds until one queue slot plausibly frees: the inverse of
        the recent completion rate (last ``_finish_times`` window),
        clamped to [0.05, 60]. A cold engine (nothing finished yet)
        hints 1s — a guess, and documented as such in the error."""
        ft = self._finish_times
        if len(ft) >= 2 and ft[-1] > ft[0]:
            est = (ft[-1] - ft[0]) / (len(ft) - 1)
        else:
            est = 1.0
        return float(min(60.0, max(0.05, est)))

    def _check_feasible(self, req: Request) -> None:
        """Page-unit submit gate (paged engines), installed on the
        scheduler as its ``feasibility`` hook: runs after the slot gate
        (so ``prompt_len < max_len`` already holds) and rejects a
        request whose solo extent could never fit the pool."""
        need = self._page_cost([req])
        if need > self.n_pages:
            raise ValueError(
                f"oversized request: prompt of {req.prompt_len} "
                f"tokens + budget of {req.max_new_tokens} new tokens "
                f"needs {need} pages ({self.page_size} slots each) "
                f"even admitted alone, but the pool holds only "
                f"{self.n_pages} pages "
                f"({self.n_pages * self.page_size} cache slots) — "
                "shrink the request or grow n_pages")

    # ------------------------------------------------------- lane helpers
    @property
    def active_lanes(self) -> list[int]:
        return [i for i, l in enumerate(self.lanes) if l is not None]

    @property
    def frontiers(self) -> np.ndarray:
        """(max_batch,) per-lane cache-slot write positions."""
        return self._mirror["frontier"].copy()

    @property
    def block_tables(self) -> np.ndarray:
        """(max_batch, max_pages) logical page -> pool page (paged)."""
        return self._mirror["bt"].copy()

    def _sync_dstate(self):
        """Upload the host mirror as the device-side slab state — called
        lazily, only after admission/eviction edits."""
        if self._dirty:
            with self.tracer.phase("engine.upload"):
                self._dstate = {k: jnp.asarray(v)
                                for k, v in self._mirror.items()}
            self._dirty = False

    def _page_cost(self, group: list[Request]) -> int:
        """Pages a tentative admission group pins: the group prefills
        right-aligned to the LONGEST member, so every lane's extent is
        ``min(group_width + budget - 1, max_len)`` slots (prefill writes
        the pad slots too; decode writes at most budget-1 more past the
        width)."""
        w = max(r.prompt_len for r in group)
        # max(.., w): prefill writes the full width even if the budget
        # were ever allowed below 1 — never pin fewer slots than it
        return sum(self.pool.slots_for(
            min(max(w + r.max_new_tokens - 1, w), self.max_len))
            for r in group)

    def _extent_pages(self, r: Request) -> int:
        """Pages covering one prefix-cached lane's whole extent (its own
        prompt is the group width: admission is per-request so every
        lane sits at offset 0 — see ``_admit_one``)."""
        return self._page_cost([r])

    def _effective_match(self, r: Request):
        """Radix match for admission, with the boundary-page CoW DROPPED
        when the request's extent fills the whole pool: the CoW needs
        the shared original and the private copy alive at once (extent
        + 1 pages), which such a request could never pin — keeping the
        tail match would make it permanently inadmissible (livelock)
        even though it fits cold. Full-page sharing never costs more
        than a cold admission, so it is always kept.
        Returns (match, extent_pages)."""
        m = self.pcache.match(r.prompt)
        extent = self._extent_pages(r)
        if m.tail_page is not None and extent >= self.n_pages:
            m = Match(m.pages, len(m.pages) * self.page_size)
        return m, extent

    def _page_cost_shared(self):
        """EFFECTIVE page-cost gate for prefix-shared admission, to be
        compared against ``free + reclaimable``: pages already in the
        radix tree cost nothing NEW, but matched pages that are
        currently cached-idle must be counted once — the admission will
        pin them, so they stop being reclaimable. Returns a
        ``group -> cost`` callable that memoizes the per-request radix
        match: the scheduler probes growing trial prefixes of the same
        queue, so each request is matched ONCE per admission attempt,
        not once per trial."""
        memo: dict[int, tuple[int, list[int]]] = {}

        def per_request(r: Request) -> tuple[int, list[int]]:
            if id(r) not in memo:
                m, extent = self._effective_match(r)
                pinned = m.pages + ([m.tail_page]
                                    if m.tail_page is not None else [])
                memo[id(r)] = (
                    extent - len(m.pages),
                    [p for p in pinned if self.pool.refcount(p) == 0])
            return memo[id(r)]

        def cost(group: list[Request]) -> int:
            new_pages = 0
            idle_matched: set[int] = set()
            for r in group:
                new, idle = per_request(r)
                new_pages += new
                idle_matched.update(idle)
            return new_pages + len(idle_matched)
        return cost

    def _finish(self, i: int, truncated: bool = False) -> GenResult:
        lane = self.lanes[i]
        self.lanes[i] = None
        self._mirror["live"][i] = False
        self._gen_pins.pop(lane.req.uid, None)
        if self.paged and lane.pages:
            # donation additionally requires CURRENT-generation KV: a
            # hot-swap flushed the radix tree at flip, and an old-gen
            # straggler finishing afterwards must not reseed it with
            # KV computed under retired weights
            if (self.pcache is not None and lane.offset == 0
                    and lane.gen == self._gen):
                # insert-on-finish: donate the pages covering every slot
                # this lane actually wrote — prompt AND emitted
                # continuation (slot s holds token seq[s]; offset 0 means
                # slot == canonical position, the sharing precondition).
                # Donated pages park as cached-idle on release below;
                # coverage the tree already has just frees.
                frontier = int(self._mirror["frontier"][i])
                seq = np.concatenate(
                    [lane.req.prompt,
                     np.asarray(lane.generated, np.int32)])[:frontier]
                self.pcache.insert(seq,
                                   lane.pages[:self.pool.slots_for(frontier)])
            self.pool.release(lane.pages)
            self._mirror["bt"][i] = 0
        self._dirty = True
        self.stats["evicted"] += 1
        self.stats["truncated"] += int(truncated)
        tt = lane.token_times
        ttft = max(0.0, tt[0] - lane.req.queued_at) if tt else 0.0
        self._ttft.append(ttft)
        self._itl.extend(b - a for a, b in zip(tt, tt[1:]))
        self._finish_times.append(time.monotonic())
        # a crash-relaunched request decoded over prompt+emitted; its
        # result must report the ORIGINAL prompt/generated split (TTFT
        # is recovery-local — the pre-crash timeline died with the
        # thread)
        prompt, gen = lane.req.prompt, lane.generated
        pre = self._recovered_prefix.pop(lane.req.uid, None)
        if pre is not None:
            prompt, gen = pre[0], list(pre[1]) + gen
        if self.tracer.enabled:
            self.tracer.event("request.finish", uid=lane.req.uid,
                              lane=i, tokens=len(gen), ttft_s=ttft,
                              truncated=truncated)
        return GenResult(lane.req.uid, prompt,
                         np.asarray(gen, np.int32), truncated,
                         ttft_s=ttft)

    # --------------------------------------------- quarantine / cancel
    def _failed_result(self, req: Request, generated: list[int],
                       exc: Exception) -> GenResult:
        """Build the structured-failure GenResult for ``req``, merging
        any crash-relaunch prefix so the prompt/generated split is the
        original one. No TTFT/ITL samples — failed requests must not
        skew the latency percentiles."""
        prompt, gen = req.prompt, list(generated)
        pre = self._recovered_prefix.pop(req.uid, None)
        if pre is not None:
            prompt, gen = pre[0], list(pre[1]) + gen
        return GenResult(req.uid, prompt, np.asarray(gen, np.int32),
                         error=exc)

    def _fail_lane(self, i: int, exc: Exception) -> GenResult:
        """Tear down lane ``i`` with a structured error: free its pages
        (NEVER donating to the prefix cache — a quarantined lane's KV
        is not trusted; shared pages it pinned just unpin), clear its
        device state, and route the failure out as a GenResult. The
        other lanes' device state is untouched — their token streams
        stay bitwise-identical to a fault-free run."""
        lane = self.lanes[i]
        self.lanes[i] = None
        self._gen_pins.pop(lane.req.uid, None)
        if (self._swap_monitor is not None
                and isinstance(exc, (LaneFaultError,
                                     OffloadCorruptionError))):
            # post-flip rollback evidence: quarantines of lanes on the
            # freshly flipped generation (serving/hotswap.py)
            self._swap_monitor.note_quarantine(lane.gen, self)
        m = self._mirror
        m["live"][i] = False
        m["faulted"][i] = False
        m["poison"][i] = 0.0
        if self.paged and lane.pages:
            self.pool.release(lane.pages)
            m["bt"][i] = 0
        self._prefilling.pop(i, None)
        self._dirty = True
        self.stats["evicted"] += 1
        self._finish_times.append(time.monotonic())
        if self.tracer.enabled:
            name = ("request.quarantined"
                    if isinstance(exc, (LaneFaultError,
                                        OffloadCorruptionError))
                    else "request.failed")
            self.tracer.event(name, uid=lane.req.uid, lane=i,
                              error=type(exc).__name__,
                              tokens=len(lane.generated))
        return self._failed_result(lane.req, lane.generated, exc)

    def _harvest_faults(self, finished: list[GenResult]) -> None:
        """Quarantine every lane the device-side finite check flagged
        this step (slab carry or mixed-step verdict, already folded
        into the mirror): each fails ONLY its own request with
        ``LaneFaultError``."""
        m = self._mirror
        if not m["faulted"].any():
            return
        for i in self.active_lanes:
            if m["faulted"][i]:
                uid = self.lanes[i].req.uid
                self.stats["lanes_quarantined"] += 1
                finished.append(self._fail_lane(i, LaneFaultError(uid, i)))
        m["faulted"][:] = False
        self._dirty = True

    def _cancel_expired(self, finished: list[GenResult]) -> None:
        """SLA-deadline enforcement (``enforce_deadlines=True``): a
        lane whose absolute deadline passed is cancelled at this host
        sync — its pages free, the remaining lanes' device state (and
        token streams) are bitwise-unchanged."""
        now = time.monotonic()
        for i in self.active_lanes:
            req = self.lanes[i].req
            if req.deadline_at is not None and now > req.deadline_at:
                self.stats["deadline_cancelled"] += 1
                self.stats["cancelled"] += 1
                finished.append(
                    self._fail_lane(i, DeadlineExceededError(req.uid)))

    def cancel(self, uid: int) -> bool:
        """Cancel a request wherever it currently lives — queued,
        decoding on a lane, or frozen preempted — releasing every
        resource it held (lane, pages, offload record; prefix-cache
        state stays consistent: cancelled work is never donated). The
        failure surfaces as a ``RequestCancelledError`` GenResult at
        the next step. Idempotent: False when the uid is not in flight
        (already finished, or never submitted)."""
        req = None
        if hasattr(self.scheduler, "remove"):
            req = self.scheduler.remove(uid)
        if req is not None:
            self.stats["cancelled"] += 1
            self._gen_pins.pop(uid, None)
            self._pending_results.append(
                self._failed_result(req, [], RequestCancelledError(uid)))
            return True
        for j, pre in enumerate(self._preempted):
            if pre.req.uid == uid:
                self._preempted.pop(j)
                self._gen_pins.pop(uid, None)
                self._offload.drop(uid)
                if pre.pinned:
                    self.pool.release(list(pre.pinned.values()))
                self.stats["cancelled"] += 1
                self._pending_results.append(self._failed_result(
                    pre.req, pre.generated, RequestCancelledError(uid)))
                return True
        for i in self.active_lanes:
            if self.lanes[i].req.uid == uid:
                self.stats["cancelled"] += 1
                self._pending_results.append(
                    self._fail_lane(i, RequestCancelledError(uid)))
                return True
        return False

    # ---------------------------------------------------------- preemption
    def _download_pages(self, pages: list[int]):
        """Device -> host pull of ``pages`` (physical indices), padded
        to a power-of-two gather width so the jit cache stays
        O(log max_pages); the pad rows are sliced off on the host."""
        n = len(pages)
        w = 1 << max(0, (n - 1).bit_length())
        idx = np.asarray(pages + [pages[0]] * (w - n), np.int32)
        k, v = self._gather(self.cache, jnp.asarray(idx))
        k = np.asarray(jax.block_until_ready(k))[:, :n].copy()
        v = np.asarray(v)[:, :n].copy()
        return k, v

    def _upload_pages(self, dst: list[int], k: np.ndarray,
                      v: np.ndarray) -> None:
        """Host -> device scatter of offloaded page KV into freshly
        allocated pages ``dst``. Power-of-two padding repeats the first
        page WITH its own data — duplicate scatter indices then write
        identical values, a no-op."""
        n = len(dst)
        w = 1 << max(0, (n - 1).bit_length())
        if w > n:
            dst = dst + [dst[0]] * (w - n)
            k = np.concatenate([k] + [k[:, :1]] * (w - n), axis=1)
            v = np.concatenate([v] + [v[:, :1]] * (w - n), axis=1)
        self.cache = self._scatter(self.cache, jnp.asarray(dst, np.int32),
                                   jnp.asarray(k), jnp.asarray(v))

    def preempt(self, i: int) -> None:
        """Freeze lane ``i`` off-device: download its exclusively owned
        LIVE pages (slots ``[0, frontier)``) to the host offload store,
        keep prefix-shared/cached pages pinned on-device (their
        refcount keeps the KV alive for the other readers — they are
        NEVER offloaded while shared), and release everything releasable
        (downloaded pages + the garbage extent past the frontier) to
        the pool. The lane's decode state (pending token, frontier,
        remaining budget) is saved so restore resumes with zero
        re-prefilled tokens — bitwise-identical greedy continuation
        (tests/test_preemption.py).

        Only live decode lanes preempt: a lane mid-prefill holds no
        resumable decode state worth offloading (evicting it would mean
        re-prefill, exactly what preemption exists to avoid)."""
        assert self.paged, "preemption requires the paged engine"
        lane = self.lanes[i]
        assert lane is not None and i not in self._prefilling, \
            f"lane {i} is not preemptible"
        m = self._mirror
        assert bool(m["live"][i]), "only live decode lanes preempt"
        n_live = self.pool.slots_for(int(m["frontier"][i]))
        dl_logical: list[int] = []
        dl_pages: list[int] = []
        pinned: dict[int, int] = {}
        for j in range(n_live):
            p = lane.pages[j]
            if self.pool.exclusive(p):
                dl_logical.append(j)
                dl_pages.append(p)
            else:
                pinned[j] = p            # reference HELD through preempt
        if dl_pages:
            k, v = self._download_pages(dl_pages)
            self._offload.save(lane.req.uid, dl_logical, k, v)
            self.stats["offloaded_pages"] += len(dl_pages)
            self.stats["offload_bytes_peak"] = max(
                self.stats["offload_bytes_peak"], self._offload.bytes_peak)
        self.stats["preempt_pinned_pages"] += len(pinned)
        # garbage extent pages (past the frontier) free without download
        # — they are never shared: sharing covers at most the prompt,
        # and a live lane's frontier is at least its prompt width
        self.pool.release(dl_pages + lane.pages[n_live:])
        self._preempted.append(_Preempted(
            req=lane.req, offset=lane.offset, generated=lane.generated,
            token_times=lane.token_times, pending=int(m["pending"][i]),
            frontier=int(m["frontier"][i]),
            remaining=int(m["remaining"][i]), n_pages=len(lane.pages),
            pinned=pinned, gen=lane.gen))
        self.lanes[i] = None
        m["live"][i] = False
        m["bt"][i] = 0
        self._dirty = True
        self.stats["preemptions"] += 1
        if self.tracer.enabled:
            self.tracer.event("request.preempt", uid=self._preempted[-1].req.uid,
                              lane=i, offloaded_pages=len(dl_pages),
                              pinned_pages=len(pinned))

    def _restore_one(self, pre: _Preempted) -> bool:
        """Re-admit one preempted lane: alloc fresh pages for every
        logical slot that was offloaded (or garbage), interleave the
        still-pinned shared pages at their logical positions, scatter
        the host KV back, and rebuild the lane at the saved frontier.
        False when no lane is free or the pool can't cover it yet."""
        free = [i for i, l in enumerate(self.lanes) if l is None]
        if not free:
            return False
        own_need = pre.n_pages - len(pre.pinned)
        if self.pcache is not None:
            short = own_need - self.pool.free_pages
            if short > 0:
                self.stats["cache_evicted_pages"] += \
                    self.pcache.evict(short)
        if own_need > self.pool.free_pages:
            return False
        i = free[0]
        own = iter(self.pool.alloc(own_need))
        pages = [pre.pinned[j] if j in pre.pinned else next(own)
                 for j in range(pre.n_pages)]
        try:
            rec = self._offload.pop(pre.req.uid)
        except OffloadCorruptionError as e:
            # the parked KV rotted in host RAM: this request fails
            # structurally (its checksummed record is gone), everyone
            # else is untouched — release everything the lane held
            # (own pages at rc 1 free; pinned-shared ones just unpin)
            self.pool.release(pages)
            self._mirror["bt"][i] = 0
            self.stats["lanes_quarantined"] += 1
            self.tracer.event("request.quarantined", uid=pre.req.uid,
                              error=type(e).__name__,
                              tokens=len(pre.generated))
            self._pending_results.append(self._failed_result(
                pre.req, pre.generated,
                LaneFaultError(pre.req.uid, -1, reason=str(e))))
            return True          # entry resolved: _try_restore pops it
        if rec is not None:   # None: every live page was pinned-shared
            dst = [pages[j] for j in rec.logical]
            self._upload_pages(dst, rec.k, rec.v)
            self.stats["restored_pages"] += len(dst)
            if pre.recovered:
                self.stats["recovered_zero_reprefill"] += 1
        self.lanes[i] = _Lane(pre.req, pre.offset, pre.generated,
                              pages=pages, token_times=pre.token_times,
                              gen=pre.gen)
        m = self._mirror
        m["bt"][i] = 0
        m["bt"][i, :len(pages)] = pages
        m["offsets"][i] = pre.offset
        m["frontier"][i] = pre.frontier
        m["remaining"][i] = pre.remaining
        m["pending"][i] = pre.pending
        m["live"][i] = True
        self._dirty = True
        self.stats["restores"] += 1
        if self.tracer.enabled:
            self.tracer.event(
                "request.restore", uid=pre.req.uid, lane=i,
                frontier=pre.frontier, recovered=pre.recovered,
                restored_pages=(len(rec.logical) if rec is not None
                                else 0))
        return True

    def _try_restore(self) -> None:
        """Readmit preempted lanes, most urgent first, unless the queue
        head outranks them (then lanes/pages stay reserved for it —
        restoring a batch lane just to preempt it again would thrash).
        Head-of-line within the preempted set: a lane that does not fit
        yet blocks the less urgent ones behind it."""
        if not self._preempted:
            return
        self._preempted.sort(key=lambda p: (p.req.priority, p.req._seq))
        while self._preempted:
            head = self.scheduler.head()
            if (head is not None
                    and head.priority < self._preempted[0].req.priority):
                return
            if not self._restore_one(self._preempted[0]):
                return
            self._preempted.pop(0)

    def _releasable(self, i: int) -> int:
        """Pages preempting lane ``i`` would actually return to the
        pool (its exclusively owned ones; pinned-shared pages stay)."""
        return sum(1 for p in self.lanes[i].pages
                   if self.pool.exclusive(p))

    def _shortfall(self, head: Request) -> int:
        """Pages the queue head still needs beyond what the pool can
        provide right now (mode-aware: prefix-shared admission counts
        effective cost against free + reclaimable-cached)."""
        if self.pcache is not None:
            return (self._page_cost_shared()([head])
                    - self.pool.free_pages - self.pcache.reclaimable())
        return self._page_cost([head]) - self.pool.free_pages

    def _preempt_for_head(self) -> bool:
        """Make room for a more urgent page- or lane-blocked queue head
        by preempting strictly-lower-priority live lanes, least urgent
        (then latest-arrived) first. Stops as soon as the head fits, no
        candidate remains, or the next preemption would gain nothing
        (short of pages but the victim has none to release). Returns
        True when at least one lane was preempted (the caller re-runs
        admission)."""
        head = self.scheduler.head()
        if head is None:
            return False
        did = False
        while True:
            free_lane = any(l is None for l in self.lanes)
            short = self._shortfall(head)
            if free_lane and short <= 0:
                return did
            cands = [i for i in self.active_lanes
                     if bool(self._mirror["live"][i])
                     and i not in self._prefilling
                     and self.lanes[i].req.priority > head.priority]
            if not cands:
                return did
            victim = max(cands, key=lambda i: (self.lanes[i].req.priority,
                                               self.lanes[i].req._seq))
            if free_lane and short > 0 and self._releasable(victim) == 0:
                return did
            try:
                self.preempt(victim)
            except OffloadCapacityError:
                # host store full: the victim keeps running (preempt
                # raises before mutating anything) and the head waits
                # for capacity the normal way
                return did
            did = True

    # ----------------------------------------------------------- admission
    def _note_admitted(self, reqs: list[Request]) -> None:
        now = time.monotonic()
        tr = self.tracer
        for r in reqs:
            q = max(0.0, now - r.queued_at)
            self.stats["queued_s_total"] += q
            self.stats["queued_s_max"] = max(self.stats["queued_s_max"], q)
            if tr.enabled:
                tr.event("request.admitted", t=now, uid=r.uid,
                         queued_s=q, priority=r.priority)
        self.stats["admitted"] += len(reqs)

    def _admit(self) -> None:
        """Admission, with preemption as the fallback: when the plain
        pass leaves a queue head behind and ``preempt=True``, try to
        free lanes/pages by preempting strictly-lower-priority lanes,
        then admit again."""
        self._admit_once()
        if (self.paged and self.preempt_enabled and len(self.scheduler)
                and self._preempt_for_head()):
            self._admit_once()

    def _admit_once(self) -> None:
        free = [i for i, l in enumerate(self.lanes) if l is None]
        if self.pcache is not None:
            self._admit_shared(free)
            return
        if self.mixed:
            self._admit_mixed(free)
            return
        if self.paged:
            reqs = self.scheduler.admit(len(free), self.pool.free_pages,
                                        self._page_cost)
        else:
            reqs = self.scheduler.admit(len(free))
        if not reqs:
            return
        # partition by target weight generation: everything lands on the
        # current weights except crash relaunches pinned to their
        # admission-time generation (the common single-generation case
        # is one group — the exact pre-swap code path)
        groups: dict[int, list[Request]] = {}
        for r in reqs:
            groups.setdefault(self._gen_pins.get(r.uid, self._gen),
                              []).append(r)
        m = self._mirror
        built: list[tuple[int, int, list[int]]] = []   # (gen, W, lanes)
        try:
            for gen in sorted(groups):
                sub = groups[gen]
                # the admitted group prefills right-aligned in slots
                # [0, W): a lane freed mid-traffic restarts at slot 0
                width = max(r.prompt_len for r in sub)
                new_lanes = []
                for r in sub:
                    i = free.pop(0)
                    off = width - r.prompt_len
                    self.lanes[i] = _Lane(r, off, [], gen=gen)
                    if self.paged:
                        need = self.pool.slots_for(
                            min(max(width + r.max_new_tokens - 1, width),
                                self.max_len))
                        self.lanes[i].pages = self.pool.alloc(need)
                        m["bt"][i] = 0
                        m["bt"][i, :need] = self.lanes[i].pages
                    m["offsets"][i] = off
                    m["frontier"][i] = width
                    m["remaining"][i] = r.max_new_tokens - 1
                    m["pending"][i] = 0
                    m["live"][i] = True
                    new_lanes.append(i)
                built.append((gen, width, new_lanes))
        except BaseException:
            # crash-safe admission: a page-alloc failure mid-group must
            # not LOSE requests — whatever never reached a lane goes
            # back to the queue head (the one stranded on a half-built
            # lane relaunches through supervisor recovery); the crash
            # still propagates to the watchdog
            placed = {self.lanes[j].req.uid for j in range(
                self.max_batch) if self.lanes[j] is not None}
            self.scheduler.push_front(
                [r for r in reqs if r.uid not in placed])
            raise
        self._dirty = True     # one upload, in step() before the slab
        self._note_admitted(reqs)

        for gen, width, new_lanes in built:
            # chunked batched prefill over [0, width), right-aligned,
            # through this group's OWN generation of the weights
            tokens = np.zeros((self.max_batch, width), np.int32)
            for i in new_lanes:
                p = self.lanes[i].req.prompt
                tokens[i, width - p.size:] = p
            self._run_prefill(new_lanes, tokens, 0, width,
                              params=self._gen_params[gen])
        self.stats["prefill_tokens"] += sum(r.prompt_len for r in reqs)
        self.stats["prompt_tokens"] += sum(r.prompt_len for r in reqs)

    def _run_prefill(self, lane_ids: list[int], tokens: np.ndarray,
                     start: int, cover_slots: int, params=None) -> None:
        """The chunked-prefill loop shared by group admission (whole
        width from slot 0) and prefix-cached per-lane admission (tail
        only, from slot ``start``): runs ``tokens[:, start:]`` through
        ``prefill_chunk`` in whole chunks (the first may be short, the
        rest ``self.chunk`` wide, so the jit cache sees at most C
        distinct shapes), lanes outside ``lane_ids`` shielded by the
        lane mask, then folds each lane's FIRST generated token into
        the mirror. ``cover_slots`` bounds the paged attention read.
        Callers account prefill_tokens/prompt_tokens themselves (pad
        and shared-prefix slots don't count as prefilled tokens).
        ``params`` selects the weight generation (defaults to the
        current one)."""
        params = self.params if params is None else params
        width = tokens.shape[1]
        lane_mask = np.zeros((self.max_batch,), bool)
        lane_mask[lane_ids] = True
        offsets = jnp.asarray(self._mirror["offsets"])
        mask_j = jnp.asarray(lane_mask)
        toks_j = jnp.asarray(tokens)
        if self.paged:
            bt_j = jnp.asarray(self._mirror["bt"])
            r_pf = _pow2_bucket(self.pool.slots_for(cover_slots),
                                self.max_pages)
        last = None
        pos = start
        span = width - start
        rem = span % self.chunk
        sizes = ([rem] if rem else []) + [self.chunk] * (span // self.chunk)
        # phased-stall accounting: every one of these blocking calls
        # runs while the OTHER live lanes' decode waits
        stalled = any(bool(self._mirror["live"][j])
                      for j in self.active_lanes if not lane_mask[j])
        if stalled:
            self.stats["stalled_decode_steps"] += len(sizes)
        t0 = time.monotonic()
        for c in sizes:
            if self.paged:
                last, self.cache = self._prefill(
                    params, self.cache, toks_j[:, pos:pos + c],
                    jnp.int32(pos), offsets, mask_j, bt_j,
                    read_pages=r_pf)
                self.stats["pages_read"] += r_pf * len(lane_ids) * c
                self.stats["pages_read_dense_equiv"] += (
                    self.pool.slots_for(self.max_len)
                    * len(lane_ids) * c)
            else:
                last, self.cache = self._prefill(
                    params, self.cache, toks_j[:, pos:pos + c],
                    jnp.int32(pos), offsets, mask_j)
            pos += c
            self.stats["prefill_chunks"] += 1
        first = np.asarray(jax.block_until_ready(jnp.argmax(last, -1)))
        now = time.monotonic()
        if self.prefill_logits_hook is not None:
            self.prefill_logits_hook(
                {self.lanes[i].req.uid: last[i] for i in lane_ids})
        self.stats["prefill_s"] += now - t0
        if self.tracer.enabled:
            # span from the timestamps this loop already took at its
            # sync points — tracing adds no sync of its own
            self.tracer.span_at(
                "prefill.chunks", t0, now, lanes=len(lane_ids),
                chunks=len(sizes), tokens=span,
                uids=[self.lanes[i].req.uid for i in lane_ids])
        for i in lane_ids:
            self._mirror["pending"][i] = int(first[i])
            self.lanes[i].generated.append(int(first[i]))
            self.lanes[i].token_times.append(now)
            self.stats["generated_tokens"] += 1

    # ------------------------------------------------- mixed admission
    def _admit_mixed(self, free: list[int]) -> None:
        """Chunk-granular admission (``mixed=True``, no prefix cache):
        each admitted request takes a lane at ``offset == 0`` (per-lane
        query runs need no group right-alignment, and the lane keeps
        its full ``max_len`` headroom), pins pages for its own extent,
        and registers as a PREFILLING lane — its prompt is fed to the
        fused mixed step chunk-by-chunk under the token budget instead
        of a blocking prefill loop here."""
        reqs = self.scheduler.admit(
            len(free), self.pool.free_pages,
            lambda group: sum(self._page_cost([r]) for r in group))
        m = self._mirror
        for j, r in enumerate(reqs):
            i = free.pop(0)
            need = self._page_cost([r])
            try:
                pages = self.pool.alloc(need)
            except BaseException:
                # crash-safe admission: un-placed requests go back to
                # the queue head; the crash propagates to the watchdog
                self.scheduler.push_front(reqs[j:])
                raise
            self.lanes[i] = _Lane(r, 0, [], pages=pages,
                                  gen=self._gen_pins.get(r.uid,
                                                         self._gen))
            m["bt"][i] = 0
            m["bt"][i, :need] = self.lanes[i].pages
            m["offsets"][i] = 0
            m["frontier"][i] = r.prompt_len
            m["remaining"][i] = r.max_new_tokens - 1
            m["pending"][i] = 0
            m["live"][i] = False          # decodable once the tail lands
            self._prefilling[i] = 0
            self.stats["prompt_tokens"] += r.prompt_len
        if reqs:
            self._dirty = True
            self._note_admitted(reqs)

    # ------------------------------------------- prefix-cached admission
    def _admit_shared(self, free: list[int]) -> None:
        """Admission with the radix-tree prefix cache: the scheduler
        gate sees the EFFECTIVE page cost (shared pages are free,
        capacity is free + reclaimable-cached), and each admitted
        request takes its own lane at ``offset == 0`` — sharing is
        positional, so every lane's cache slot must equal its logical
        position. A request whose re-checked match no longer covers
        what the gate assumed (a concurrent eviction inside this batch)
        is returned to the queue HEAD.

        The uncovered TAILS of every request admitted in this round are
        prefilled together: one batched cross-request loop through the
        mixed-step call (phased) or chunk-granular fusion into the
        decode steps (mixed) — never a per-lane chunk loop each."""
        avail = self.pool.free_pages + self.pcache.reclaimable()
        reqs = self.scheduler.admit(len(free), avail,
                                    self._page_cost_shared())
        tails: list[int] = []
        for j, r in enumerate(reqs):
            try:
                ok = self._admit_one(free[0], r)
            except BaseException:
                # crash-safe admission: see _admit_once
                self.scheduler.push_front(reqs[j:])
                raise
            if not ok:
                self.scheduler.push_front(reqs[j:])
                break
            tails.append(free.pop(0))
            self._note_admitted([r])
        if not self.mixed and tails:
            self._prefill_tails(tails)

    def _admit_one(self, i: int, r: Request) -> bool:
        """match -> pin shared pages -> evict-for-room -> alloc own
        pages -> CoW the boundary page -> register the tail prefill
        (``self._prefilling``; the caller batches it). Returns False
        when the pool can't cover the request — no lane/page state is
        held, but the eviction pass may already have dropped cold
        cached-idle entries (that reclaim is never undone)."""
        gen = self._gen_pins.get(r.uid, self._gen)
        if gen != self._gen:
            # a crash relaunch pinned to RETIRED weights must not match
            # the radix tree: cached KV always belongs to the current
            # generation (the hot-swap flushed everything older)
            m, extent = Match([], 0), self._extent_pages(r)
        else:
            m, extent = self._effective_match(r)
        # pin everything matched BEFORE eviction/allocation can touch
        # it: the tail page only until its copy lands, the full pages
        # for the lane's lifetime (they go into its block table)
        pin_tail = [m.tail_page] if m.tail_page is not None else []
        self.pool.retain(m.pages + pin_tail)
        own_need = extent - len(m.pages)
        short = own_need - self.pool.free_pages
        if short > 0:
            self.stats["cache_evicted_pages"] += self.pcache.evict(short)
        if own_need > self.pool.free_pages:
            self.pool.release(m.pages + pin_tail)   # un-pin, re-queue
            return False
        try:
            own = self.pool.alloc(own_need)
        except BaseException:
            self.pool.release(m.pages + pin_tail)   # no pins leak
            raise
        if m.tail_page is not None:
            # copy-on-write: the lane keeps writing this page (prompt
            # tail, then decode) — give it a private copy; the shared
            # original stays read-only in the tree
            self.cache = self._copy_pages(
                self.cache, jnp.asarray([m.tail_page], jnp.int32),
                jnp.asarray([own[0]], jnp.int32))
            self.pool.release(pin_tail)
            self.stats["cow_copies"] += 1
        pages = m.pages + own           # logical page order
        self.lanes[i] = _Lane(r, 0, [], pages=pages, gen=gen)
        mir = self._mirror
        mir["bt"][i] = 0
        mir["bt"][i, :len(pages)] = pages
        mir["offsets"][i] = 0
        mir["frontier"][i] = r.prompt_len
        mir["remaining"][i] = r.max_new_tokens - 1
        mir["pending"][i] = 0
        mir["live"][i] = False        # decodable once the tail lands
        self._prefilling[i] = m.matched_tokens
        self._dirty = True
        self.stats["prompt_tokens"] += r.prompt_len
        self.stats["prefix_hits"] += int(m.matched_tokens > 0)
        self.stats["prefix_misses"] += int(m.matched_tokens == 0)
        self.stats["prefill_tokens_skipped"] += m.matched_tokens
        return True

    def _prefill_tails(self, lane_ids: list[int]) -> None:
        """Batched cross-request tail prefill (phased engines): the
        uncovered tails ``[matched, plen)`` of every lane admitted in
        this round advance TOGETHER, one chunk each per fused call —
        ``ceil(max_tail / chunk)`` jitted calls total instead of a
        per-lane chunk loop each (the matched slots are already backed
        by shared or CoW-copied pages holding identical K/V, so the
        logits come out bitwise-equal to a full prefill). Blocking —
        running decode lanes stall (phased semantics, counted in
        ``stalled_decode_steps``); the mixed engine fuses these same
        tails into its decode steps instead."""
        while any(i in self._prefilling for i in lane_ids):
            plan = {i: min(self.lanes[i].req.prompt_len
                           - self._prefilling[i], self.chunk)
                    for i in lane_ids if i in self._prefilling}
            self._run_mixed([], plan)

    def _sweep_finished(self, finished: list[GenResult]) -> None:
        """Evict lanes whose budget is spent, that emitted eos (the
        first prefill token may already do either), or that ran out of
        cache slots (per-lane truncation)."""
        m = self._mirror
        for i in self.active_lanes:
            lane = self.lanes[i]
            done = (len(lane.generated) >= lane.req.max_new_tokens or
                    (self.eos_id is not None and lane.generated and
                     lane.generated[-1] == self.eos_id))
            if done:
                finished.append(self._finish(i))
            elif m["frontier"][i] >= self.max_len:
                finished.append(self._finish(i, truncated=True))

    # --------------------------------------------------------------- step
    def step(self) -> list[GenResult]:
        """One engine iteration. Phased (``mixed=False``): evict,
        (re)admit — which BLOCKS on the new prompts' whole prefill —
        then one decode SLAB (``slab_k`` on-device steps, one host
        sync). Mixed: evict, admit (chunk-granular, non-blocking), then
        either ONE fused decode+prefill call (whenever the token-budget
        planner assigned prompt chunks) or a decode slab (no prompt in
        flight — full slab throughput between admissions). Returns
        requests finished during this step — successes AND structured
        failures (quarantined / cancelled / expired), plus any failure
        results parked by out-of-band paths (cancel, recovery) since
        the last step.

        An installed ``FaultPlan`` fires here: host-side faults at the
        top (before any mutation — a crash leaves the engine at the
        previous step's consistent host-sync snapshot, which is what
        makes supervisor recovery possible), device-side faults at the
        jitted call sites.

        The step's phases are profiler annotations and, with a tracer,
        spans (``Tracer.phase``): ``engine.admit``, ``engine.upload``,
        ``decode.slab.dispatch`` / ``mixed.step.dispatch``,
        ``decode.slab.wait`` / ``mixed.step.wait`` and ``engine.fold``."""
        idx = self._step_idx
        self._step_idx += 1
        if self._faults is not None:
            self._faults.on_step(idx, self)
        finished: list[GenResult] = self._pending_results
        self._pending_results = []
        with self.tracer.phase("engine.admit"):
            self._sweep_finished(finished)
            if self.enforce_deadlines:
                self._cancel_expired(finished)
            if self._preempted:
                self._try_restore()  # older work first, unless outranked
            self._admit()
            self._sweep_finished(finished)   # e.g. max_new_tokens == 1
            if self.mixed:
                decode_lanes = [i for i in self.active_lanes
                                if self._mirror["live"][i]]
                tails = [(i, self.lanes[i].req.prompt_len - pos)
                         for i, pos in self._prefilling.items()]
                plan = self.scheduler.plan_chunks(
                    tails, len(decode_lanes), self.chunk)
        if self.mixed:
            if plan:
                self._run_mixed(decode_lanes, plan)
            elif decode_lanes:
                self._decode_slab()
        elif self.active_lanes:
            self._decode_slab()
        self._harvest_faults(finished)
        self._gc_generations()
        if self._swap_monitor is not None:
            self._swap_monitor.on_step_end(self)
        # failures parked DURING this step (e.g. a corrupted offload
        # record hit by _try_restore) come out with it, not one late
        finished.extend(self._pending_results)
        self._pending_results = []
        return finished

    def _gc_generations(self) -> None:
        """Drop weight generations no lane, preempted record, or pin
        references any more — the moment the last admission-time-pinned
        request retires, the pre-swap params are freed. The CURRENT
        generation is always held."""
        if len(self._gen_params) == 1:
            return
        held = {self._gen}
        held.update(l.gen for l in self.lanes if l is not None)
        held.update(p.gen for p in self._preempted)
        held.update(self._gen_pins.values())
        for g in [g for g in self._gen_params if g not in held]:
            del self._gen_params[g]
        self.stats["weight_generations_held"] = len(self._gen_params)

    def swap_weights(self, artifact_dir: str, **kw):
        """Zero-downtime weight hot-swap from a sealed artifact:
        validate -> stage -> canary -> generational flip -> monitored
        commit (or automatic rollback). See serving/hotswap.py for the
        state machine; this is a convenience wrapper so callers hold
        only an Engine. Must be called between steps (slab boundary)."""
        from repro.serving import hotswap
        return hotswap.swap_weights(self, artifact_dir, **kw)

    def _decode_slab(self) -> None:
        """One decode slab: the on-device ``lax.scan`` token loop, one
        host sync per ``slab_k`` steps.

        During a hot-swap transition window (serving/hotswap.py) the
        live lanes may span several WEIGHT GENERATIONS: the slab then
        runs once per generation with the other generations' lanes
        masked out of ``live`` (batched decode is row-independent, so a
        masked lane's stream is bitwise-untouched — the same property
        the prefill lane-mask and continuous-batching parity already
        lean on). Outside a transition window — always, before the
        first swap — there is exactly one generation and this is the
        original single-call path."""
        self._sync_dstate()
        if self._faults is not None:
            self._faults.on_device_step(self._step_idx - 1, self)
        gens = sorted({self.lanes[i].gen for i in self.active_lanes
                       if self._mirror["live"][i]})
        if len(gens) <= 1:
            params = self._gen_params[gens[0]] if gens else self.params
            self._slab_call(params, self.active_lanes)
            return
        for g in gens:
            part = [i for i in self.active_lanes
                    if self._mirror["live"][i]
                    and self.lanes[i].gen == g]
            mask = np.zeros(self.max_batch, bool)
            mask[part] = True
            save_live = self._mirror["live"].copy()
            save_poison = self._mirror["poison"].copy()
            # mask the other generations out of this call; restore
            # their live/poison below (the scan zeroes poison and the
            # download would otherwise clobber their saved state)
            self._mirror["live"] = save_live & mask
            self._mirror["poison"] = np.where(mask, save_poison, 0.0)
            self._dirty = True
            self._sync_dstate()
            self._slab_call(self._gen_params[g], part)
            m = self._mirror
            m["live"] = np.where(mask, m["live"], save_live)
            m["poison"] = np.where(mask, m["poison"], save_poison)
            self._dirty = True

    def _slab_call(self, params, lanes: list[int]) -> None:
        """One jitted slab dispatch + host fold for ``lanes`` (the
        other lanes ride along masked)."""
        t0 = time.monotonic()
        if self.paged:
            fmax = int(max(self._mirror["frontier"][i] for i in lanes))
            need = min(fmax + self.slab_k, self.max_len)
            r = _pow2_bucket(self.pool.slots_for(need), self.max_pages)
            with self.tracer.phase("decode.slab.dispatch"):
                block, self._dstate, self.cache = self._slab(
                    params, self.cache, self._dstate, read_pages=r)
            n = len(lanes) * self.slab_k
            self.stats["pages_read"] += r * n
            self.stats["pages_read_dense_equiv"] += (
                self.pool.slots_for(self.max_len) * n)
        else:
            with self.tracer.phase("decode.slab.dispatch"):
                block, self._dstate, self.cache = self._slab(
                    params, self.cache, self._dstate)
        with self.tracer.phase("decode.slab.wait"):
            block = np.asarray(jax.block_until_ready(block))
        now = time.monotonic()
        self.stats["decode_s"] += now - t0
        self.stats["decode_slabs"] += 1
        self.stats["decode_steps"] += self.slab_k
        if self.tracer.enabled:
            self.tracer.span_at(
                "decode.slab", t0, now, k=self.slab_k,
                lanes=len(lanes),
                uids=[self.lanes[i].req.uid for i in lanes])
        with self.tracer.phase("engine.fold"):
            self._replay(block, now)

    def _run_mixed(self, decode_lanes: list[int],
                   plan: dict[int, int]) -> None:
        """ONE fused decode+prefill call: decode lanes contribute one
        token each (q_len 1 at their frontier), ``plan`` lanes a prompt
        chunk (q_len c at their prefill position), padded to a
        power-of-two query width (jit cache stays O(log chunk)). The
        host folds the returned per-lane next tokens: decode lanes
        advance with EXACTLY the slab's stop logic (frontier/remaining/
        eos — bitwise-identical greedy streams), prefill lanes advance
        their prompt position and go live when the tail lands (their
        argmax is the request's first generated token).

        Also the phased engine's batched tail-prefill core
        (``decode_lanes == []``): then the call time is prefill time
        and running decode lanes are stalled by it (counted).

        As in ``_decode_slab``, a hot-swap transition window may leave
        the participating lanes spanning several weight generations:
        the call then runs once per generation over that generation's
        lanes only (row independence keeps the split bitwise-exact);
        the single-generation case — always, outside a swap window —
        is the original one-call path."""
        gens = sorted({self.lanes[i].gen for i in decode_lanes}
                      | {self.lanes[i].gen for i in plan})
        if len(gens) <= 1:
            params = self._gen_params[gens[0]] if gens else self.params
            self._mixed_call(decode_lanes, plan, params)
            return
        for g in gens:
            dl = [i for i in decode_lanes if self.lanes[i].gen == g]
            pl = {i: c for i, c in plan.items()
                  if self.lanes[i].gen == g}
            if dl or pl:
                self._mixed_call(dl, pl, self._gen_params[g],
                                 split=True)

    def _mixed_call(self, decode_lanes: list[int], plan: dict[int, int],
                    params, split: bool = False) -> None:
        """One jitted fused call + host fold. ``split=True`` (per-
        generation call) masks the poison carry to this call's own
        lanes and clears only theirs afterwards, so a poison aimed at
        another generation's lane still reaches ITS call."""
        m = self._mirror
        w = _pow2_bucket(max(plan.values(), default=1), self._wcap)
        tokens = np.zeros((self.max_batch, w), np.int32)
        starts = np.zeros(self.max_batch, np.int32)
        q_lens = np.zeros(self.max_batch, np.int32)
        need = 1
        for i in decode_lanes:
            tokens[i, 0] = m["pending"][i]
            starts[i] = m["frontier"][i]
            q_lens[i] = 1
            need = max(need, int(m["frontier"][i]) + 1)
        for i, c in plan.items():
            pos = self._prefilling[i]
            tokens[i, :c] = self.lanes[i].req.prompt[pos:pos + c]
            starts[i] = pos
            q_lens[i] = c
            need = max(need, pos + c)
        covered = set(decode_lanes) | set(plan)
        if plan and any(bool(m["live"][j]) for j in self.active_lanes
                        if j not in covered):
            self.stats["stalled_decode_steps"] += 1
        r = _pow2_bucket(self.pool.slots_for(need), self.max_pages)
        if self._faults is not None:
            self._faults.on_device_step(self._step_idx - 1, self)
        if split:
            pmask = np.zeros(self.max_batch, bool)
            pmask[list(covered)] = True
            poison = np.where(pmask, m["poison"], 0.0)
        else:
            poison = m["poison"]
        t0 = time.monotonic()
        with self.tracer.phase("engine.upload"):
            args = [jnp.asarray(a) for a in (tokens, starts, q_lens,
                                             m["offsets"], m["bt"])]
            poison_j = jnp.asarray(poison)
        with self.tracer.phase("mixed.step.dispatch"):
            nxt, faulted, self.cache = self._mixed_fn(
                params, self.cache, *args, read_pages=r, poison=poison_j)
        if split:
            m["poison"] = np.where(pmask, 0.0, m["poison"])
        else:
            m["poison"][:] = 0.0     # one-shot, like the slab's carry
        # the host only needs the token vector when somebody emits a
        # token this call (a decode lane, or a prompt finishing its
        # tail); mid-prompt-only calls stay ASYNC so consecutive chunk
        # dispatches pipeline like the phased prefill loop's — the
        # finite-check verdict is read at the same syncs (a fault in a
        # non-emitting chunk poisons the KV it wrote, so the NEXT
        # emitting call's check still catches that lane)
        fa = None
        synced = bool(decode_lanes) or any(
            self._prefilling[i] + c >= self.lanes[i].req.prompt_len
            for i, c in plan.items())
        if synced:
            with self.tracer.phase("mixed.step.wait"):
                nxt = np.asarray(jax.block_until_ready(nxt))
                fa = np.asarray(faulted)
        now = time.monotonic()
        if self.tracer.enabled:
            # synced=False: no sync, so [t0, now) is the upload and the
            # dispatch alone; the device work lands in a later call's
            # wait
            self.tracer.span_at(
                "mixed.step", t0, now, decode_lanes=len(decode_lanes),
                prefill_lanes=len(plan),
                prefill_tokens=sum(plan.values()), synced=synced,
                uids=[self.lanes[i].req.uid
                      for i in set(decode_lanes) | set(plan)])
        with self.tracer.phase("engine.fold"):
            if self.mixed:
                self.stats["mixed_steps"] += 1
            if decode_lanes:
                self.stats["mixed_s"] += now - t0
                self.stats["decode_steps"] += 1
            else:
                # no decode lane rode along (none live, or the phased
                # engine's batched tail prefill): pure prefill time
                self.stats["prefill_s"] += now - t0
            n_tok = len(decode_lanes) + sum(plan.values())
            self.stats["pages_read"] += r * n_tok
            self.stats["pages_read_dense_equiv"] += (
                self.pool.slots_for(self.max_len) * n_tok)
            if plan:
                self.stats["prefill_chunks"] += 1
                self.stats["prefill_tokens"] += sum(plan.values())
            for i in decode_lanes:
                if fa is not None and fa[i]:
                    # non-finite logits: freeze the lane (frontier does
                    # not advance, the garbage token is never kept) and
                    # leave the verdict for _harvest_faults to quarantine
                    m["faulted"][i] = True
                    m["live"][i] = False
                    continue
                t = int(nxt[i])
                self.lanes[i].generated.append(t)
                self.lanes[i].token_times.append(now)
                m["pending"][i] = t
                m["frontier"][i] += 1
                m["remaining"][i] -= 1
                if (m["remaining"][i] <= 0
                        or m["frontier"][i] >= self.max_len
                        or (self.eos_id is not None and t == self.eos_id)):
                    m["live"][i] = False     # same cut as _run_slab's
                self.stats["generated_tokens"] += 1
                self.stats["decode_tokens"] += 1
            for i, c in plan.items():
                pos = self._prefilling[i] + c
                if pos < self.lanes[i].req.prompt_len:
                    self._prefilling[i] = pos
                    continue
                del self._prefilling[i]   # tail landed: first token out
                if fa is not None and fa[i]:
                    m["faulted"][i] = True
                    continue
                first = int(nxt[i])
                self.lanes[i].generated.append(first)
                self.lanes[i].token_times.append(now)
                m["pending"][i] = first
                m["live"][i] = True
                self.stats["generated_tokens"] += 1
            self._dirty = True

    def _replay(self, block: np.ndarray, now: float) -> None:
        """Fold a slab's token block into the host mirror using the
        per-lane state the slab returned (downloaded at the same sync —
        the device's stop logic is the single source of truth): lane i
        kept exactly ``new_frontier - old_frontier`` tokens; anything it
        emitted after its stop point is discarded here."""
        new = {k: np.array(v) for k, v in self._dstate.items()}
        for i in self.active_lanes:
            kept = int(new["frontier"][i] - self._mirror["frontier"][i])
            self.lanes[i].generated.extend(
                int(t) for t in block[i, :kept])
            self.lanes[i].token_times.extend([now] * kept)
            self.stats["generated_tokens"] += kept
            self.stats["decode_tokens"] += kept
        self._mirror = new

    # ---------------------------------------------------------------- run
    def run(self) -> dict[int, GenResult]:
        """Drain the queue and all active lanes; {uid: GenResult}."""
        out: dict[int, GenResult] = {}
        while (len(self.scheduler) or self.active_lanes
               or self._preempted or self._pending_results):
            for r in self.step():
                out[r.uid] = r
        self.finalize_stats()
        return out

    def finalize_stats(self) -> dict:
        """Fold the raw counters into derived stats (throughputs, KV
        peaks, latency percentiles). ``run`` calls this at drain;
        callers driving ``step`` themselves (continuous-arrival
        harnesses) call it when their workload ends. Returns stats."""
        # decode throughput (oracle semantics: decode-emitted tokens
        # over decode time — mixed fused-call time included, since
        # those calls carry the decode tokens); e2e adds prefill
        dec_s = self.stats["decode_s"] + self.stats["mixed_s"]
        self.stats["tok_per_s"] = (
            self.stats["decode_tokens"] / dec_s if dec_s > 0 else 0.0)
        total_s = dec_s + self.stats["prefill_s"]
        self.stats["e2e_tok_per_s"] = (
            self.stats["generated_tokens"] / total_s
            if total_s > 0 else 0.0)
        # per-request latency: TTFT (submit -> first token) and
        # inter-token gaps, over the requests FINISHED since the last
        # reset_stats (tokens folded at one host sync share timestamps,
        # so in-slab gaps read 0 and the slab boundary carries the gap)
        for name, vals in (("ttft", self._ttft), ("itl", self._itl)):
            arr = np.asarray(vals, np.float64)
            self.stats[f"{name}_p50_s"] = (
                float(np.percentile(arr, 50)) if arr.size else 0.0)
            self.stats[f"{name}_p95_s"] = (
                float(np.percentile(arr, 95)) if arr.size else 0.0)
        if self.paged:
            self.stats["peak_kv_pages"] = self.pool.peak_in_use
            # pages live lanes pin at once (shared pages count ONCE):
            # the rightsized-pool requirement — cached-idle pages are
            # reclaimable on demand, so they are excluded here while
            # peak_kv_bytes (occupancy watermark) includes them
            self.stats["peak_kv_bytes_referenced"] = (
                self.pool.peak_referenced * self.page_bytes)
        self.stats["peak_kv_bytes"] = self.kv_bytes_peak
        self.stats["kv_bytes_contiguous_equiv"] = \
            self.kv_bytes_contiguous_equiv
        self.stats["admission_rejections"] = getattr(
            self.scheduler, "rejections", 0)
        self.stats["admission_rejected_steps"] = getattr(
            self.scheduler, "rejected_steps", 0)
        if getattr(self, "_offload", None) is not None:
            self.stats["offload_bytes_peak"] = max(
                self.stats["offload_bytes_peak"],
                self._offload.bytes_peak)
            # peak vs the configured byte budget (0 = unbounded): the
            # host-RAM headroom dashboards watch
            self.stats["offload_capacity_bytes"] = (
                self._offload.capacity_bytes or 0)
        if self.pcache is not None:
            self.stats["prefix_hit_rate"] = (
                self.stats["prefill_tokens_skipped"]
                / max(1, self.stats["prompt_tokens"]))
            self.stats["cached_pages"] = self.pool.cached_pages
        return self.stats


def generate(cfg, params, prompts, *, max_new_tokens: int = 32,
             max_len: int | None = None, eos_id: int | None = None,
             prefill_chunk: int = 16, slab_k: int = 8,
             max_batch: int | None = None, dist=None, paged: bool = True,
             page_size: int = 16, n_pages: int | None = None,
             attn_backend: str = "xla", prefix_cache: bool = False,
             mixed: bool = False,
             prefill_token_budget: int | None = None,
             tracer=None):
    """Batch-convenience wrapper: list of ragged 1-D prompts (or a 2-D
    equal-length array) -> (list of per-request token arrays, stats).

    Greedy; equal-length batches are bitwise-identical to
    ``serve_loop.generate`` for every slab size and for both cache
    layouts (tests/test_serving_engine.py, tests/test_paged_kv.py). A
    request that runs out of cache headroom returns fewer than
    ``max_new_tokens`` tokens — ``stats["truncated"]`` counts them (use
    ``Engine`` directly for per-request ``GenResult.truncated``)."""
    prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
    maxp = max(p.size for p in prompts)
    max_len = max_len or (maxp + max_new_tokens)
    eng = Engine(cfg, params, max_batch=max_batch or len(prompts),
                 max_len=max_len, prefill_chunk=prefill_chunk,
                 slab_k=slab_k, eos_id=eos_id, dist=dist, paged=paged,
                 page_size=page_size, n_pages=n_pages,
                 attn_backend=attn_backend, prefix_cache=prefix_cache,
                 mixed=mixed, prefill_token_budget=prefill_token_budget,
                 tracer=tracer)
    uids = [eng.submit(p, max_new_tokens) for p in prompts]
    res = eng.run()
    return [res[u].tokens for u in uids], eng.stats
