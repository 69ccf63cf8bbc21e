"""Crash recovery: rebuild a dead engine's device state, keep its work.

The engine's step loop is ATOMIC at host syncs by construction: every
jitted call is functional (``self.cache = step(...)`` only rebinds on
success) and the host mirror folds results only after
``block_until_ready`` — so however an engine thread dies (injected
crash, real exception, watchdog-condemned hang), the host-visible
``(mirror, lanes)`` pair is exactly the snapshot of the last COMPLETED
sync. The cache is that snapshot too, with one exception: the engine
DONATES the cache to each jitted call that returns it (it is updated
in place), so a call that dies after its dispatch has already consumed
the arrays ``self.cache`` still names — they report ``is_deleted()``
and no KV is left to download. Recovery treats that exactly as device
loss. Injected crashes fire before the call, so they leave the cache
intact. ``Supervisor.recover`` turns the snapshot back into a running
engine:

  1. **salvage** — every live decode lane's KV pages (slots
     ``[0, frontier)``) are downloaded to the host offload store and
     the lane is parked as a ``_Preempted`` record (``recovered=True``)
     with its exact decode state (pending token, frontier, remaining
     budget). Restore is PR 6's zero-re-prefill path: the lane resumes
     at its saved frontier, bitwise-identical to an uninterrupted run,
     with ``re_prefilled_tokens == 0``. Skipped when the fault lost the
     device (``exc.device_lost``) or the cache was consumed by a donated
     call that died — there is nothing left to download;
  2. **relaunch** — lanes that could not salvage (device lost,
     mid-prefill, host store full) are re-queued AT THE HEAD as
     ``prompt + emitted`` with the remaining budget. Greedy decode is
     deterministic, so the re-prefilled continuation is bitwise what
     the dead lane would have produced; the engine re-splits the result
     at the original prompt boundary (``_recovered_prefix``);
  3. **rebuild** — fresh page pool, fresh (zeroed) device cache and
     slab state, fresh prefix cache (the old tree indexed pages of the
     dead pool); pre-existing preempted records keep their host KV —
     records with device-pinned shared pages get those pages salvaged
     into the record first (or relaunch, if the device is gone);
  4. finished-but-unswept lanes are synthesized into normal results —
     a completed request never re-runs just because the sweep had not
     reached it yet.

The jitted step functions are REUSED — shapes and donation patterns are
unchanged, so recovery costs no recompilation. Queued requests are
untouched (the scheduler is host state). The watchdog in
serving/frontend.py is the caller: it detects the dead/hung stepper
thread, invokes ``recover``, and restarts stepping.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.models import registry
from repro.serving.engine import GenResult, _Preempted
from repro.serving.faults import LaneFaultError, OffloadCapacityError
from repro.serving.pages import PagePool
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import Request


def cache_consumed(eng) -> bool:
    """True when the engine's cache arrays were deleted: donated to a
    jitted call that never returned its result."""
    return any(a.is_deleted() for a in jax.tree_util.tree_leaves(eng.cache))


class Supervisor:
    """Owns crash recovery for one engine (see module docstring)."""

    def __init__(self, engine):
        self.engine = engine

    # ------------------------------------------------------------ parts
    def _classify_lanes(self, device_lost: bool, results: list,
                        relaunch: list, salvaged: list) -> None:
        eng = self.engine
        m = eng._mirror
        for i in eng.active_lanes:
            lane = eng.lanes[i]
            req, gen = lane.req, lane.generated
            done = (len(gen) >= req.max_new_tokens
                    or (eng.eos_id is not None and gen
                        and gen[-1] == eng.eos_id))
            trunc = not done and int(m["frontier"][i]) >= eng.max_len
            if done or trunc:
                # finished before the crash, sweep never reached it
                prompt, full = req.prompt, list(gen)
                pre = eng._recovered_prefix.pop(req.uid, None)
                if pre is not None:
                    prompt, full = pre[0], list(pre[1]) + full
                tt = lane.token_times
                ttft = max(0.0, tt[0] - req.queued_at) if tt else 0.0
                results.append(GenResult(req.uid, prompt,
                                         np.asarray(full, np.int32),
                                         truncated=trunc, ttft_s=ttft))
                continue
            if bool(m["faulted"][i]):
                # the finite-check verdict landed but the crash beat
                # the harvest: quarantine now
                eng.stats["lanes_quarantined"] += 1
                results.append(eng._failed_result(
                    req, gen, LaneFaultError(req.uid, i)))
                continue
            if (eng.paged and not device_lost and bool(m["live"][i])
                    and i not in eng._prefilling):
                try:
                    n_live = eng.pool.slots_for(int(m["frontier"][i]))
                    k, v = eng._download_pages(lane.pages[:n_live])
                    eng._offload.save(req.uid, list(range(n_live)), k, v)
                    eng.stats["offloaded_pages"] += n_live
                    salvaged.append(_Preempted(
                        req=req, offset=lane.offset, generated=gen,
                        token_times=lane.token_times,
                        pending=int(m["pending"][i]),
                        frontier=int(m["frontier"][i]),
                        remaining=int(m["remaining"][i]),
                        n_pages=len(lane.pages), pinned={},
                        recovered=True, gen=lane.gen))
                    continue
                except OffloadCapacityError:
                    pass        # host store full: fall through
                except Exception:
                    pass        # device download failed: fall through
            relaunch.append((req, list(gen), lane.gen))

    def _resolve_preempted(self, device_lost: bool,
                           relaunch: list) -> list:
        """Pre-existing preempted records survive on the host; ones
        with device-pinned shared pages need those pages pulled down
        (device alive) or a full relaunch (device lost)."""
        eng = self.engine
        keep = []
        for pre in eng._preempted:
            if not pre.pinned:
                keep.append(pre)
                continue
            if not device_lost:
                try:
                    logical = sorted(pre.pinned)
                    pages = [pre.pinned[j] for j in logical]
                    k, v = eng._download_pages(pages)
                    if pre.req.uid in eng._offload:
                        eng._offload.extend(pre.req.uid, logical, k, v)
                    else:
                        eng._offload.save(pre.req.uid, logical, k, v)
                    eng.stats["offloaded_pages"] += len(pages)
                    pre.pinned = {}
                    keep.append(pre)
                    continue
                except Exception:
                    pass
            eng._offload.drop(pre.req.uid)
            relaunch.append((pre.req, list(pre.generated), pre.gen))
        return keep

    def _rebuild(self, keep_preempted: list) -> None:
        eng = self.engine
        if eng.paged:
            eng.pool = PagePool(eng.n_pages, eng.page_size)
            if eng._faults is not None:
                eng.pool.fault_hook = eng._faults.on_alloc
            eng.cache = registry.init_paged_cache(
                eng.cfg, eng.n_pages, eng.page_size)
            if eng.pcache is not None:
                # the old radix tree indexed pages of the dead pool
                eng.pcache = PrefixCache(eng.pool)
        else:
            eng.cache = registry.init_cache(eng.cfg, eng.max_batch,
                                            eng.max_len)
        eng.lanes = [None] * eng.max_batch
        for key in eng._mirror:
            eng._mirror[key][:] = 0
        eng._prefilling.clear()
        eng._preempted = keep_preempted
        eng._dstate = None
        eng._dirty = True
        eng._condemned.clear()

    def _relaunch(self, relaunch: list) -> None:
        eng = self.engine
        reqs, deadlines = [], []
        for req, emitted, gen in relaunch:
            # remember the ORIGINAL split so results re-split there;
            # chains across repeated crashes (prompt may already be
            # orig + earlier emissions)
            orig, prev = eng._recovered_prefix.get(
                req.uid, (req.prompt, []))
            eng._recovered_prefix[req.uid] = (orig,
                                              list(prev) + list(emitted))
            # a relaunch mid-swap must re-prefill and continue under
            # its ADMISSION-TIME weights — greedy-decode determinism
            # (the bitwise recovery guarantee) only holds against the
            # same generation; the pin is dropped when the lane retires
            eng._gen_pins[req.uid] = gen
            nr = Request(
                req.uid,
                np.concatenate([req.prompt,
                                np.asarray(emitted, np.int32)]),
                req.max_new_tokens - len(emitted),
                priority=req.priority, deadline_s=req.deadline_s)
            eng.stats["re_prefilled_tokens"] += nr.prompt_len
            reqs.append(nr)
            deadlines.append(req.deadline_at)
        eng.scheduler.reinstate(reqs)
        for nr, dl in zip(reqs, deadlines):
            if dl is not None:
                nr.deadline_at = dl   # the SLA clock does not reset

    # ---------------------------------------------------------- recover
    def recover(self, exc: BaseException) -> dict:
        """Rebuild the engine after its stepper died with ``exc``.
        Returns a summary dict (latency, lanes salvaged/relaunched) —
        also appended to the engine's pending results are any requests
        that had already finished. Safe to call repeatedly (each call
        recovers the CURRENT snapshot)."""
        eng = self.engine
        t0 = time.monotonic()
        # a donated call that died after its dispatch consumed the
        # cache: no KV survives, exactly as if the device were lost
        device_lost = (bool(getattr(exc, "device_lost", False))
                       or cache_consumed(eng))
        # the flight recorder holds the last N spans BEFORE the crash:
        # freeze them first, so the rebuild below (which clears lanes)
        # cannot disturb the timeline being reported
        eng.tracer.postmortem(
            "supervisor_recover", error=type(exc).__name__,
            device_lost=device_lost,
            active_uids=[eng.lanes[i].req.uid for i in eng.active_lanes])
        results: list = []
        relaunch: list = []
        salvaged: list = []
        self._classify_lanes(device_lost, results, relaunch, salvaged)
        keep = (self._resolve_preempted(device_lost, relaunch)
                if eng.paged else [])
        self._rebuild(keep + salvaged)
        self._relaunch(relaunch)
        eng._pending_results.extend(results)
        eng.stats["recoveries"] += 1
        if eng.paged:
            eng.stats["offload_bytes_peak"] = max(
                eng.stats["offload_bytes_peak"],
                eng._offload.bytes_peak)
        latency = time.monotonic() - t0
        if eng.tracer.enabled:
            eng.tracer.span_at("recovery", t0, t0 + latency,
                               error=type(exc).__name__,
                               device_lost=device_lost,
                               salvaged=len(salvaged),
                               relaunched=len(relaunch))
        return {"latency_s": latency,
                "device_lost": device_lost,
                "salvaged_lanes": len(salvaged),
                "relaunched_lanes": len(relaunch),
                "finished_lanes": len(results)}
