"""Crash recovery (serving/recovery.py) + the watchdog front end: an
engine whose stepper thread dies — injected host crash, device loss,
page-alloc failure, or a hung step past the watchdog deadline — is
rebuilt by the supervisor and every surviving request completes
bitwise-identical to an uninterrupted run. Live lanes with trusted
device state come back from host-offloaded KV with ZERO re-prefilled
tokens; the rest re-prefill deterministically. The chaos parity oracle
composes a NaN lane + a mid-run crash + a corrupted offload record in
one run."""
import asyncio

import jax
import numpy as np
import pytest

from conftest import tiny_cfg

from repro.models import registry
from repro.obs.trace import Tracer
from repro.serving.engine import Engine
from repro.serving.faults import (EngineCrashError, FaultPlan,
                                  LaneFaultError, RequestCancelledError)
from repro.serving.frontend import AsyncEngine
from repro.serving.recovery import Supervisor, cache_consumed


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _drain(eng):
    out, steps = {}, 0
    while (len(eng.scheduler) or eng.active_lanes or eng._preempted
           or eng._pending_results):
        for r in eng.step():
            out[r.uid] = r
        steps += 1
        assert steps < 500
    eng.finalize_stats()
    return out


def _drain_with_recovery(eng):
    """Drive to completion, recovering in place whenever a step dies —
    the synchronous stand-in for the watchdog loop."""
    out, steps = {}, 0
    while (len(eng.scheduler) or eng.active_lanes or eng._preempted
           or eng._pending_results):
        try:
            for r in eng.step():
                out[r.uid] = r
        except Exception as e:
            Supervisor(eng).recover(e)
        steps += 1
        assert steps < 500
    eng.finalize_stats()
    return out


def _pool_consistent(eng):
    pool = eng.pool
    return (pool.free_pages + pool.referenced + pool.cached_idle
            == pool.n_pages)


def _assert_parity(got, uids, base, buids):
    for u1, u0 in zip(uids, buids):
        assert got[u1].ok, got[u1].error
        assert got[u1].generated.tolist() == base[u0].generated.tolist()
        np.testing.assert_array_equal(got[u1].prompt, base[u0].prompt)


# ----------------------------------------------- supervisor, synchronous
def test_host_crash_salvages_kv_zero_reprefill(model):
    """A host-side crash leaves device arrays intact: every live lane's
    KV is salvaged to host RAM and restored at its exact frontier —
    bitwise-identical results with ZERO extra prefill tokens."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 5, 9), seed=0)

    def make(plan):
        eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                     page_size=4, faults=plan)
        return eng, [eng.submit(p, 12) for p in prompts]

    eng0, uids0 = make(None)
    base = _drain(eng0)

    eng, uids = make(FaultPlan().crash(2, device_lost=False))
    got = _drain_with_recovery(eng)
    _assert_parity(got, uids, base, uids0)
    st = eng.stats
    assert st["recoveries"] == 1 and st["engine_crashes"] == 0
    assert st["recovered_zero_reprefill"] >= 1         # salvage worked
    assert st["re_prefilled_tokens"] == 0              # nobody relaunched
    assert st["prefill_tokens"] == eng0.stats["prefill_tokens"]
    assert _pool_consistent(eng) and eng.pool.referenced == 0
    assert len(eng._offload) == 0


def test_device_loss_relaunches_deterministically(model):
    """Device loss: no KV survives, every live lane relaunches as
    prompt+emitted at the queue head — results still bitwise-identical
    (greedy decode is deterministic), re-prefill is paid and counted."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 5, 9), seed=1)

    def make(plan):
        eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                     page_size=4, faults=plan)
        return eng, [eng.submit(p, 12) for p in prompts]

    eng0, uids0 = make(None)
    base = _drain(eng0)

    eng, uids = make(FaultPlan().crash(2, device_lost=True))
    got = _drain_with_recovery(eng)
    _assert_parity(got, uids, base, uids0)
    st = eng.stats
    assert st["recoveries"] == 1
    assert st["recovered_zero_reprefill"] == 0
    assert st["re_prefilled_tokens"] > 0
    assert st["prefill_tokens"] > eng0.stats["prefill_tokens"]
    assert eng._recovered_prefix == {}        # every split resolved
    assert _pool_consistent(eng) and eng.pool.referenced == 0


def test_alloc_failure_recovers_and_survives_repeat(model):
    """A page-allocation crash recovers like any other, and a SECOND
    crash chains: the relaunch prompt folds prior emissions, results
    still re-split at the original prompt boundary."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 5), seed=2)

    def make(plan):
        eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                     page_size=4, faults=plan)
        return eng, [eng.submit(p, 12) for p in prompts]

    eng0, uids0 = make(None)
    base = _drain(eng0)

    plan = (FaultPlan().fail_alloc(0)              # crash during admit
            .crash(2, device_lost=True)            # then lose the device
            .crash(4, device_lost=True))           # and again
    eng, uids = make(plan)
    got = _drain_with_recovery(eng)
    _assert_parity(got, uids, base, uids0)
    assert len(plan.fired) >= 2                    # alloc + >=1 crash
    assert eng.stats["recoveries"] == len(plan.fired)
    assert eng.stats["faults_injected"] == len(plan.fired)
    assert _pool_consistent(eng) and eng.pool.referenced == 0


def test_recovery_preserves_queued_and_preempted(model):
    """Work that was NOT on a lane survives recovery untouched: queued
    requests stay queued (host state), a preempted record's host KV
    restores after the rebuild — still zero re-prefill for it."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 5, 6), seed=3)

    def make(plan):
        eng = Engine(cfg, params, max_batch=1, max_len=48, slab_k=4,
                     page_size=4, faults=plan)
        return eng, [eng.submit(p, 10) for p in prompts]

    eng0, uids0 = make(None)
    base = _drain(eng0)

    eng, uids = make(None)
    out = {}
    for r in eng.step():                      # uid0 starts decoding
        out[r.uid] = r
    [i] = eng.active_lanes
    eng.preempt(i)                            # uid0 frozen in host RAM
    # crash at the top of the NEXT step — before the restore pass, so
    # the record is still frozen when the supervisor runs
    eng.install_faults(FaultPlan().crash(eng._step_idx))
    try:
        eng.step()
        raise AssertionError("crash did not fire")
    except EngineCrashError as e:
        Supervisor(eng).recover(e)
    assert len(eng._preempted) == 1           # the record survived
    assert len(eng.scheduler) == 2            # so did the queue
    out.update(_drain(eng).items())
    _assert_parity(out, uids, base, uids0)
    assert eng.stats["restores"] >= 1         # uid0 came back from host
    # nobody re-prefilled: total prefill matches the fault-free run
    assert eng.stats["re_prefilled_tokens"] == 0
    assert eng.stats["prefill_tokens"] == eng0.stats["prefill_tokens"]
    assert _pool_consistent(eng) and eng.pool.referenced == 0


# -------------------------------------------------- watchdog front end
def test_watchdog_recovers_hung_step(model):
    """A step stalled past ``watchdog_s`` is condemned, torn down, and
    recovered — streams pause, then complete bitwise-identical; the
    salvage restores >=1 lane with zero re-prefill (the acceptance
    criterion, also recorded by the chaos bench)."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 5, 9), seed=4)

    def make(plan):
        eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                     page_size=4, faults=plan)
        return eng, [eng.submit(p, 12) for p in prompts]

    eng0, uids0 = make(None)
    base = _drain(eng0)

    tr = Tracer()

    async def drive():
        eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                     page_size=4, tracer=tr,
                     faults=FaultPlan().stall(2, seconds=30.0))
        # the deadline must be generous enough that a REAL (slow but
        # progressing) step never trips it — only the 30s stall does
        front = AsyncEngine(eng, watchdog_s=2.0, max_recoveries=1)
        async with front:
            streams = [await front.submit_async(p, 12) for p in prompts]
            results = [await s.result() for s in streams]
        return eng, front, {r.uid: r for r in results}

    eng, front, got = asyncio.run(drive())
    _assert_parity(got, sorted(got), base, uids0)
    st = eng.stats
    assert st["watchdog_hangs"] == 1 and st["recoveries"] == 1
    assert st["recovered_zero_reprefill"] >= 1
    assert st["re_prefilled_tokens"] == 0
    assert len(front.recovery_log) == 1
    assert front.recovery_log[0]["salvaged_lanes"] >= 1
    assert front.recovery_log[0]["latency_s"] < 10.0
    assert _pool_consistent(eng) and eng.pool.referenced == 0
    # the flight recorder dumped the hang: watchdog first, then the
    # supervisor, each carrying the condemned step's victim timelines
    reasons = [p["reason"] for p in tr.postmortems]
    assert reasons[:2] == ["watchdog_hang", "supervisor_recover"]
    pm = tr.postmortems[0]
    assert pm["spans"], "empty flight-recorder ring at the crash"
    pm_uids = {s["attrs"].get("uid") for s in pm["spans"]} | {
        u for s in pm["spans"]
        for u in (s["attrs"].get("uids") or ())}
    hung = set(tr.postmortems[1]["meta"]["active_uids"])
    assert hung and hung <= pm_uids


@pytest.mark.slow
def test_chaos_parity_oracle(model):
    """THE acceptance oracle: one seeded plan arms a NaN lane, a
    mid-run engine-thread crash, and a corrupted offloaded page — the
    non-faulted requests stream bitwise-identical to the fault-free
    run, the two faulted ones fail with structured errors, and the page
    pool balances after recovery."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 5, 9, 6), seed=5)

    eng0 = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                  page_size=4)
    uids0 = [eng0.submit(p, 12) for p in prompts]
    base = _drain(eng0)

    tr = Tracer()

    async def drive():
        # step 2: lane 1's logits poisoned (quarantine); step 4: the
        # stepper thread dies host-side (salvage both live lanes to
        # host RAM); the FIRST salvage record is bit-flipped, so that
        # lane fails its checksum at restore — three faults, one run
        plan = (FaultPlan(seed=5).poison_logits(2, 1)
                .crash(4, device_lost=False)
                .corrupt_offload(nth_save=0))
        eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                     page_size=4, faults=plan, tracer=tr)
        # no stall in this plan: hang detection stays off (watchdog_s
        # None) and the monitor only has to recover the dead stepper
        front = AsyncEngine(eng, max_recoveries=2)
        async with front:
            streams = [await front.submit_async(p, 12) for p in prompts]
            results = {}
            for s in streams:
                try:
                    res = await s.result()
                except Exception as e:         # structured failure
                    results[s.uid] = e
                else:
                    results[res.uid] = res
        return eng, plan, results

    eng, plan, got = asyncio.run(drive())
    assert len(plan.fired) == 3                # all three faults fired
    failed = {u: r for u, r in got.items()
              if isinstance(r, Exception)}
    # exactly two victims: the poisoned lane and the corrupted record
    assert len(failed) == 2
    assert all(isinstance(e, LaneFaultError) for e in failed.values())
    assert sum("checksum" in e.reason for e in failed.values()) == 1
    survivors = sorted(u for u in got if u not in failed)
    _assert_parity(got, survivors, base, survivors)
    st = eng.stats
    assert st["faults_injected"] == 3
    assert st["lanes_quarantined"] == 2
    assert st["recoveries"] == 1 and st["engine_crashes"] == 1
    # free + referenced + cached_idle == n_pages after the dust settles
    assert _pool_consistent(eng) and eng.pool.referenced == 0
    assert len(eng._offload) == 0
    # flight recorder: the stepper crash produced postmortems whose
    # frozen ring holds EVERY victim's span timeline — the poisoned
    # lane's quarantine landed before the crash, so it is in the dump
    assert [p["reason"] for p in tr.postmortems][:2] == [
        "watchdog_crash", "supervisor_recover"]
    pm = tr.postmortems[0]
    assert pm["spans"]
    pm_uids = {s["attrs"].get("uid") for s in pm["spans"]} | {
        u for s in pm["spans"]
        for u in (s["attrs"].get("uids") or ())}
    assert set(failed) <= pm_uids
    quarantined = [s for s in pm["spans"]
                   if s["name"] == "request.quarantined"]
    assert quarantined and quarantined[0]["attrs"]["uid"] in failed


# -------------------------------------------------- front-end satellites
def test_stream_cancel_is_safe_and_isolated(model):
    """``TokenStream.cancel``: the cancelled stream ends with its error
    swallowed, its lane and pages free, the OTHER stream is
    bitwise-identical to a run where the cancelled request never
    interfered — and cancelling twice (or after completion) is a
    no-op."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 5), seed=6)

    eng0 = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                  page_size=4)
    uids0 = [eng0.submit(p, 20) for p in prompts]
    base = _drain(eng0)

    async def drive():
        eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                     page_size=4)
        async with AsyncEngine(eng) as front:
            s0 = await front.submit_async(prompts[0], 20)
            s1 = await front.submit_async(prompts[1], 20)
            await s0.__anext__()               # s0 is mid-decode
            await s0.cancel()
            await s0.cancel()                  # twice: no-op
            with pytest.raises(RequestCancelledError):
                await s0.result()
            r1 = await s1.result()
            await s1.cancel()                  # after completion: no-op
            assert (await s1.result()) is r1
            return eng, r1

    eng, r1 = asyncio.run(drive())
    assert r1.generated.tolist() == base[uids0[1]].generated.tolist()
    assert eng.stats["cancelled"] == 1
    assert _pool_consistent(eng) and eng.pool.referenced == 0


def test_aclose_finalizes_orphan_streams(model):
    """Satellite: ``aclose`` must leave NO stream hanging — anything
    still unfinished at teardown (inbox entries that never submitted,
    streams orphaned by a dead stepper) fails with
    ``RequestCancelledError`` instead of awaiting forever."""
    cfg, params = model

    async def drive():
        eng = Engine(cfg, params, max_batch=1, max_len=48, slab_k=4,
                     page_size=4)
        front = AsyncEngine(eng).start()
        s = await front.submit_async(np.ones(4, np.int32), 4)
        await s.result()
        await front.aclose()        # clean shutdown: everything drained
        # orphan a stream + an unsubmitted inbox entry AFTER the
        # stepper is gone (the states a dead stepper leaves behind —
        # nothing will ever finish them except the aclose sweep)
        from repro.serving.frontend import TokenStream
        loop = asyncio.get_running_loop()
        orphan, inboxed = TokenStream(loop), TokenStream(loop)
        orphan._front = inboxed._front = front
        front._streams[999] = orphan
        front._inbox.append(
            (np.ones(4, np.int32), 4, 0, None, inboxed))
        await front.aclose()        # safe to call twice; sweeps both
        for stream in (orphan, inboxed):
            with pytest.raises(RequestCancelledError):
                await stream.result()
            with pytest.raises(RequestCancelledError):
                await stream._submitted
            with pytest.raises(StopAsyncIteration):
                await stream.__anext__()

    asyncio.run(drive())


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_crash_without_recovery_budget_fails_streams(model):
    """max_recoveries=0 keeps the legacy contract: the first crash
    fails every open stream with the structured error instead of
    recovering."""
    cfg, params = model

    async def drive():
        eng = Engine(cfg, params, max_batch=1, max_len=48, slab_k=4,
                     page_size=4,
                     faults=FaultPlan().crash(1, device_lost=False))
        async with AsyncEngine(eng) as front:
            s = await front.submit_async(np.ones(6, np.int32), 12)
            with pytest.raises(EngineCrashError):
                await s.result()

    asyncio.run(drive())


def test_call_that_dies_after_dispatch_relaunches(model):
    """A donated call that dies after its dispatch has consumed the
    cache the engine still names: recovery sees the deleted arrays,
    takes the device-lost branch (no salvage, every live lane
    relaunches) and every request still completes bitwise-identical."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 5, 9), seed=2)

    def make():
        eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                     page_size=4)
        return eng, [eng.submit(p, 12) for p in prompts]

    eng0, uids0 = make()
    base = _drain(eng0)

    eng, uids = make()
    slab, calls = eng._slab, [0]

    def dies_after_dispatch(*a, **k):
        out = slab(*a, **k)
        calls[0] += 1
        if calls[0] == 2:
            raise RuntimeError("stepper died after the slab's dispatch")
        return out

    eng._slab = dies_after_dispatch
    got, summaries, steps = {}, [], 0
    while (len(eng.scheduler) or eng.active_lanes or eng._preempted
           or eng._pending_results):
        try:
            for r in eng.step():
                got[r.uid] = r
        except RuntimeError as e:
            assert cache_consumed(eng)
            summaries.append(Supervisor(eng).recover(e))
        steps += 1
        assert steps < 500
    eng.finalize_stats()
    _assert_parity(got, uids, base, uids0)
    [s] = summaries
    assert s["device_lost"] and s["salvaged_lanes"] == 0
    assert s["relaunched_lanes"] >= 1
    assert not cache_consumed(eng)
    st = eng.stats
    assert st["recovered_zero_reprefill"] == 0
    assert st["re_prefilled_tokens"] > 0
    assert _pool_consistent(eng) and eng.pool.referenced == 0
