"""Unified observability layer (obs/): typed metrics registry with the
anti-drift reset guarantee, zero-overhead request-span tracing (no span
objects allocated when disabled, no bit changed when enabled — serving
AND training), Prometheus exposition round-trip, Chrome/Perfetto
export, and the crash flight recorder's postmortem contents."""
import json
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from conftest import tiny_cfg
from repro.data.pipeline import SyntheticLM
from repro.models import registry
from repro.obs import export as obs_export
from repro.obs import trace as trace_mod
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               parse_prometheus_text)
from repro.obs.trace import NULL_TRACER, Tracer
from repro.optim import adamw
from repro.serving.engine import Engine
from repro.serving.faults import FaultPlan, LaneFaultError
from repro.training import train_loop
from repro.training.faults import TrainFaultPlan


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
    out = {}
    steps = 0
    while (len(eng.scheduler) or eng.active_lanes or eng._preempted
           or eng._pending_results):
        for r in eng.step():
            out[r.uid] = r
        steps += 1
        assert steps < 500
    eng.finalize_stats()
    return out


# ------------------------------------------------------ metrics registry
def test_registry_kinds_and_reset():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "requests")
    g = reg.gauge("depth")
    h = reg.histogram("lat_s")
    c.inc()
    c.inc(3)
    g.set(7.5)
    h.observe(0.1)
    h.observe(0.3)
    assert isinstance(c, Counter) and isinstance(g, Gauge)
    assert isinstance(h, Histogram)
    assert reg.counter("reqs") is c          # get-or-create
    snap = reg.snapshot()
    assert snap["reqs"] == 4 and snap["depth"] == 7.5
    assert snap["lat_s"]["count"] == 2
    assert snap["lat_s"]["sum"] == pytest.approx(0.4)
    reg.reset()
    snap = reg.snapshot()
    assert snap["reqs"] == 0 and snap["depth"] == 0
    assert snap["lat_s"]["count"] == 0


def test_histogram_reset_keeps_list_identity():
    """The engine exposes ``Histogram.samples`` directly (``_ttft``);
    reset must clear IN PLACE so held references stay live."""
    h = Histogram("x")
    ref = h.samples
    h.observe(1.0)
    h.reset()
    h.observe(2.0)
    assert ref == [2.0] and h.samples is ref


def test_histogram_percentile_matches_numpy():
    h = Histogram("x")
    vals = [0.5, 0.1, 0.9, 0.3, 0.7, 0.2]
    for v in vals:
        h.observe(v)
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(vals, q)))


def test_stats_view_is_a_dict_facade():
    reg = MetricsRegistry()
    reg.counter("a")
    view = reg.view()
    view["a"] += 2                        # counter through the view
    view["b"] = 5                         # auto-registers a Counter
    view["r"] = 1.5                       # float auto-registers a Gauge
    reg.histogram("h").observe(1.0)
    assert view["a"] == 2 and view["b"] == 5
    assert reg["b"].kind == "counter" and reg["r"].kind == "gauge"
    assert "h" not in view                # histograms not in the facade
    with pytest.raises(KeyError):
        view["h"]
    d = dict(view)
    assert d == {"a": 2, "b": 5, "r": 1.5}
    view.update({"a": 9})
    assert view["a"] == 9
    reg.reset()                           # auto-registered keys too
    assert dict(view) == {"a": 0, "b": 0, "r": 0}


def test_engine_reset_stats_round_trips_every_metric(model):
    """THE anti-drift regression (the bug class that bit PR 6 and
    PR 7): mutate EVERY registered scalar and histogram, reset, and
    require every one of them back at its zero — including stats
    auto-registered at finalize time. No hand-kept key list exists to
    go stale."""
    cfg, params = model
    eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                 page_size=4)
    for p in _prompts(cfg, (6, 5)):
        eng.submit(p, 6)
    _drain(eng)                           # populates + finalizes
    for name in eng.metrics.names():
        m = eng.metrics[name]
        if isinstance(m, Histogram):
            m.observe(1.0)
        else:
            m.set(m.get() + 1)            # force every scalar nonzero
    assert any(v for v in dict(eng.stats).values())
    eng.reset_stats()
    for name in eng.metrics.names():
        m = eng.metrics[name]
        if isinstance(m, Histogram):
            assert m.samples == [], name
        else:
            assert m.get() == 0, name


# ------------------------------------------------------------ exposition
def test_prometheus_text_round_trip():
    reg = MetricsRegistry(namespace="blast")
    reg.counter("decode_tokens", "tokens emitted").inc(41)
    reg.gauge("queue_depth_peak").set(3)
    h = reg.histogram("ttft_s", "time to first token")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    text = reg.prometheus_text()
    assert "# TYPE blast_decode_tokens counter" in text
    assert "# HELP blast_decode_tokens tokens emitted" in text
    assert "# TYPE blast_ttft_s summary" in text
    parsed = parse_prometheus_text(text)
    assert parsed["blast_decode_tokens"] == 41
    assert parsed["blast_queue_depth_peak"] == 3
    assert parsed["blast_ttft_s_count"] == 3
    assert parsed["blast_ttft_s_sum"] == pytest.approx(0.6)
    assert parsed["blast_ttft_s"]['quantile="0.5"'] == pytest.approx(0.2)


def test_prometheus_parser_rejects_malformed():
    with pytest.raises(ValueError):
        parse_prometheus_text("not a sample line at all\n")
    with pytest.raises(ValueError):
        parse_prometheus_text("# BOGUS comment kind\n")


# --------------------------------------------------------------- tracing
def test_tracer_records_and_spans_for():
    clock = iter(float(i) for i in range(100))
    tr = Tracer(capacity=8, clock=lambda: next(clock))
    tr.span_at("decode.slab", 1.0, 2.0, k=4, uids=[1, 2])
    tr.event("request.finish", uid=1, tokens=5)
    with tr.span("ckpt.save", step=3):
        pass
    assert [s.name for s in tr.records] == [
        "decode.slab", "request.finish", "ckpt.save"]
    assert tr.records[0].dur == 1.0
    mine = tr.spans_for(1)
    assert [s["name"] for s in mine] == ["decode.slab",
                                         "request.finish"]
    assert tr.spans_for(99) == []
    # bounded ring: old spans fall off, never an unbounded list
    for i in range(20):
        tr.event("e", t=float(i))
    assert len(tr.records) == 8


def test_span_ctx_records_error_name():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("ckpt.restore", step=1):
            raise RuntimeError("boom")
    assert tr.records[-1].attrs["error"] == "RuntimeError"


def test_postmortem_payload_and_file(tmp_path):
    tr = Tracer(postmortem_dir=str(tmp_path))
    tr.event("request.queued", t=0.0, uid=7)
    pm = tr.postmortem("watchdog_crash", error="EngineCrashError")
    assert pm["reason"] == "watchdog_crash"
    assert pm["meta"]["error"] == "EngineCrashError"
    assert [s["name"] for s in pm["spans"]] == ["request.queued"]
    assert tr.postmortems == [pm]
    files = sorted(p.name for p in tmp_path.iterdir())
    assert files == ["postmortem_0000_watchdog_crash.json"]
    with open(tmp_path / files[0]) as f:
        assert json.load(f)["reason"] == "watchdog_crash"


def test_chrome_trace_export():
    tr = Tracer()
    tr.span_at("decode.slab", 1.0, 2.0, k=4, uids=[0, 1])
    tr.event("request.finish", t=2.5, uid=1, tokens=5)
    doc = tr.chrome_trace()
    ev = doc["traceEvents"]
    assert len(ev) == 2
    slab, fin = ev
    assert slab["ph"] == "X" and slab["dur"] == pytest.approx(1e6)
    # on the profiler's clock: the tracer's seconds plus its offset
    assert slab["ts"] == pytest.approx((1.0 + tr.clock_offset) * 1e6)
    assert fin["ph"] == "i" and fin["s"] == "t"
    assert fin["tid"] == 2                # uid 1 -> row 2 (0 = engine)
    json.dumps(doc)                       # valid JSON all the way down
    # the exporter also takes already-serialized dicts (postmortems)
    again = obs_export.to_chrome_trace([s.to_dict()
                                        for s in tr.records],
                                       offset_s=tr.clock_offset)
    assert again["traceEvents"] == ev


# -------------------------------------------- zero-overhead: allocation
def test_disabled_tracing_allocates_no_spans(model, monkeypatch):
    """With no tracer installed the hot path must never construct a
    Span (or call any recording method): count every Span.__init__
    while a full workload runs against NULL_TRACER."""
    calls = []
    orig = trace_mod.Span

    class CountingSpan(orig):
        def __init__(self, *a, **kw):
            calls.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace_mod, "Span", CountingSpan)
    cfg, params = model
    eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                 page_size=4)
    assert eng.tracer is NULL_TRACER
    for p in _prompts(cfg, (6, 5, 7)):
        eng.submit(p, 8)
    _drain(eng)
    assert calls == []


def test_null_tracer_phase_allocates_no_span(monkeypatch):
    """A phase with tracing off is the profiler annotation alone: no
    ``Span`` is built."""
    calls = []
    orig = trace_mod.Span

    class CountingSpan(orig):
        def __init__(self, *a, **kw):
            calls.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace_mod, "Span", CountingSpan)
    ctx = NULL_TRACER.phase("engine.fold", lanes=3)
    assert isinstance(ctx, TraceAnnotation)
    with ctx:
        pass
    assert calls == []


def test_tracer_phase_is_span_and_annotation(tmp_path):
    """An enabled tracer's phase records a span of the annotation's
    name, and on the profiler's clock the span lies within the
    annotation the profiler recorded."""
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.phase("engine.fold", lanes=2):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (span,) = tr.records
    assert span.name == "engine.fold" and span.attrs == {"lanes": 2}
    assert span.dur >= 0.005
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    start = dict(pd.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    (ann,) = [e for p in pd.planes if p.name.startswith("/host:")
              for ln in p.lines for e in ln.events
              if e.name == "engine.fold"]
    ev = tr.chrome_trace()["traceEvents"][0]
    ann_us = (start + ann.start_ns) * 1e-3, (start + ann.end_ns) * 1e-3
    assert ann_us[0] <= ev["ts"] + 1e3 and \
        ev["ts"] + ev["dur"] <= ann_us[1] + 1e3      # within 1 ms


# ----------------------------------------------- bitwise parity oracles
def test_serving_parity_tracing_on_vs_off(model):
    """THE serving oracle: the same workload with tracing enabled emits
    bitwise-identical tokens (spans attach at existing host syncs only;
    no device-graph change), and the trace actually covers the whole
    request lifecycle."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 5, 9), seed=4)

    def run(tracer):
        eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                     page_size=4, tracer=tracer)
        uids = [eng.submit(p, 12) for p in prompts]
        return uids, _drain(eng)

    uids0, base = run(None)
    tr = Tracer()
    uids1, got = run(tr)
    for u0, u1 in zip(uids0, uids1):
        assert got[u1].tokens.tolist() == base[u0].tokens.tolist()
    names = {s.name for s in tr.records}
    assert {"request.queued", "request.admitted", "prefill.chunks",
            "decode.slab", "request.finish"} <= names
    # every request has a queued -> admitted -> finish timeline
    for u in uids1:
        mine = [s["name"] for s in tr.spans_for(u)]
        assert mine[0] == "request.queued"
        assert "request.admitted" in mine
        assert mine[-1] == "request.finish"


def test_training_parity_tracing_on_vs_off():
    """THE training oracle: identical TrainState leaves with tracing on
    vs off, and the tracer carries train.step spans plus the routed
    structured events."""
    cfg = tiny_cfg()

    def run(tracer):
        src = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=16,
                          seed=3)
        opt = adamw.AdamWConfig(peak_lr=2e-2, warmup_steps=5,
                                total_steps=60, weight_decay=0.0)
        loop = train_loop.TrainLoopConfig(total_steps=8, log_every=4)
        return train_loop.train(cfg, opt, src, loop,
                                log_fn=lambda m: None, tracer=tracer)

    state_a, hist_a = run(None)
    tr = Tracer()
    state_b, hist_b = run(tr)
    leaves = lambda st: jax.tree_util.tree_leaves(  # noqa: E731
        {"step": st.step, "params": st.params,
         "opt_state": st.opt_state, "masks": st.masks, "rng": st.rng})
    for a, b in zip(leaves(state_a), leaves(state_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    steps = [s for s in tr.records if s.name == "train.step"]
    assert len(steps) == 8
    assert [s.attrs["step"] for s in steps] == list(range(8))
    assert all(s.dur > 0 for s in steps)


def test_training_events_route_through_tracer():
    """Satellite: straggler/anomaly/rewind history events and the span
    stream share ONE schema — every structured history event appears as
    a train.* span with the same fields."""
    cfg = tiny_cfg()
    src = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=16,
                      seed=3)
    opt = adamw.AdamWConfig(peak_lr=2e-2, warmup_steps=5,
                            total_steps=60, weight_decay=0.0)
    loop = train_loop.TrainLoopConfig(total_steps=10, log_every=5)
    tr = Tracer()
    reg = MetricsRegistry(namespace="blast_train")
    _, hist = train_loop.train(
        cfg, opt, src, loop, log_fn=lambda m: None, tracer=tr,
        metrics=reg, faults=TrainFaultPlan().nan_grads(4))
    events = [h for h in hist if "event" in h]
    # every history event has a matching train.* span, same fields
    by_name = {}
    for s in tr.records:
        by_name.setdefault(s.name, []).append(s)
    for h in events:
        spans = by_name.get("train." + h["event"])
        assert spans, f"no span for history event {h['event']!r}"
        assert any(all(s.attrs.get(k) == v for k, v in h.items()
                       if k != "event") for s in spans)
    # the guard's own anomaly event fired for the injected NaN step
    anom = by_name.get("train.anomaly")
    assert anom and anom[0].attrs["verdict"] == "skip"
    assert anom[0].attrs["step"] == 4
    # injected registry scraped the loop's counters
    assert reg.counter("skipped_steps").get() == 1
    assert parse_prometheus_text(reg.prometheus_text())[
        "blast_train_skipped_steps"] == 1


# ------------------------------------------------------- flight recorder
def test_flight_recorder_captures_poisoned_lane(model):
    """A quarantined request's full timeline — queued, admitted, and
    the quarantine itself — is retrievable from the ring by uid."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 5, 9), seed=4)
    tr = Tracer()
    eng = Engine(cfg, params, max_batch=2, max_len=48, slab_k=4,
                 page_size=4, tracer=tr,
                 faults=FaultPlan(seed=0).poison_logits(2, 0))
    uids = [eng.submit(p, 12) for p in prompts]
    got = _drain(eng)
    victim = uids[0]
    assert isinstance(got[victim].error, LaneFaultError)
    mine = [s["name"] for s in tr.spans_for(victim)]
    assert mine[0] == "request.queued"
    assert "request.admitted" in mine
    assert mine[-1] == "request.quarantined"
    q = tr.spans_for(victim)[-1]["attrs"]
    assert q["error"] == "LaneFaultError" and q["lane"] == 0
    # survivors finished normally in the same ring
    for u in uids[1:]:
        assert [s["name"] for s in tr.spans_for(u)][-1] \
            == "request.finish"
