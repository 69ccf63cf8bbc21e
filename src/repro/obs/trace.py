"""Request-span tracing with a bounded flight recorder.

The prime directive of this repo's serving/training hot paths is ONE
host sync per slab / step — so the tracer never creates sync points of
its own. Instrumented code hands ``span_at`` the ``t0``/``now``
monotonic timestamps it ALREADY captured around its jitted calls, and
the tracer's whole job is to remember them:

  tr.span_at("decode.slab", t0, now, lanes=4, k=8)     # completed span
  tr.event("request.finish", uid=3, tokens=17)         # point event
  with tr.span("ckpt.save", step=40):                  # host-only phase
      ...

Completed spans/events land in a ``deque(maxlen=capacity)`` — the
flight recorder. Appends are GIL-atomic, so the engine thread, the
asyncio front end, and a watchdog thread share one tracer without a
lock (the same idiom as serving/frontend.py's token deques). When a
crash path fires (watchdog, supervisor, training rewind),
``postmortem()`` freezes the ring into a JSON dump: the last N things
that happened, with the victim request's uid threaded through its
spans, instead of nothing.

``NULL_TRACER`` is the disabled default. Its methods are no-ops that
never touch ``Span`` — tests/test_obs.py proves no span object is
allocated on the hot path when tracing is off. Instrumented sites that
would build attribute collections eagerly guard on ``tracer.enabled``.

Engine phases go through ``phase(name)``: a context manager that always
enters a ``jax.profiler.TraceAnnotation`` of that name (one native
check when no profile is being collected), so a profiler trace names
what the host was doing around each device op; an enabled ``Tracer``
also records the phase as a ``Span``. ``Tracer.clock_offset`` maps the
tracer's clock onto the profiler host plane's (the wall clock), so
``chrome_trace()`` can be laid over a profile.

``SCOPES`` is the one vocabulary of ``jax.named_scope`` names the model
step carries into its HLO metadata (``op_name``), from which a device
trace attributes each op to a layer:

  embed          token embedding lookup
  norm           a layer's pre-attention and pre-MLP norms
  attn           one attention layer (the parent of the attn.* scopes)
  attn.qkv       q/k/v projections and rope
  attn.kv_write  writing the new K/V into the cache (``paged_write``)
  attn.kv_read   reading cached K/V (``gather_pages``) and its dtype
                 convert
  attn.core      scores, softmax and p·v
  attn.out       the output projection ``wo``
  mlp            the (packed, block-sparse) MLP
  lm_head        the final norm and the head matmul
  sample         argmax, stop logic and lane-state update of a serving
                 step
"""
from __future__ import annotations

import json
import os
import time
from collections import deque

from jax.profiler import TraceAnnotation

SCOPES = ("embed", "norm", "attn", "attn.qkv", "attn.kv_write",
          "attn.kv_read", "attn.core", "attn.out", "mlp", "lm_head",
          "sample")


class Span:
    """One completed span (or point event: ``t1 == t0``). Monotonic
    timestamps, arbitrary small attrs (uids, counts, error names)."""
    __slots__ = ("name", "t0", "t1", "attrs")

    def __init__(self, name: str, t0: float, t1: float, attrs: dict):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.attrs = attrs

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        return {"name": self.name, "t0": self.t0, "t1": self.t1,
                "attrs": self.attrs}

    def __repr__(self):
        return (f"Span({self.name!r}, t0={self.t0:.6f}, "
                f"dur={self.dur:.6f}, {self.attrs})")


class _SpanCtx:
    """Context manager for host-only phases (checkpoint writes,
    supervisor recovery, engine phases) where the span IS allowed to
    read the clock — reading it adds no device sync. With ``ann`` (a
    ``TraceAnnotation``) the span also lies inside that profiler
    annotation."""
    __slots__ = ("_tr", "_name", "_attrs", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict,
                 ann: TraceAnnotation | None = None):
        self._tr = tracer
        self._name = name
        self._attrs = attrs
        self._ann = ann

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = self._tr.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = self._tr.clock()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self._attrs["error"] = exc_type.__name__
        self._tr.span_at(self._name, self._t0, t1, **self._attrs)
        return False


class Tracer:
    """Span recorder + flight recorder + postmortem dumper.

    ``capacity`` bounds the ring buffer (host memory is the only cost:
    ~one small object per slab/step/event, not per token).
    ``postmortem_dir`` (optional) is where ``postmortem()`` writes its
    JSON dumps; without it the payloads still accumulate on
    ``self.postmortems`` for programmatic access. ``clock_offset``
    (seconds) is the profiler's clock minus ``clock``, read once here:
    the JAX profiler stamps host events with the wall clock."""

    enabled = True

    def __init__(self, capacity: int = 4096,
                 postmortem_dir: str | None = None,
                 clock=time.monotonic):
        self.capacity = capacity
        self.clock = clock
        self.clock_offset = time.time() - clock()
        self.postmortem_dir = postmortem_dir
        self.records: deque[Span] = deque(maxlen=capacity)
        self.postmortems: list[dict] = []
        self._pm_seq = 0

    # -------------------------------------------------------- recording
    def span_at(self, name: str, t0: float, t1: float, **attrs) -> None:
        """Record a span from timestamps the caller ALREADY took at its
        existing host-sync points — the zero-extra-sync attach."""
        self.records.append(Span(name, t0, t1, attrs))

    def event(self, name: str, t: float | None = None, **attrs) -> None:
        """Point event (admission, finish, preempt, quarantine...).
        ``t`` defaults to now — events fire from host control flow,
        never between a device dispatch and its sync."""
        if t is None:
            t = self.clock()
        self.records.append(Span(name, t, t, attrs))

    def span(self, name: str, **attrs) -> _SpanCtx:
        return _SpanCtx(self, name, attrs)

    def phase(self, name: str, **attrs) -> _SpanCtx:
        """An engine phase: a profiler annotation and a span, both
        ``name``, over the ``with`` block."""
        return _SpanCtx(self, name, attrs, TraceAnnotation(name))

    # ---------------------------------------------------- flight recorder
    def snapshot(self) -> list[dict]:
        """The ring as JSON-able dicts, oldest first. Snapshotting the
        deque (GIL-atomic copy) before iterating keeps this safe against
        concurrent appends from the engine thread."""
        return [s.to_dict() for s in list(self.records)]

    def spans_for(self, uid) -> list[dict]:
        """A request's timeline: every retained span/event whose attrs
        carry the uid (directly or in a ``uids`` list)."""
        out = []
        for s in list(self.records):
            a = s.attrs
            if a.get("uid") == uid or uid in (a.get("uids") or ()):
                out.append(s.to_dict())
        return out

    def postmortem(self, reason: str, **meta) -> dict:
        """Freeze the flight recorder into a crash dump. Writes
        ``postmortem_<seq>_<reason>.json`` under ``postmortem_dir``
        when one is set; always appends the payload to
        ``self.postmortems``. Never raises — a failing dump must not
        mask the crash being reported."""
        payload = {
            "reason": reason,
            "wall_time_unix": time.time(),
            "monotonic": self.clock(),
            "meta": meta,
            "spans": self.snapshot(),
        }
        self.postmortems.append(payload)
        if self.postmortem_dir is not None:
            try:
                os.makedirs(self.postmortem_dir, exist_ok=True)
                fname = f"postmortem_{self._pm_seq:04d}_{reason}.json"
                with open(os.path.join(self.postmortem_dir, fname),
                          "w") as f:
                    json.dump(payload, f, indent=2, default=str)
            except OSError:
                pass
        self._pm_seq += 1
        return payload

    # ------------------------------------------------------------ export
    def chrome_trace(self) -> dict:
        """The ring as trace events on the profiler's clock."""
        from repro.obs.export import to_chrome_trace
        return to_chrome_trace(list(self.records),
                               offset_s=self.clock_offset)


class _NullCtx:
    """Shared reusable no-op context manager."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_CTX = _NullCtx()


class _NullTracer:
    """Tracing disabled: every method is a no-op that never constructs
    a ``Span`` (or anything else). Hot-path sites additionally guard
    attr building on ``tracer.enabled`` so the disabled engine runs
    byte-for-byte the same work as before tracing existed."""

    enabled = False
    records = ()          # empty, iterable, immutable
    postmortems = ()

    def span_at(self, name, t0, t1, **attrs) -> None:
        pass

    def event(self, name, t=None, **attrs) -> None:
        pass

    def span(self, name, **attrs) -> _NullCtx:
        return _NULL_CTX

    def phase(self, name, **attrs) -> TraceAnnotation:
        return TraceAnnotation(name)

    def snapshot(self) -> list:
        return []

    def spans_for(self, uid) -> list:
        return []

    def postmortem(self, reason, **meta) -> None:
        return None

    def chrome_trace(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}


NULL_TRACER = _NullTracer()
