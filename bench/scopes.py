"""Device time of the traced window by the model's named scopes.

The program runs each layer of its step under a ``jax.named_scope`` from
one vocabulary (``repro.obs.trace.SCOPES``); the compiler keeps the name
path as each HLO op's ``op_name``. A v5e profile keeps it as the
``tf_op`` stat of each op's event metadata, which ``ProfileData`` does
not expose, and keeps each program's compiled HLO in its
``/host:metadata`` plane (an ``Hlo Proto`` stat per program, keyed
``<module>(<program id>)``), which ``op_names`` reads from the raw
file. A device op belongs to the program whose ``XLA Modules`` event
holds it, and its ``op_name`` is that program's instruction of the
same name. An op counts for the innermost vocabulary scope on its path.

Two kinds of op have no scope of their own: those the compiler made
(a convert it added, a rematerialised copy), which carry no
``op_name``, and the layer loop's own slicing and stacking of what it
carries (an ``op_name`` right inside a loop body: ``while/body/
dynamic_slice``, ``dynamic_update_slice``, ``squeeze``, the counter's
``add``). Such an op counts for the scope of the first of its operands
that resolves to one, else of the first op that consumes it: the trace
names each op by its instruction text (``%convert.142 = ...
convert(... %fusion.228)``), which lists its operands. So the f32
convert the compiler puts after the paged gather counts as
``attn.kv_read``, and slicing a layer's KV pool out of the stack and
stacking the updated slice back count as ``attn.kv_write``. What
resolves to no scope counts as ``unscoped``: copies of the whole carried
pool between loop iterations, anything outside the model.

Device op events are clipped to the ``bench.window`` annotation and
container ops (``while``, ``call``) are skipped, as in ``devtrace``.
The profile is the newest ``.xplane.pb`` under the serve driver's
``PROFILE_DIR``, read once per process.
"""
from __future__ import annotations

import bisect
import functools
import re
from pathlib import Path

import devtrace
import harness

UNSCOPED = "unscoped"
_NAME = re.compile(r"^%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
# an op_name right inside a loop body: the loop's slicing, stacking and
# counter
LOOP = re.compile(r"while/body(/add|(/closed_call)?"
                  r"(/(dynamic_slice|dynamic_update_slice|squeeze))?)$")


def vocabulary() -> tuple[str, ...]:
    """The program's scope names; none where the program has none."""
    try:
        from repro.obs.trace import SCOPES
    except ImportError:
        return ()
    return tuple(SCOPES)


def scope_of(op_name: str, vocab) -> str | None:
    """The innermost vocabulary scope on an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in vocab:
            return part
    return None


def _instruction(name: str) -> tuple[str, list[str]]:
    """(instruction name, operand names) from an op event's name."""
    m = _NAME.match(name)
    return (m.group(1) if m else name), \
        _OPERAND.findall(name.partition("=")[2])


def _varint(b, i: int) -> tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        if c < 0x80:
            return r, i
        shift += 7


def _fields(b):
    """(field number, value) of a serialized protobuf message: an int
    for varint and fixed-width fields, a memoryview for the rest."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = int.from_bytes(b[i:i + size], "little"), i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _first(b, number: int):
    return next((v for f, v in _fields(b) if f == number), None)


def _hlo_op_names(proto) -> dict[str, str]:
    """{instruction: op_name} of a serialized ``xla.HloProto``: module
    (field 1), its computations (3), their instructions (2), each with
    its name (1) and ``OpMetadata`` (7), whose op_name is field 2."""
    out = {}
    module = _first(proto, 1)
    for f, comp in _fields(module if module is not None else b""):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g != 2:
                continue
            name = meta = None
            for h, v in _fields(ins):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    meta = v
            op = _first(meta, 2) if meta is not None else None
            if name and op is not None:
                out[name] = bytes(op).decode()
    return out


def op_names(raw: bytes) -> dict[str, dict[str, str]]:
    """{program: {instruction: op_name}} from the ``Hlo Proto`` stats of
    the ``/host:metadata`` plane of a serialized ``XSpace`` (planes:
    field 1; a plane's name: 2, event metadata: 4, stat metadata: 5)."""
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1 or bytes(_first(plane, 2) or b"") != b"/host:metadata":
            continue
        stat_names, events = {}, []
        for g, entry in _fields(plane):
            value = _first(entry, 2) if g in (4, 5) else None
            if g == 5 and value is not None:
                stat_names[_first(value, 1)] = bytes(_first(value, 2)
                                                     or b"").decode()
            elif g == 4 and value is not None:
                events.append(value)
        for ev in events:
            name = bytes(_first(ev, 2) or b"").decode()
            for h, stat in _fields(ev):
                if h == 5 and stat_names.get(_first(stat, 1)) == \
                        "Hlo Proto":
                    out[name] = _hlo_op_names(_first(stat, 6) or b"")
    return out


def _program(start, modules, starts) -> str:
    """The ``XLA Modules`` event (name, start, end) that holds an op
    event starting at ``start``; ``starts``: the modules' starts."""
    k = bisect.bisect_right(starts, start) - 1
    return modules[k][0] if k >= 0 and start <= modules[k][2] else ""


def attribute(ops, vocab) -> list[tuple[str, float, float]]:
    """``ops``: [(event name, start, end, program, op_name or None)] of
    one device. Returns [(scope or UNSCOPED, start, end)] of its leaf
    ops."""
    own, inherits, operands = {}, set(), {}
    consumers: dict = {}
    for name, _, _, prog, path in ops:
        op, args = _instruction(name)
        key = (prog, op)
        if key in own:
            continue
        own[key] = scope_of(path or "", vocab)
        if own[key] is None and (not path or LOOP.search(path)):
            inherits.add(key)
        operands[key] = [(prog, a) for a in args]
        for a in operands[key]:
            consumers.setdefault(a, []).append(key)
    memo: dict = {}

    def resolve(key):
        if key not in memo:
            memo[key] = own.get(key)          # also ends a cycle
            if key in inherits:
                memo[key] = next(
                    (s for s in map(resolve, operands[key]
                                    + consumers.get(key, []))
                     if s is not None), None)
        return memo[key]

    return [(resolve((prog, _instruction(name)[0])) or UNSCOPED, s, e)
            for name, s, e, prog, _ in ops
            if not devtrace.CONTAINER.search(name)]


def from_profile(pd, vocab, names) -> dict[str, float] | None:
    """Seconds per scope in the window, summed over the devices; None
    where no op carries a vocabulary scope (a program without them).
    ``names``: ``op_names`` of the same profile."""
    devices, window = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            modules = sorted(((e.name, e.start_ns, e.end_ns) for e in
                              (lines["XLA Modules"].events
                               if "XLA Modules" in lines else ())),
                             key=lambda m: m[1])
            starts = [m[1] for m in modules]
            ops = []
            for e in lines["XLA Ops"].events:
                prog = _program(e.start_ns, modules, starts)
                path = names.get(prog, {}).get(_instruction(e.name)[0])
                ops.append((e.name, e.start_ns, e.end_ns, prog, path))
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            window += [(e.start_ns, e.end_ns) for ln in plane.lines
                       for e in ln.events if e.name == devtrace.WINDOW]
    if not window or not vocab:
        return None
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    out: dict[str, float] = {}
    for ops in devices:
        for n, t in devtrace.time_by_name(
                attribute(ops, vocab), lo, hi).items():
            out[n] = out.get(n, 0.0) + t * 1e-9
    if not set(out) - {UNSCOPED}:
        return None
    return out


@functools.cache
def _load(path: str) -> dict[str, float] | None:
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    return from_profile(ProfileData.from_serialized_xspace(raw),
                        vocabulary(), op_names(raw))


def seconds() -> dict[str, float] | None:
    """Seconds per scope of the run's traced window (see module doc)."""
    d = Path(harness.load_module("drivers", "serve").PROFILE_DIR)
    files = sorted(d.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return _load(str(files[-1])) if files else None


def per_decode_step_ms(ctx, pick) -> float | None:
    """Device ms per decode step of the traced window, in the scopes
    ``pick(scope)`` selects, per chip (``decode_step_ms.decode``'s
    normalisation); None without a traced decode step or scopes."""
    c = ctx.get("trace_counters")
    if ctx.get("trace") is None or not c or not c["decode_steps"]:
        return None
    s = seconds()
    if s is None:
        return None
    t = sum(v for k, v in s.items() if pick(k))
    return 1e3 * t / ctx["chips"] / c["decode_steps"]


def is_attn(scope: str) -> bool:
    return scope == "attn" or scope.startswith("attn.")
