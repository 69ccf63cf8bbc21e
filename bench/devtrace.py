"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read: busy intervals and idle gaps of each device,
device time per program and per op, collective time and the part of it
that no compute overlaps, and the host annotations around each gap.

Device planes are ``/device:TPU:<n>``; on each, the line ``XLA Ops``
holds one event per executed HLO op and ``XLA Modules`` one per program
run. Host planes hold the harness's ``TraceAnnotation`` spans; the one
named ``WINDOW`` bounds the traced window. Times are in nanoseconds on
the trace's one clock.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

WINDOW = "bench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|psum", re.I)


@dataclasses.dataclass
class Device:
    ops: list[tuple[str, float, float]]       # (name, start, end)
    modules: list[tuple[str, float, float]]


@dataclasses.dataclass
class Trace:
    devices: dict[str, Device]
    host: list[tuple[str, float, float]]      # annotations on host planes
    window: tuple[float, float]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def program_name(name: str) -> str:
    """A program's name without the run id the trace appends."""
    return re.sub(r"\(\d+\)$", "", name)


def from_profile(pd) -> Trace:
    """``pd``: a ``jax.profiler.ProfileData``."""
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
            mods = (_events(lines["XLA Modules"])
                    if "XLA Modules" in lines else [])
            if ops or mods:
                devices[plane.name] = Device(ops, mods)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += _events(ln)
    spans = [(s, e) for n, s, e in host if n == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    window = (min(s for s, _ in spans), max(e for _, e in spans))
    return Trace(devices, host, window)


def load(profile_dir: Path) -> Trace:
    """The newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])))


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged (start, end) intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of merged ``busy`` within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def subtract(a, b) -> list[tuple[float, float]]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        t = s
        while j < len(b) and b[j][1] <= t:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def busy_intervals(dev: Device, lo: float, hi: float):
    return union([(s, e) for _, s, e in (dev.ops or dev.modules)], lo, hi)


def collective_exposed(dev: Device, lo: float, hi: float
                       ) -> tuple[float, float]:
    """(collective op time, the part of it during which no other op runs
    on this device), both in ns within [lo, hi]."""
    coll = union([(s, e) for n, s, e in dev.ops if COLLECTIVE.search(n)],
                 lo, hi)
    comp = union([(s, e) for n, s, e in dev.ops
                  if not COLLECTIVE.search(n)], lo, hi)
    return total(coll), total(subtract(coll, comp))


def op_name(name: str) -> str:
    """An HLO op event's name without its layouts, cut to 120 characters
    (the trace names an op by its whole instruction text)."""
    return re.sub(r"\{[^{}]*\}", "", name)[:120]


CONTAINER = re.compile(r"\s(while|conditional|call)\(")


def time_by_name(events, lo: float, hi: float, key=lambda n: n
                 ) -> dict[str, float]:
    """ns per (keyed) event name, clipped to [lo, hi]."""
    out: dict[str, float] = {}
    for n, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out[key(n)] = out.get(key(n), 0.0) + d
    return out


def gap_causes(gap_list, host, top: int = 10):
    """The ``top`` longest gaps, each named by what the host was doing:
    the shortest host span that covers half of the gap or more (the most
    specific of nested spans), else the span that overlaps it most,
    else ``"(no annotation)"``. [(name, seconds)]."""
    out = []
    for s, e in sorted(gap_list, key=lambda g: g[0] - g[1])[:top]:
        best, cover, inner = "(no annotation)", 0.0, None
        for n, hs, he in host:
            if n == WINDOW:
                continue
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = n, c
            if 2 * c >= e - s and (inner is None or he - hs < inner[1]):
                inner = (n, he - hs)
        out.append((inner[0] if inner else best, (e - s) * 1e-9))
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over the devices
    program_s: dict[str, float]         # summed over the devices
    op_s: dict[str, float]              # ops that hold no other ops
    collective_s: float                 # mean over the devices
    exposed_collective_s: float
    idle_gaps: list[tuple[str, float]]  # of the first device


def summarize(tr: Trace) -> Summary:
    lo, hi = tr.window
    if not tr.devices:
        raise ValueError("no device plane with ops in the trace")
    busy, prog, ops, coll, exp = [], {}, {}, [], []
    first_gaps = None
    for dev in tr.devices.values():
        b = busy_intervals(dev, lo, hi)
        busy.append(total(b))
        if first_gaps is None:
            first_gaps = gaps(b, lo, hi)
        leaf_ops = [ev for ev in dev.ops if not CONTAINER.search(ev[0])]
        for src, dst, key in ((dev.modules, prog, program_name),
                              (leaf_ops, ops, op_name)):
            for n, t in time_by_name(src, lo, hi, key).items():
                dst[n] = dst.get(n, 0.0) + t * 1e-9
        c, x = collective_exposed(dev, lo, hi)
        coll.append(c)
        exp.append(x)
    n = len(tr.devices)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / n * 1e-9,
                   program_s=prog, op_s=ops,
                   collective_s=sum(coll) / n * 1e-9,
                   exposed_collective_s=sum(exp) / n * 1e-9,
                   idle_gaps=gap_causes(first_gaps, tr.host))


def breakdown(s: Summary, top: int = 10) -> dict:
    ops = sorted(s.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in s.idle_gaps[:top]]}
