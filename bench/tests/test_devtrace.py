"""The trace reduction on a small synthetic trace: busy union, idle gaps
and the annotations over them, program and op time, and collective time
with the part no compute overlaps."""
import pytest
from jax.profiler import ProfileData

import devtrace

US = 1_000_000       # one microsecond in picoseconds


def _plane(pid, name, lines, names):
    meta = "".join(f' event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }}' for i, n in enumerate(names, 1))
    body = ""
    for lid, (lname, events) in enumerate(lines.items(), 1):
        evs = "".join(f" events {{ metadata_id: {names.index(n) + 1} "
                      f"offset_ps: {s * US} duration_ps: {d * US} }}"
                      for n, s, d in events)
        body += f' lines {{ id: {lid} name: "{lname}" timestamp_ns: 0{evs} }}'
    return f'planes {{ id: {pid} name: "{name}"{body}{meta} }}'


def _trace():
    """Window [10, 110) us. Device: a program over [10, 60) and [80, 100)
    whose ops are matmuls at [10, 30), [25, 40) and [80, 100) and an
    all-reduce at [35, 60), partly under the second matmul. Host:
    Engine.step over [40, 75) with fold over [58, 74) inside it,
    Engine.submit over [100, 106)."""
    dev = _plane(1, "/device:TPU:0", {
        "XLA Modules": [("jit_step(7)", 10, 50), ("jit_step(8)", 80, 20)],
        "XLA Ops": [("dot.1", 10, 20), ("dot.2", 25, 15),
                    ("all-reduce.1", 35, 25), ("dot.1", 80, 20)],
    }, ["jit_step(7)", "jit_step(8)", "dot.1", "dot.2", "all-reduce.1"])
    host = _plane(2, "/host:CPU", {
        "python": [("bench.window", 10, 100), ("Engine.step", 40, 35),
                   ("fold", 58, 16), ("Engine.submit", 100, 6)],
    }, ["bench.window", "Engine.step", "fold", "Engine.submit"])
    return devtrace.from_profile(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(dev + host)))


def test_intervals():
    assert devtrace.union([(5, 9), (0, 2), (1, 3), (9, 10)], 1, 9.5) == \
        [(1, 3), (5, 9.5)]
    assert devtrace.gaps([(1, 3), (5, 9)], 0, 10) == \
        [(0, 1), (3, 5), (9, 10)]
    assert devtrace.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == \
        [(0, 2), (4, 8), (22, 30)]


def test_summary():
    s = devtrace.summarize(_trace())
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(70e-6)      # [10, 60) + [80, 100)
    assert s.program_s == {"jit_step": pytest.approx(70e-6)}
    assert s.op_s["dot.1"] == pytest.approx(40e-6)
    assert s.collective_s == pytest.approx(25e-6)
    assert s.exposed_collective_s == pytest.approx(20e-6)   # [40, 60)
    # gap [60, 80): fold covers 14 of it, inside Engine.step's 15;
    # gap [100, 110): Engine.submit covers 6
    assert s.idle_gaps == [("fold", pytest.approx(20e-6)),
                           ("Engine.submit", pytest.approx(10e-6))]
    b = devtrace.breakdown(s)
    assert b["device_ops"][0] == ["dot.1", pytest.approx(40e-6)]
    assert len(b["idle_gaps"]) == 2


def test_no_window_annotation():
    tr = _trace()
    pd_text = _plane(1, "/device:TPU:0",
                     {"XLA Ops": [("dot.1", 0, 5)]}, ["dot.1"])
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(pd_text))
    with pytest.raises(ValueError):
        devtrace.from_profile(pd)
    assert tr.window == (10_000.0, 110_000.0)
