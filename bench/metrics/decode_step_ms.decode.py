"""Device time per on-device decode step over the traced part of the
window: the time of every program the device ran there (in a serving
window, the engine's decode slabs and mixed decode+prefill steps), over
the decode steps the engine counted there. A trace in which the device
ran no program is an error."""
import harness


def read(ctx):
    s, c = ctx.get("trace"), ctx.get("trace_counters")
    if s is None or not c or not c["decode_steps"]:
        return None
    t = sum(s.program_s.values())
    if t <= 0:
        raise harness.BenchError("the traced window holds no device "
                                 "program to time the decode step by")
    return 1e3 * t / ctx["chips"] / c["decode_steps"]
