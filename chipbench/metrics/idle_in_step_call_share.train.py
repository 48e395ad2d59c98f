"""Share of the traced window in which the first chip is idle while the
innermost host span open is the step call (the harness's ``step`` span
around ``PSLStrategy.step``), in a training cell: the part of
``device_idle_share.train`` that the host spends inside the jitted
call before the chip gets its work."""
from chipbench import program_trace, trace_reduce

UNIT = "%"


def read(record):
    trace = record.get("trace")
    if record.get("kind") != "train" or trace is None or not trace.devices \
            or not any(name == "step" for name, _, _ in trace.host):
        return None
    lo, hi = trace.window
    idle = program_trace.idle_by_span(
        trace_reduce.busy(trace.devices[0], trace.window), trace.host,
        trace.window)
    return 100.0 * idle.get("step", 0.0) / (hi - lo)
