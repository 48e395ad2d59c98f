"""Prefill programs' share of the device's busy time in the traced
window (``runtime/engine.py`` ``admit_batch``)."""
from chipbench import trace_reduce

UNIT = "%"


def read(record):
    trace = record.get("trace")
    if record.get("kind") != "serve" or trace is None:
        return None
    busy = trace_reduce.busy_s(trace)
    secs, _ = trace_reduce.program_time(trace, record["prefill_program"])
    return 100.0 * secs / busy if busy > 0 else None
