"""Device time of one PSL step (``core/psl.py`` fused step): the step
program's runs in the profiler trace, mean per run."""
from chipbench import trace_reduce

UNIT = "ms"


def read(record):
    trace = record.get("trace")
    if record.get("kind") != "train" or trace is None:
        return None
    secs, runs = trace_reduce.program_time(trace, record["step_program"])
    return 1e3 * secs / runs if runs else None
