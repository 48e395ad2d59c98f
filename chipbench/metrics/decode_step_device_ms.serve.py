"""Device time of one paged decode step (``runtime/paging.py``,
``models/transformer.py`` ``decode_step_paged``): the decode program's
runs in the profiler trace, mean per run."""
from chipbench import trace_reduce

UNIT = "ms"


def read(record):
    trace = record.get("trace")
    if record.get("kind") != "serve" or trace is None:
        return None
    secs, runs = trace_reduce.program_time(trace, record["decode_program"])
    return 1e3 * secs / runs if runs else None
