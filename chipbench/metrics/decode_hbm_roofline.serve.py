"""Decode step's share of its HBM roofline: the least bytes the decode
algorithm moves per step (``counts/decoder.py``: weights once, the
logical KV of the live rows) over the chip's HBM bandwidth, against the
decode program's mean device time per run in the trace. The steps
counted are all those of the traced run (the window and its drain)."""
from chipbench import trace_reduce
from chipbench.counts import decoder

UNIT = "%"


def read(record):
    trace = record.get("trace")
    if record.get("kind") != "serve" or trace is None:
        return None
    secs, runs = trace_reduce.program_time(trace, record["decode_program"])
    steps = record["steps"]
    if not runs or not steps:
        return None
    mean_bytes = sum(decoder.decode_step_bytes(record["counts"], r, c)
                     for _, _, r, c in steps) / len(steps)
    least_s = mean_bytes / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (secs / runs)
