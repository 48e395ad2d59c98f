"""Share of the traced window in which no operation ran on the chip
(averaged over the chips), in a serving cell: where the host's round
trip per decode step shows."""
from chipbench import trace_reduce

UNIT = "%"


def read(record):
    trace = record.get("trace")
    if record.get("kind") != "serve" or trace is None \
            or not trace.devices:
        return None
    return 100.0 * trace_reduce.idle_share(trace, trace.window)
