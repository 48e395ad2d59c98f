"""Time a request waited before the scheduler started its prefill
(``runtime/scheduler.py``): admission start minus due time, from the
engine's records, 90th percentile over the requests due in the window."""
import numpy as np

UNIT = "ms"


def read(record):
    waits = record.get("queue_wait_ms")
    if record.get("kind") != "serve" or not waits:
        return None
    return float(np.percentile(waits, 90))
