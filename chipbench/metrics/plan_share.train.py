"""Share of the window the host spent in the epoch planner
(``core/sampling.py``, ``core/planner.py``): the harness's span around
``plan_epoch``, over the window. It tests the GPSL paper's claim that
global sampling adds a negligible overhead."""
UNIT = "%"


def read(record):
    if record.get("kind") != "train" or "plan" not in record["spans"]:
        return None
    return 100.0 * record["spans"]["plan"] / record["window_s"]
