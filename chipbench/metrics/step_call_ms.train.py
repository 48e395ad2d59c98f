"""Host time of one step call (``PSLStrategy.step``: the jitted fused
step from entry until it returns, the call the program's own ``step``
span times): the harness's span around it, mean per step in the
window."""
UNIT = "ms"


def read(record):
    if record.get("kind") != "train" or "step" not in record["spans"]:
        return None
    return 1e3 * record["spans"]["step"] / record["span_counts"]["step"]
