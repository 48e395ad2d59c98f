"""Host time to assemble one global batch (``data/federated.py``
``GlobalBatchIterator``, then the host-to-device put): the harness's
span around the iterator's ``next``, mean per step in the window."""
UNIT = "ms"


def read(record):
    if record.get("kind") != "train" or "batch" not in record["spans"]:
        return None
    return 1e3 * record["spans"]["batch"] / record["span_counts"]["batch"]
