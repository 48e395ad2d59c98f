"""XLA compilations (or compile-cache retrievals) from the window's start
to the end of its drain: each one is a stall a request pays for. It
should read 0."""
UNIT = "count"


def read(record):
    if record.get("kind") != "serve":
        return None
    return record["compiles"]
