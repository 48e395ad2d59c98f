"""Model FLOPs utilization of training: the forward and backward
operations of a sample (``counts/``, nothing recomputed counted) times
the samples per second of the window, over the chips' bf16 peak."""
UNIT = "%"


def read(record):
    if record.get("kind") != "train":
        return None
    peak = record["chips"] * record["peaks"]["bf16_flops_per_s"]
    return 100.0 * record["flops_per_sample"] * record["samples_per_s"] \
        / peak
