"""Model FLOPs utilization of serving: the operations of every prefill
and decode token processed in the window (``counts/decoder.py``), over
the window and the chips' bf16 peak."""
from chipbench.counts import decoder

UNIT = "%"


def read(record):
    if record.get("kind") != "serve":
        return None
    c = record["counts"]
    end = record["window_end"]
    flops = sum(decoder.decode_step_flops(c, r, ctx)
                for _, t1, r, ctx in record["steps"] if t1 <= end)
    flops += sum(decoder.prefill_flops(c, n, pairs)
                 for _, t1, n, pairs in record["prefills"] if t1 <= end)
    peak = record["chips"] * record["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (end * peak)
