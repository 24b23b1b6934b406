"""Model FLOPs of the rows the traced calls returned, over the traced wall
time and the chip's bf16 peak. The FLOPs are the benchmark's own count from
the shapes (``work/``), so work the executor adds or saves does not move it."""


def read(record: dict, params: dict):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not peaks or not trace["window_s"]:
        return None
    rows = sum(r for _, _, r in record["calls"][: trace["calls"]])
    flops = rows * record["flops_per_row"]
    return 100.0 * flops / (trace["window_s"] * trace["devices"]
                            * peaks["bf16_flops_per_s"])
