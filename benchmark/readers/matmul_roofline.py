"""Least time the chip could take over one bucket's matrix products (the
larger of FLOPs over the bf16 peak and bytes over the HBM peak, product by
product, ``work/``) over the device time of the operations that hold a
convolution or dot in one execution. Silent when no such operation ran."""


def read(record: dict, params: dict):
    trace, least = record.get("trace"), record.get("matmul_least_s")
    if not trace or not least or not trace["executions"]:
        return None
    runs = trace["executions"]
    spent = sum(r["matmul_s"] for r in runs) / len(runs)
    return 100.0 * least / spent if spent > 0 else None
