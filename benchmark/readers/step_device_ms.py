"""Union of the device-op intervals of one execution of the configuration's
program, mean over the traced executions."""


def read(record: dict, params: dict):
    trace = record.get("trace")
    if not trace or not trace["executions"]:
        return None
    runs = trace["executions"]
    return sum(r["busy_s"] for r in runs) / len(runs) * 1e3
