"""Device-idle time inside the benchmark's call annotations, a call: what
the chip waits for the host inside one ``transform``."""


def read(record: dict, params: dict):
    trace = record.get("trace")
    if not trace or not trace["call_idle_s"]:
        return None
    return sum(trace["call_idle_s"]) / len(trace["call_idle_s"]) * 1e3
