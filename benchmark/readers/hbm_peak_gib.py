"""Peak HBM held on the fullest device after the window and before the
reference runs (``run.memory_peak_bytes``). Silent where the backend reports
no memory statistics."""


def read(record: dict, params: dict):
    peak = record.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
