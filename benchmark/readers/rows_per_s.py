"""Every row of every call that ended in the window, over all of its wall
time (the call in flight at the deadline is finished and counted)."""


def read(record: dict, params: dict):
    if not record["calls"]:
        return None
    return sum(rows for _, _, rows in record["calls"]) / record["window_s"]
