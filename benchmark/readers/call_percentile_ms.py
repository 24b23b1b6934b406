"""A percentile of the wall time of all calls of the window, each timed to
the returned numpy columns."""

import numpy as np


def read(record: dict, params: dict):
    if not record["calls"]:
        return None
    walls = [end - start for start, end, _ in record["calls"]]
    return float(np.percentile(walls, params["percentile"])) * 1e3
