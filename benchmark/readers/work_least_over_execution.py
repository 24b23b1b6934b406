"""A roofline share of the whole call: the least time the chip could take
over one call of the cell's bucket, as a function of ``work/<family>.py``
named by the metric reckons it from shapes (``params.function``, called with
the configuration, the traffic's ``dims``, the bucket and the chip's peaks;
its ``seconds``), over the device time of one traced execution of the
program, mean over the traced executions. Silent where there is no trace, no
peak, or the work module has no such function."""

import importlib
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def read(record: dict, params: dict):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not peaks or not trace["executions"]:
        return None
    try:
        cell = _load("workloads", params["workload"] + ".json")
        config = _load("configs", cell["config"] + ".json")
        work = importlib.import_module("benchmark.work." + config["work"])
        least = getattr(work, params["function"])(
            config, cell["traffic"].get("dims", {}), record["bucket"], peaks)
    except (OSError, KeyError, ImportError, AttributeError):
        return None
    runs = trace["executions"]
    spent = sum(r["busy_s"] for r in runs) / len(runs)
    return 100.0 * least["seconds"] / spent if spent > 0 else None
