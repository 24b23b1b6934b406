"""Seconds of the set-up by the program's own spans, as the registry holds
them AFTER the window: the phases of a set-up run once a process, before the
harness takes its first snapshot, so the window's difference reads nothing.

A metric names the spans to ``add`` and those to ``subtract``, each as
``[stage, method]`` (both values of ``cold`` summed) or ``[stage, method,
cold]`` (``"1"``: the samples of a stage instance's first call, and of the
phases inside it); with ``"from": "setup_s"`` the sum starts from the
harness's own ``setup_s`` (process start to the first timed call) and not
from zero, which is how what lies OUTSIDE the program's spans gets a number.
Silent when any named span has no sample: a program that lacks a span gives
no number, not a wrong one."""

FAMILY = "smt_stage_duration_seconds"


def _seconds(families: dict, span) -> tuple:
    """(seconds, samples) of one ``[stage, method]`` or ``[stage, method,
    cold]`` in a snapshot."""
    family = families.get(FAMILY) or {}
    names = family.get("labelnames", [])
    want = dict(zip(("stage", "method", "cold"), span))
    seconds, samples = 0.0, 0
    for series in family.get("series", []):
        labels = dict(zip(names, series["labels"]))
        if all(labels.get(k) == v for k, v in want.items()):
            seconds += series["sum"]
            samples += int(series["count"])
    return seconds, samples


def read(record: dict, params: dict):
    total = record[params["from"]] if "from" in params else 0.0
    for sign, spans in ((1.0, params.get("add", [])),
                        (-1.0, params.get("subtract", []))):
        for span in spans:
            seconds, samples = _seconds(record["families_after"], span)
            if not samples:
                return None
            total += sign * seconds
    return total
