"""Host time the program spent inside its own spans during the window, a call.

The spans are ``synapseml_tpu.observability.spans``: each records its wall
time, on the host's monotonic clock where it runs, into the histogram family
``smt_stage_duration_seconds{stage,method,cold}`` of the registry that
``run.py`` snapshots before and after the window. A metric names the
``[stage, method]`` pairs to ``add``, those to ``subtract`` (a parent's self
time is its span less its children's), and the pair whose sample count is the
divisor (``per``). Silent when any pair has no sample in the window: a
program that lacks the span gives no number, not a wrong one."""

FAMILY = "smt_stage_duration_seconds"


def _totals(families: dict, pair) -> tuple:
    """(seconds, samples) of one ``[stage, method]``, summed over ``cold``."""
    family = families.get(FAMILY) or {}
    names = family.get("labelnames", [])
    seconds, samples = 0.0, 0
    for series in family.get("series", []):
        labels = dict(zip(names, series["labels"]))
        if [labels.get("stage"), labels.get("method")] == list(pair):
            seconds += series["sum"]
            samples += int(series["count"])
    return seconds, samples


def span_delta(record: dict, pair) -> tuple:
    """(seconds, samples) one pair gained between the two snapshots."""
    after = _totals(record["families_after"], pair)
    before = _totals(record["families_before"], pair)
    return after[0] - before[0], after[1] - before[1]


def read(record: dict, params: dict):
    calls = span_delta(record, params["per"])[1]
    if not calls:
        return None
    total = 0.0
    for sign, pairs in ((1.0, params["add"]), (-1.0, params.get("subtract", []))):
        for pair in pairs:
            seconds, samples = span_delta(record, pair)
            if not samples:
                return None
            total += sign * seconds
    return total / calls * 1e3
