"""What one of the program's counters gained during the window, a call: the
family's value after the window minus before (all its series), times
``scale``, over the samples the ``per`` span gained (``span_ms_per_call``).
Silent where the program has no such counter or no such span."""

from benchmark.readers.span_ms_per_call import span_delta


def _value(families: dict, name: str):
    family = families.get(name)
    if family is None:
        return None
    return sum(s["value"] for s in family.get("series", []))


def read(record: dict, params: dict):
    calls = span_delta(record, params["per"])[1]
    after = _value(record["families_after"], params["family"])
    if not calls or after is None:
        return None
    before = _value(record["families_before"], params["family"]) or 0.0
    return (after - before) * params.get("scale", 1.0) / calls
