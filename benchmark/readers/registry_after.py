"""What a family of the program's registry holds AFTER the window, summed over
the series whose labels match ``where``: for what the program sets once, at
construction or at trace time, before the harness takes its first snapshot
(a gauge, a counter of lowerings, the seconds of a one-off span), where the
window's difference would read nothing. ``field`` is ``value`` (gauge,
counter) or ``sum`` (a histogram's seconds). Silent where the program has no
such family; where it has the family and no matching series, ``absent`` if
the metric gives one (a counter's healthy zero), else silent."""


def read(record: dict, params: dict):
    family = record["families_after"].get(params["family"])
    if family is None:
        return None
    names, where = family.get("labelnames", []), params.get("where", {})
    matching = [s for s in family.get("series", [])
                if all(dict(zip(names, s["labels"])).get(k) == v
                       for k, v in where.items())]
    if not matching:
        return params.get("absent")
    field = params.get("field", "value")
    return sum(s[field] for s in matching) * params.get("scale", 1.0)
