"""Compiles ``ProfiledJit`` recorded between the window's start and its end:
samples of ``smt_compile_seconds`` whose ``fn`` starts with the prefix."""


def _count(families: dict, prefix: str) -> int:
    family = families.get("smt_compile_seconds") or {}
    names = family.get("labelnames", [])
    return sum(int(s["count"]) for s in family.get("series", [])
               if dict(zip(names, s["labels"])).get("fn", "").startswith(prefix))


def read(record: dict, params: dict):
    prefix = params["fn_prefix"]
    return (_count(record["families_after"], prefix)
            - _count(record["families_before"], prefix))
