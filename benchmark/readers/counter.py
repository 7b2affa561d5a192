"""A ratio of two exact counts of the run.
spec: {"numerator": <counter>, "denominator": <counter>, "scale"}."""


def read(spec: dict, sources: dict) -> float | None:
    c = sources.get("counters", {})
    num, den = c.get(spec["numerator"]), c.get(spec["denominator"])
    if num is None or not den:
        return None
    return num / den * spec.get("scale", 1)
