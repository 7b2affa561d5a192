"""Wall seconds of one of the program's enclosing phases (`res.phases` of
`revalidate(collect_phases=True)`), per replay. spec: {"key": <phase>}."""


def read(spec: dict, sources: dict) -> float | None:
    total = sources.get("phase_wall", {}).get(spec["key"])
    n = sources.get("replays")
    if total is None or not n:
        return None
    return total / n * spec.get("scale", 1)
