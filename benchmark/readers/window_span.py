"""Mean of one field of the program's per-window spans (obs `WindowSpan`)
over every window of the timed replays. spec: {"key": <field>, "scale"}."""


def read(spec: dict, sources: dict) -> float | None:
    spans = sources.get("window_spans") or []
    vals = [s[spec["key"]] for s in spans if spec["key"] in s]
    if not vals:
        return None
    return sum(vals) / len(vals) * spec.get("scale", 1)
