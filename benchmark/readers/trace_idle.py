"""The device's idle share of the traced window, in per cent:
1 - (union of the device's operation intervals / traced window)."""


def read(spec: dict, sources: dict) -> float | None:
    tr = sources.get("trace")
    if not tr or not tr["window_s"] or not tr["busy_s"]:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
