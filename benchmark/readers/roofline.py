"""A share of a roofline, in per cent: the least time the chip could take
for the bytes (or operations) the work needs, over the device time the
stages took. spec: {"bound": "hbm_bytes", "stages": [...]}.

Only the bytes bound exists today: the stages are int32 limb arithmetic on
the vector unit, for which no peak is published (benchmark/peaks.json)."""

from benchmark import roofline


def read(spec: dict, sources: dict) -> float | None:
    tr = sources.get("trace")
    wire = sources.get("wire")
    if not tr or not wire:
        return None
    # one window's device time: each stage's mean run, summed. A stage
    # with no whole run in the traced stretch leaves the time short, and
    # the share would read too high: then nothing is returned
    if not all(tr["stage_runs"].get(s) for s in spec["stages"]):
        return None
    window_s = sum(tr["stage_s"][s] / tr["stage_runs"][s]
                   for s in spec["stages"])
    if window_s <= 0:
        return None
    if spec["bound"] != "hbm_bytes":
        raise ValueError(f"no peak for bound {spec['bound']!r}")
    peak = roofline.peak(sources["device_kind"], "hbm_bytes_per_s")
    return roofline.window_bytes(**wire) / peak / window_s * 100.0
