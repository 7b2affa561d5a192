"""Device time of one run of a stage's XLA module, from the profiler trace:
the sum of the durations of the stage's module events that lie wholly inside
the traced stretch, divided by their number (one run a window). The data
file benchmark/trace_modules.json maps module names to stages.
spec: {"stage": <stage>, "scale"}."""


def read(spec: dict, sources: dict) -> float | None:
    tr = sources.get("trace")
    if not tr or not tr["stage_runs"].get(spec["stage"]):
        return None
    return (tr["stage_s"][spec["stage"]] / tr["stage_runs"][spec["stage"]]
            * spec.get("scale", 1))
