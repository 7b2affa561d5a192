"""One of the readings a traffic kind takes of its whole window beside
its end-to-end metrics (`sources["window_stats"]`; for the replay kind
`benchmark/replay_rate.window_stats`, from the walls of the window's
whole replays). spec: {"key": <field>}."""


def read(spec: dict, sources: dict) -> float | None:
    return (sources.get("window_stats") or {}).get(spec["key"])
