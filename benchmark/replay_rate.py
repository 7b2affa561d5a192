"""What the walls of a window's replays say beside the end-to-end rate.

A window is whole replays of one chain, back to back, and its end-to-end
rate is all its headers over all its wall (`traffic/replay.run`): a
replay that stalls and a window that is still warming up are in it, as
they are in a user's wall time. These readings of the same walls say
which of the two moved it. The rate by the MEDIAN replay is what the
window would read if every replay took the median wall; a stall in one
replay does not move it. The excess is the wall the window spent over
`replays x median` (a stall, the gaps between replays), and the drift
how much slower its first third ran than its last. No replay is trimmed,
dropped or chosen. All are pure functions of the walls.
"""

from __future__ import annotations

import statistics


def median_headers_per_s(headers: int, walls) -> float | None:
    """All `headers` of the window's `len(walls)` replays, a replay's
    share of them over the median wall. One replay gives its own rate."""
    if not walls:
        return None
    return headers / len(walls) / statistics.median(walls)


def excess_s_per_replay(walls, window_s: float) -> float | None:
    """(window wall - replays x median wall) / replays: what the whole
    window took beyond what the median replay accounts for."""
    if not walls:
        return None
    return (window_s - len(walls) * statistics.median(walls)) / len(walls)


def drift_pct(walls) -> float | None:
    """Median wall of the first third of the replays over that of the
    last third, less one, in per cent. Under three replays there are no
    thirds, and nothing is returned."""
    third = len(walls) // 3
    if not third:
        return None
    return (statistics.median(walls[:third])
            / statistics.median(walls[-third:]) - 1.0) * 100.0


def window_stats(headers: int, walls, window_s: float) -> dict:
    """The per-layer readings of the walls, by metric name (reader
    `window_stat`)."""
    return {
        "replay_median_headers_per_s": median_headers_per_s(headers, walls),
        "replay_excess_s_per_replay": excess_s_per_replay(walls, window_s),
        "replay_drift_pct": drift_pct(walls),
    }
