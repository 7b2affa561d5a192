"""The metric arithmetic: the rate over the whole window, what the walls of
its replays say beside it (the rate by the median replay, the excess, the
drift), the bytes function of the roofline, the table of peaks, the generic
readers."""

import pytest

from benchmark import replay_rate, roofline
from benchmark.readers import counter, phase_wall, window_span, window_stat
from benchmark.readers import roofline as roofline_reader


def test_window_bytes_follow_from_the_wire_shapes():
    # 8192 lanes x (449 B body + 320 B depth-7 KES signature + 24 + 8)
    # + 8 bitmasks of 1024 B + the 64 B nonce carry
    assert roofline.window_bytes(8192, 449, 7) == \
        8192 * (449 + 320 + 32) + 8 * 1024 + 64
    assert roofline.window_bytes(8, 443, 7) == 8 * (443 + 352) + 8 + 64


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    assert roofline.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        roofline.peak("cpu", "hbm_bytes_per_s")


def test_roofline_share_and_its_silence():
    src = {"trace": {"stage_s": {"ed": 1.0, "vrf": 1.5},
                     "stage_runs": {"ed": 2, "vrf": 3}},
           "wire": {"lanes": 8192, "body_bytes": 449, "kes_depth": 7},
           "device_kind": "TPU v5 lite"}
    spec = {"bound": "hbm_bytes", "stages": ["ed", "vrf"]}
    want = roofline.window_bytes(8192, 449, 7) / 819e9 / 1.0 * 100
    assert roofline_reader.read(spec, src) == pytest.approx(want)
    assert 0 < want < 100
    # a stage with no whole run in the trace: the time would be short
    assert roofline_reader.read(dict(spec, stages=["ed", "vrf", "kes"]),
                                src) is None
    # nothing traced: nothing returned, never 0
    assert roofline_reader.read(spec, dict(src, trace=None)) is None
    assert roofline_reader.read(
        spec, dict(src, trace={"stage_s": {}, "stage_runs": {}})) is None


def test_rate_is_all_headers_over_the_whole_window():
    # traffic/replay.run: sum of n_valid of the whole replays completed,
    # over the wall from the window's open to the last replay's return
    n_valid, window_s = [43290, 43290, 43290], 21.5
    assert sum(n_valid) / window_s == pytest.approx(6040.465, rel=1e-6)


HEADERS = 42_500  # of one replay


def _sound(n=25):
    """Walls of a sound window: 1.17-1.28 s, in no order."""
    return [1.17 + 0.11 * ((i * 7) % n) / (n - 1) for i in range(n)]


def _window(walls, gaps_s=0.0):
    """(all headers, walls, the window's wall) of back-to-back replays."""
    return HEADERS * len(walls), walls, sum(walls) + gaps_s


def _whole(headers, walls, window_s):
    """The end-to-end rate, as `traffic/replay.run` takes it."""
    return headers / window_s


@pytest.mark.parametrize("stalls, whole_moves_pct, excess_s", [
    ((2.3,), 4.0, 0.044), ((2.3, 2.3), 7.0, 0.085)])
def test_a_stall_moves_the_rate_and_not_the_median_replays(
        stalls, whole_moves_pct, excess_s):
    """The end-to-end rate pays a stalled replay, as a user does; the
    per-layer readings say that it was a stall: the median replay's rate
    stays and the excess rises by what was planted."""
    sound = _window(_sound())
    walls = _sound()
    for i, w in enumerate(stalls):
        walls.insert(10 + 9 * i, w)
    stalled = _window(walls)
    a, b = (replay_rate.window_stats(*w) for w in (sound, stalled))
    assert (1 - _whole(*stalled) / _whole(*sound)) * 100 \
        == pytest.approx(whole_moves_pct, abs=1.0)
    assert abs(b["replay_median_headers_per_s"]
               / a["replay_median_headers_per_s"] - 1) < 0.005
    med = HEADERS / b["replay_median_headers_per_s"]
    assert b["replay_excess_s_per_replay"] == pytest.approx(
        (sum(walls) - len(walls) * med) / len(walls))
    assert b["replay_excess_s_per_replay"] == pytest.approx(excess_s,
                                                            abs=0.01)
    assert abs(a["replay_excess_s_per_replay"]) < 0.01


def test_a_linear_drift_reads_as_planted():
    walls = [1.2 * (1.05 - 0.05 * i / 23) for i in range(24)]
    # thirds of 8: medians at replays 3.5 and 19.5 of a 5% slope over 23
    want = ((1.05 - 0.05 * 3.5 / 23) / (1.05 - 0.05 * 19.5 / 23) - 1) * 100
    assert replay_rate.drift_pct(walls) == pytest.approx(want)
    assert 3.0 < want < 4.0
    assert replay_rate.drift_pct(walls[::-1]) < -3.0
    assert replay_rate.drift_pct([1.2] * 24) == 0.0


def test_gaps_between_replays_are_excess_too():
    w = _window([1.2] * 10, gaps_s=0.5)
    st = replay_rate.window_stats(*w)
    assert st["replay_excess_s_per_replay"] == pytest.approx(0.05)
    assert st["replay_median_headers_per_s"] == pytest.approx(HEADERS / 1.2)
    assert _whole(*w) == pytest.approx(HEADERS * 10 / 12.5)


@pytest.mark.parametrize("walls, median", [
    ([1.31], 1.31),  # one replay: that replay's rate
    ([1.2, 1.4], 1.3), ([1.2, 1.4, 1.3, 9.0], 1.35),  # an even count
    ([1.2, 1.4, 9.0], 1.4)])
def test_the_median_replays_rate_is_its_headers_over_the_median_wall(
        walls, median):
    st = replay_rate.window_stats(*_window(walls))
    assert st["replay_median_headers_per_s"] == pytest.approx(
        HEADERS / median)
    # under three replays there are no thirds: nothing, never 0
    assert (st["replay_drift_pct"] is None) == (len(walls) < 3)


def test_no_replay_reads_nothing():
    assert replay_rate.median_headers_per_s(0, []) is None
    assert replay_rate.excess_s_per_replay([], 30.0) is None
    assert replay_rate.drift_pct([]) is None


def test_the_window_stat_reader_reads_what_the_run_took():
    stats = replay_rate.window_stats(*_window(_sound() + [2.3], gaps_s=0.1))
    src = {"window_stats": stats}
    for key in stats:
        assert window_stat.read({"key": key}, src) == stats[key]
    # nothing to read: nothing, never 0
    assert window_stat.read({"key": "replay_drift_pct"}, {}) is None
    two = {"window_stats": replay_rate.window_stats(*_window([1.2, 1.3]))}
    assert window_stat.read({"key": "replay_drift_pct"}, two) is None
    assert window_stat.read({"key": "best_stretch"}, src) is None


def test_generic_readers():
    src = {"replays": 4, "phase_wall": {"stream": 2.0},
           "window_spans": [{"stage_s": 0.010}, {"stage_s": 0.030}],
           "counters": {"h2d_bytes": 1000, "headers": 10,
                        "lanes_live": 44, "lanes_padded": 100}}
    assert phase_wall.read({"key": "stream"}, src) == 0.5
    assert phase_wall.read({"key": "absent"}, src) is None
    assert window_span.read({"key": "stage_s", "scale": 1000}, src) == \
        pytest.approx(20.0)
    assert window_span.read({"key": "stage_s"}, {"window_spans": []}) is None
    assert counter.read({"numerator": "h2d_bytes",
                         "denominator": "headers"}, src) == 100
    assert counter.read({"numerator": "lanes_live", "scale": 100,
                         "denominator": "lanes_padded"}, src) == 44
    assert counter.read({"numerator": "x", "denominator": "headers"},
                        src) is None
