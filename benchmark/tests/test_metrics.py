"""The metric arithmetic: the rate over the whole window, the bytes function
of the roofline, the table of peaks, the generic readers."""

import pytest

from benchmark import roofline
from benchmark.readers import counter, phase_wall, window_span
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
