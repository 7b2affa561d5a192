"""Device resource accounting (obs/resources.py): extraction units,
the runtime capture hooks (ops/pk/kernels._stage_call and the
protocol/batch _warm_timed wrapper), the oct_stage_* gauge mirroring,
the OCT_STAGE_RESOURCES lever, and the budgets.json "device_resources"
ratchet — pin coverage of the whole registry, hash-consistency with
costmodel.json, and the check/update dict logic."""

from __future__ import annotations

import json

import numpy as np
import pytest

import jax
from jax import numpy as jnp

from ouroboros_consensus_tpu import obs
from ouroboros_consensus_tpu.analysis import costmodel, graphs
from ouroboros_consensus_tpu.obs import resources as R
from ouroboros_consensus_tpu.obs.registry import MetricsRegistry


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    obs.reset_for_tests()
    R.RESOURCES.reset()
    yield
    R.RESOURCES.reset()
    obs.reset_for_tests()


# ---------------------------------------------------------------------------
# extraction units
# ---------------------------------------------------------------------------


def test_from_cost_analysis_handles_dict_list_none():
    assert R.from_cost_analysis(None) == {}
    assert R.from_cost_analysis([]) == {}
    d = {"flops": 12.0, "bytes accessed": 34.0, "utilization0{}": 1.0}
    assert R.from_cost_analysis(d) == {"flops": 12, "bytes_accessed": 34}
    # Compiled returns a per-partition list on this jax
    assert R.from_cost_analysis([d]) == {"flops": 12, "bytes_accessed": 34}


def test_from_memory_analysis_computes_peak():
    class Stats:
        argument_size_in_bytes = 100
        output_size_in_bytes = 20
        temp_size_in_bytes = 300
        generated_code_size_in_bytes = 7

    out = R.from_memory_analysis(Stats())
    assert out["peak_hbm_bytes"] == 427
    assert out["argument_bytes"] == 100
    assert R.from_memory_analysis(None) == {}


def test_from_lowered_and_compiled_real_program():
    lo = jax.jit(lambda x: jnp.dot(x, x) + 1).lower(
        jnp.ones((16, 16), jnp.float32)
    )
    res = R.from_lowered(lo)
    assert res and res["flops"] > 0
    co = lo.compile()
    full = R.from_compiled(co)
    assert full and full["flops"] > 0
    assert "peak_hbm_bytes" in full and full["peak_hbm_bytes"] > 0
    assert full["argument_bytes"] == 16 * 16 * 4


# ---------------------------------------------------------------------------
# recorder + gauges + lever
# ---------------------------------------------------------------------------


def test_note_stage_first_wins_and_mirrors_gauges():
    from ouroboros_consensus_tpu.obs.registry import default_registry

    ok = R.RESOURCES.note_stage(
        "ed@b8", 8, 7,
        {"flops": 100, "bytes_accessed": 200, "peak_hbm_bytes": 50,
         "argument_bytes": 30, "output_bytes": 10, "temp_bytes": 10},
        via="jit",
    )
    assert ok
    # second note for the same (stage, lanes, depth) is dropped
    assert not R.RESOURCES.note_stage("ed@b8", 8, 7, {"flops": 999})
    rep = R.RESOURCES.report()
    (key,) = rep
    assert key == "ed@b8|8|7"
    assert rep[key]["flops"] == 100
    json.dumps(rep)  # ledger/bench bankable
    snap = default_registry().snapshot()
    assert snap["oct_stage_flops"]["samples"][0]["labels"] == {
        "stage": "ed@b8"
    }
    assert snap["oct_stage_flops"]["samples"][0]["value"] == 100
    kinds = {
        s["labels"]["kind"]: s["value"]
        for s in snap["oct_stage_hbm_bytes"]["samples"]
    }
    assert kinds == {"argument": 30, "output": 10, "temp": 10, "peak": 50}


def test_capture_lever(monkeypatch):
    fn = jax.jit(lambda x: x + 1)
    args = (jnp.zeros((4,), jnp.int32),)
    monkeypatch.setenv("OCT_STAGE_RESOURCES", "0")
    assert not R.capture_stage("lever@b4", fn, args, lanes=4)
    assert R.RESOURCES.report() == {}
    monkeypatch.setenv("OCT_STAGE_RESOURCES", "1")
    assert R.capture_stage("lever@b4", fn, args, lanes=4)
    assert "lever@b4|4|None" in R.RESOURCES.report()
    # unset: off, and installing the recorder does not turn it on
    # (capture re-lowers every stage: tracing must not change set-up)
    monkeypatch.delenv("OCT_STAGE_RESOURCES")
    R.RESOURCES.reset()
    assert not R.enabled()
    obs.install()
    try:
        assert not R.enabled()
    finally:
        obs.uninstall()
    assert not R.enabled()


def test_stage_call_captures_on_first_execute(monkeypatch):
    """The ops/pk dispatch hook: one capture per (stage, bucket), on
    the jit path, riding the warmup first-execute gate."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP
    from ouroboros_consensus_tpu.ops.pk import kernels

    monkeypatch.setenv("OCT_PK_AOT", "0")
    monkeypatch.setenv("OCT_STAGE_RESOURCES", "1")
    WARMUP.reset()
    kernels._FIRST_EXEC.discard("restest@b4")
    fn = jax.jit(lambda x: x * 2)
    kernels._stage_call("restest", fn, 4, 3, jnp.ones((2, 4), jnp.int32))
    kernels._stage_call("restest", fn, 4, 3, jnp.ones((2, 4), jnp.int32))
    rep = R.RESOURCES.report()
    (key,) = [k for k in rep if k.startswith("restest@b4")]
    assert key == "restest@b4|4|3"
    assert rep[key]["via"] == "jit"
    assert rep[key]["bytes_accessed"] > 0
    kernels._FIRST_EXEC.discard("restest@b4")


def test_warm_timed_captures_xla_twin(monkeypatch):
    """The protocol/batch XLA-twin hook: _warm_timed wraps the jit, the
    first call records both the warmup wall AND the resources, with
    lanes read off the leading batch axis. Since round 10 the
    first-execute label is LANE-QUALIFIED (`<stage>:<lanes>l`) — the
    warm ladder runs the same program family at rung and production
    lane counts, and each shape's compile attributes separately."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP
    from ouroboros_consensus_tpu.protocol import batch as pbatch

    monkeypatch.setenv("OCT_STAGE_RESOURCES", "1")
    WARMUP.reset()
    pbatch._WARM_SEEN.discard("restest-twin:6l")
    try:
        wrapped = pbatch._warm_timed("restest-twin",
                                     jax.jit(lambda x: x.sum(axis=1)))
        wrapped(np.ones((6, 3), np.float32))
        wrapped(np.ones((6, 3), np.float32))
        rep = R.RESOURCES.report()
        assert "restest-twin:6l|6|None" in rep
        assert rep["restest-twin:6l|6|None"]["via"] == "xla-jit"
        assert "restest-twin:6l" in WARMUP.report()["stages"]
        # a DIFFERENT lane count is a separate first execute
        wrapped(np.ones((4, 3), np.float32))
        assert "restest-twin:4l" in WARMUP.report()["stages"]
    finally:
        pbatch._WARM_SEEN.discard("restest-twin:6l")
        pbatch._WARM_SEEN.discard("restest-twin:4l")
        WARMUP.reset()


def test_capture_never_raises(monkeypatch):
    monkeypatch.setenv("OCT_STAGE_RESOURCES", "1")

    class Broken:
        def lower(self, *a):
            raise RuntimeError("boom")

    assert not R.capture_stage("broken@b1", Broken(), (), lanes=1)


def test_capture_rows_carry_their_own_cost(monkeypatch):
    """Telemetry is accountable: every captured row records what the
    capture itself cost (capture_s), so a warmup wall burned on the
    re-trace is attributed, never mysterious."""
    monkeypatch.setenv("OCT_STAGE_RESOURCES", "1")
    fn = jax.jit(lambda x: x + 1)
    assert R.capture_stage("acct@b4", fn, (jnp.zeros((4,), jnp.int32),),
                           lanes=4)
    row = R.RESOURCES.report()["acct@b4|4|None"]
    assert "capture_s" in row and row["capture_s"] >= 0.0


# ---------------------------------------------------------------------------
# static measurement + the ratchet
# ---------------------------------------------------------------------------


def test_measure_graph_small_no_compile():
    res = graphs.measure_graph("verdict_reduce", 8, compile=False)
    assert res["flops"] > 0 and res["bytes_accessed"] > 0
    assert res["source"] == "lowered"
    assert res["at_lanes"] == 8
    assert "peak_hbm_bytes" not in res  # memory stats need the compile


class _Feat:
    def __init__(self, name, h):
        self.name = name
        self._h = h

    def hash(self):
        return self._h


def _budgets_with(pin_hash="h1", flops=100):
    return {
        "device_resources": {
            "graphs": {
                "g": {"feature_hash": pin_hash, "flops": flops,
                      "bytes_accessed": 10, "peak_hbm_bytes": 5,
                      "at_lanes": 2},
            },
            "ceilings": {
                "g": {"flops_max": 120, "bytes_accessed_max": 12,
                      "peak_hbm_bytes_max": 6},
            },
        }
    }


def test_check_device_resources_dict_logic():
    feats = [_Feat("g", "h1")]
    assert R.check_device_resources(feats, _budgets_with()) == []
    # missing pin
    v = R.check_device_resources([_Feat("other", "x")], _budgets_with())
    assert v and "no device_resources pin" in v[0]
    # stale structure fails loudly BEFORE any ceiling compare
    v = R.check_device_resources([_Feat("g", "DRIFTED")], _budgets_with())
    assert v and "drifted" in v[0]
    # pinned value over its ceiling
    v = R.check_device_resources(feats, _budgets_with(flops=121))
    assert v and "exceeds ceiling" in v[0]


def test_update_budgets_section_preserves_existing_ceilings():
    budgets = _budgets_with()
    meas = {"g": {"flops": 110, "bytes_accessed": 11, "peak_hbm_bytes": 6,
                  "at_lanes": 2, "source": "compiled"}}
    R.update_budgets_section(budgets, meas, {"g": "h2"}, measured_at="t")
    sec = budgets["device_resources"]
    assert sec["graphs"]["g"]["feature_hash"] == "h2"
    assert sec["graphs"]["g"]["flops"] == 110
    # the OLD ceiling survives the update — that asymmetry IS the
    # ratchet (a grown program trips it until raised on purpose)
    assert sec["ceilings"]["g"]["flops_max"] == 120
    # a brand-new graph gets a fresh ceiling at the headroom factor
    meas["g2"] = {"flops": 100, "bytes_accessed": 10,
                  "peak_hbm_bytes": 10, "at_lanes": 4,
                  "source": "compiled"}
    R.update_budgets_section(budgets, meas, {"g": "h2", "g2": "h9"})
    assert sec["ceilings"]["g2"]["flops_max"] == int(
        100 * R.CEILING_HEADROOM
    )
    # dropping a graph from the measurements drops its ceiling too
    del meas["g"]
    R.update_budgets_section(budgets, meas, {"g2": "h9"})
    assert "g" not in sec["ceilings"] and "g" not in sec["graphs"]


# ---------------------------------------------------------------------------
# the shipped pins (budgets.json) — coverage + hash consistency
# ---------------------------------------------------------------------------


def test_shipped_pins_cover_every_registry_graph():
    sec = graphs.load_budgets().get("device_resources", {})
    pins = sec.get("graphs", {})
    assert set(pins) == set(graphs.registered_graphs()), (
        "every registry stage must carry a device_resources pin "
        "(run scripts/lint.py --update-resources)"
    )
    for name, pin in pins.items():
        for key in ("flops", "bytes_accessed", "peak_hbm_bytes"):
            assert isinstance(pin.get(key), int) and pin[key] >= 0, (
                f"{name}: pin missing {key}"
            )
        assert pin.get("feature_hash"), f"{name}: pin missing its hash key"
    ceilings = sec.get("ceilings", {})
    for name, pin in pins.items():
        ceil = ceilings.get(name, {})
        for key in R.CEILING_KEYS:
            cmax = ceil.get(f"{key}_max")
            assert cmax is not None, f"{name}: no ceiling for {key}"
            assert pin[key] <= cmax, (
                f"{name}: shipped pin {key}={pin[key]} over its own "
                f"ceiling {cmax}"
            )


def test_shipped_pins_keyed_by_costmodel_hashes():
    """The staleness key IS octwall's pinned feature hash: the two pin
    files must agree, or a costmodel refresh would orphan the resource
    pins silently."""
    sec = graphs.load_budgets().get("device_resources", {})
    for name, pin in sec.get("graphs", {}).items():
        cm = costmodel.pinned(name)
        assert cm is not None, f"{name}: no costmodel.json pin"
        assert pin["feature_hash"] == cm["feature_hash"], (
            f"{name}: device_resources pin hash diverged from "
            "costmodel.json (run scripts/lint.py --update-resources)"
        )


def test_resources_payload_reports_freshness():
    budgets = _budgets_with()
    rows = R.resources_payload(["g", "missing"], budgets,
                               [_Feat("g", "h1")])
    assert rows["g"]["fresh"] and rows["g"]["pin"]["flops"] == 100
    assert rows["missing"]["pin"] is None and not rows["missing"]["fresh"]
    # the CLI --json contract: sorted-keys strict JSON round-trip
    json.loads(json.dumps(rows, sort_keys=True, allow_nan=False))
