"""The obs/ flight recorder: metrics registry units, Prometheus
exposition format, event-SEQUENCE assertions over the pipelined
validate_chain loop (span / gate / fallback order, including the
aggregate anomaly re-dispatch), Perfetto export schema validation of a
replay, warmup-forensics crash safety, and the instrumentation-purity
differential (telemetry must add ZERO equations to the registry
graphs).

Crypto is the hash-only stub throughout (test_packed_batch idiom): the
telemetry plumbing is what's under test, not the ladders."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from dataclasses import replace
from fractions import Fraction

import numpy as np
import pytest

import jax

from ouroboros_consensus_tpu import obs
from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.block.metrics import NodeMetrics
from ouroboros_consensus_tpu.obs import perfetto
from ouroboros_consensus_tpu.obs.registry import MetricsRegistry
from ouroboros_consensus_tpu.obs.warmup import WarmupRecorder, read_report
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures
from ouroboros_consensus_tpu.utils import trace as T

from tests.test_packed_batch import _stub_verify


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test gets a clean process-wide recorder + registry."""
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()


# ---------------------------------------------------------------------------
# registry units
# ---------------------------------------------------------------------------


def test_counter_gauge_labels_and_exposition():
    reg = MetricsRegistry()
    c = reg.counter("oct_widgets_total", "widgets seen", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    g = reg.gauge("oct_depth", "queue depth")
    g.set(3)
    text = reg.expose_text()
    assert "# HELP oct_widgets_total widgets seen" in text
    assert "# TYPE oct_widgets_total counter" in text
    assert 'oct_widgets_total{kind="a"} 3' in text
    assert 'oct_widgets_total{kind="b"} 1' in text
    assert "oct_depth 3" in text
    # re-registering the same family returns it; a different shape fails
    assert reg.counter("oct_widgets_total", "x", ("kind",)) is c
    with pytest.raises(ValueError):
        reg.counter("oct_widgets_total", "x", ("other",))
    with pytest.raises(ValueError):
        c.labels(wrong="a")


def test_histogram_buckets_quantiles_and_exposition():
    reg = MetricsRegistry()
    h = reg.histogram("oct_lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    h.observe_many(np.asarray([0.5, 100.0]))  # second lands in +Inf
    assert h.count == 6
    assert h.sum == pytest.approx(0.05 + 0.5 * 3 + 5.0 + 100.0)
    assert np.array_equal(h.counts, [1, 3, 1, 1])
    # cumulative bucket exposition + _sum/_count
    text = reg.expose_text()
    assert 'oct_lat_seconds_bucket{le="0.1"} 1' in text
    assert 'oct_lat_seconds_bucket{le="1"} 4' in text
    assert 'oct_lat_seconds_bucket{le="10"} 5' in text
    assert 'oct_lat_seconds_bucket{le="+Inf"} 6' in text
    assert "oct_lat_seconds_count 6" in text
    # quantiles interpolate within the bucket; +Inf clamps to last bound
    assert 0.1 < h.quantile(0.5) <= 1.0
    assert h.quantile(0.999) == 10.0
    assert reg.histogram("oct_empty", "e").quantile(0.5) is None
    # snapshot is JSON-able and carries p50/p99
    snap = reg.snapshot()
    json.dumps(snap)
    row = snap["oct_lat_seconds"]["samples"][0]
    assert row["count"] == 6 and row["p99"] == 10.0


def test_histogram_observe_many_equals_observe():
    reg = MetricsRegistry()
    a = reg.histogram("a", "", buckets=(0.01, 0.1, 1.0))
    b = reg.histogram("b", "", buckets=(0.01, 0.1, 1.0))
    vals = [0.001, 0.02, 0.5, 2.0, 0.09]
    for v in vals:
        a.observe(v)
    b.observe_many(vals)
    assert np.array_equal(a.counts, b.counts)
    assert a.sum == pytest.approx(b.sum)


# ---------------------------------------------------------------------------
# event dataclasses + NodeTracers
# ---------------------------------------------------------------------------


def test_enclose_event_frozen_like_every_other_event():
    ev = T.EncloseEvent("x", "start", 1.0)
    with pytest.raises(dataclasses.FrozenInstanceError):
        ev.duration = 2.0
    lt = T.ListTracer()
    with T.Enclose(lt, "phase"):
        pass
    assert [e.edge for e in lt.events] == ["start", "end"]
    assert lt.events[1].duration is not None


def test_node_tracers_all_to_derives_field_count():
    tr = T.ListTracer()
    nt = T.NodeTracers.all_to(tr)
    assert all(
        getattr(nt, f.name) is tr for f in dataclasses.fields(T.NodeTracers)
    )

    # REGRESSION: a subclass gaining a tracer field must not silently
    # desync (the old `cls(*([tracer] * 7))` left new fields at null)
    @dataclasses.dataclass
    class MoreTracers(T.NodeTracers):
        extra_subsystem: T.Tracer = T.null_tracer

    mt = MoreTracers.all_to(tr)
    assert mt.extra_subsystem is tr
    assert all(
        getattr(mt, f.name) is tr for f in dataclasses.fields(MoreTracers)
    )


# ---------------------------------------------------------------------------
# NodeMetrics <-> registry wiring
# ---------------------------------------------------------------------------


def test_node_metrics_registry_mirror_and_batch_fold():
    reg = MetricsRegistry()
    m = NodeMetrics().bind(reg)
    m.inc("blocks_forged")
    m.note_batch(T.ValidatedBatch(n_headers=8, n_valid=7, device_s=0.25))
    m.note_batch(T.ValidatedBatch(n_headers=4, n_valid=4, device_s=0.05))
    assert m.batches_validated == 2
    assert m.headers_validated == 11
    assert m.headers_invalid == 1
    assert m.batch_device_s == pytest.approx(0.30)
    snap = reg.snapshot()
    assert snap["oct_node_blocks_forged_total"]["samples"][0]["value"] == 1
    assert snap["oct_node_headers_validated_total"]["samples"][0]["value"] == 11
    assert snap["oct_node_headers_invalid_total"]["samples"][0]["value"] == 1


def test_kernel_wires_ledgerdb_batch_events(tmp_path):
    from tests.test_hotkey import _mk_kernel

    kernel = _mk_kernel(tmp_path)
    reg = MetricsRegistry()
    kernel.metrics.bind(reg)
    lt = T.ListTracer()
    kernel.tracers = T.NodeTracers(batch_validation=lt)
    # the kernel pointed the LedgerDB's typed tracer at its fold
    ldb = kernel.chain_db.ledgerdb
    assert ldb.tracer is not None
    ev = T.ValidatedBatch(n_headers=16, n_valid=15, device_s=0.5)
    ldb.tracer(ev)
    assert kernel.metrics.headers_validated == 15
    assert kernel.metrics.headers_invalid == 1
    assert lt.events == [ev]
    assert (
        reg.snapshot()["oct_node_batches_validated_total"]["samples"][0]["value"]
        == 1
    )


# ---------------------------------------------------------------------------
# pipelined validate_chain: span / gate / fallback event sequences
# ---------------------------------------------------------------------------


def make_params(kes_depth=3, epoch_length=100_000):
    return praos.PraosParams(
        slots_per_kes_period=100,
        max_kes_evolutions=62,
        security_param=4,
        active_slot_coeff=Fraction(1, 2),
        epoch_length=epoch_length,
        kes_depth=kes_depth,
    )


@pytest.fixture(scope="module")
def pools():
    return [fixtures.make_pool(50 + i, kes_depth=3) for i in range(2)]


@pytest.fixture(scope="module")
def lview(pools):
    return fixtures.make_ledger_view(pools)


@pytest.fixture
def stubbed(monkeypatch):
    """Hash-only fused verifiers, aggregate path off, jit caches fenced
    (the test_packed_batch stubbed_crypto idiom, local so this module
    controls OCT_VRF_AGG per test)."""
    before = set(pbatch._JIT)
    monkeypatch.setenv("OCT_VRF_AGG", "0")
    monkeypatch.setattr(pbatch, "verify_praos", _stub_verify)
    monkeypatch.setattr(pbatch, "verify_praos_bc", _stub_verify)
    monkeypatch.setattr(pbatch, "verify_praos_any", _stub_verify)

    def patched_jv(bc=False):
        key = ("fn-stub", bc)
        if key not in pbatch._JIT:
            pbatch._JIT[key] = jax.jit(_stub_verify)
        return pbatch._JIT[key]

    monkeypatch.setattr(pbatch, "_jitted_verify", patched_jv)
    yield
    for k in set(pbatch._JIT) - before:
        del pbatch._JIT[k]


def _forge_chain(params, pools, lview, n, first_slot=100, first_blkno=1):
    st = praos.PraosState(epoch_nonce=b"\x07" * 32)
    hvs, prev = [], b"\xaa" * 32
    slot, blkno = first_slot, first_blkno
    while len(hvs) < n:
        ticked = praos.tick(params, lview, slot, st)
        blk = forge_block(
            params, pools[len(hvs) % 2], slot=slot, block_no=blkno,
            prev_hash=prev, epoch_nonce=ticked.state.epoch_nonce,
            txs=(b"t",),
        )
        hv = blk.header.to_view()
        st = praos.reupdate(params, hv, slot, ticked)
        hvs.append(hv)
        prev = blk.header.hash_
        slot += 1
        blkno += 1
    return st, hvs


def _of(events, cls):
    return [e for e in events if isinstance(e, cls)]


def test_clean_chain_span_sequence(pools, lview, stubbed):
    """Every window: WindowStaged at dispatch, WindowSpan at retire, in
    index order, packed outcome, correct lane accounting."""
    params = make_params()
    _, hvs = _forge_chain(params, pools, lview, 24)
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    lt = T.ListTracer()
    pbatch.set_batch_tracer(lt)
    try:
        res = pbatch.validate_chain(
            params, lambda _e: lview, st0, hvs, max_batch=8
        )
    finally:
        pbatch.set_batch_tracer(None)
    assert res.error is None and res.n_valid == 24
    staged = _of(lt.events, T.WindowStaged)
    spans = _of(lt.events, T.WindowSpan)
    assert len(spans) == len(staged) >= 3
    assert [s.index for s in spans] == sorted(s.index for s in staged)
    assert sum(s.n_valid for s in spans) == 24
    assert not any(s.failed for s in spans)
    # a window is always staged before it retires
    for sp in spans:
        i_staged = next(
            i for i, e in enumerate(lt.events)
            if isinstance(e, T.WindowStaged) and e.index == sp.index
        )
        i_span = lt.events.index(sp)
        assert i_staged < i_span
    # phase walls are populated and sane
    for sp in spans:
        for v in (sp.stage_s, sp.dispatch_s, sp.materialize_s,
                  sp.epilogue_s):
            assert v >= 0.0
        assert sp.t_done >= sp.t_materialized >= sp.t_dispatch - 1e-9


def test_gate_decline_names_the_gate(pools, lview, stubbed):
    """A window whose signed bodies do not embed its fields cannot stage
    packed: the WindowStaged event says generic AND names the
    qualification gate (the first gates were silent about why). A window
    mixing CBOR body widths stages packed, one body layout each."""
    params = make_params()
    # block_no 18..: crosses the CBOR 1->2-byte boundary at 24, so one
    # window mixes body widths (the test_columnar boundary idiom)
    _, hvs = _forge_chain(params, pools, lview, 16, first_blkno=18)
    widths = {len(hv.signed_bytes) for hv in hvs}
    assert len(widths) == 2, "fixture must cross a CBOR width boundary"
    nonce = b"\x07" * 32
    synthetic = [
        fixtures.forge_header_view(params, pools[i % 2], slot=100 + i,
                                   epoch_nonce=nonce, prev_hash=b"x" * 32,
                                   body_bytes=b"body-%d" % i)
        for i in range(16)
    ]
    st0 = praos.PraosState(epoch_nonce=nonce)
    for chain, outcome, gate, layouts in ((hvs, "packed", None, 2),
                                          (synthetic, "generic",
                                           "field-offsets", 0)):
        lt = T.ListTracer()
        pbatch.set_batch_tracer(lt)
        try:
            res = pbatch.validate_chain(
                params, lambda _e: lview, st0, chain, max_batch=16
            )
        finally:
            pbatch.set_batch_tracer(None)
        assert res.error is None and res.n_valid == 16
        (staged,) = _of(lt.events, T.WindowStaged)
        assert (staged.outcome, staged.gate) == (outcome, gate)
        # and the retired span carries the same attribution
        (sp,) = _of(lt.events, T.WindowSpan)
        assert (sp.outcome, sp.gate, sp.layouts) == (outcome, gate, layouts)


def test_stage_packed_decline_reasons_unit(pools, lview):
    """Each qualification gate reports its own reason."""
    params = make_params()
    _, hvs = _forge_chain(params, pools, lview, 4)
    nonce = b"\x07" * 32

    assert pbatch.stage_packed(params, lview, nonce, []) is None
    assert pbatch._LAST_DECLINE == "empty-window"

    # bodies of two lengths are no gate: one layout each
    bad = [replace(hvs[0], signed_bytes=hvs[0].signed_bytes + b"x"), *hvs[1:]]
    assert pbatch.stage_packed(params, lview, nonce,
                               bad)[1].body_layout.max() == 1

    bad = [replace(hv, kes_sig=hv.kes_sig + b"x") for hv in hvs]
    assert pbatch.stage_packed(params, lview, nonce, bad) is None
    assert pbatch._LAST_DECLINE == "kes-sig-len"

    bad = [replace(hv, vrf_proof=hv.vrf_proof[:64]) for hv in hvs]
    assert pbatch.stage_packed(params, lview, nonce, bad) is None
    assert pbatch._LAST_DECLINE == "proof-format"

    # lane 0's field not embedded in its body at all: offset discovery
    bad = [replace(hvs[0], vk_cold=bytes(32)), *hvs[1:]]
    assert pbatch.stage_packed(params, lview, nonce, bad) is None
    assert pbatch._LAST_DECLINE == "field-offsets"

    # a LATER lane whose field differs from its embedded copy: the
    # per-lane byte verification
    bad = [hvs[0], replace(hvs[1], vk_cold=bytes(32)), *hvs[2:]]
    assert pbatch.stage_packed(params, lview, nonce, bad) is None
    assert pbatch._LAST_DECLINE == "field-mismatch"

    bad = [replace(hv, slot=hv.slot + 2**31) for hv in hvs]
    assert pbatch.stage_packed(params, lview, nonce, bad) is None
    assert pbatch._LAST_DECLINE == "int32-range"


def test_corrupted_chain_failing_window_span(pools, lview, stubbed):
    """First-failure semantics in the telemetry: the failing window's
    span reports failed=True with the valid-prefix lane count, and no
    window after it retires (discarded in-flight successors emit
    WindowStaged but never WindowSpan)."""
    params = make_params()
    _, hvs = _forge_chain(params, pools, lview, 24)
    # lane 13 (window 1 of 3 at max_batch=8): unknown pool -> the exact
    # host precheck error; the signed body still embeds the original
    # key, so the window ALSO exercises the field-mismatch fallback
    hvs = [
        replace(hv, vk_cold=bytes(32)) if i == 13 else hv
        for i, hv in enumerate(hvs)
    ]
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    lt = T.ListTracer()
    pbatch.set_batch_tracer(lt)
    try:
        res = pbatch.validate_chain(
            params, lambda _e: lview, st0, hvs, max_batch=8
        )
    finally:
        pbatch.set_batch_tracer(None)
    assert res.n_valid == 13
    # the exact reference error order: the stateful counter check runs
    # before the VRF pool lookup, and an unknown pool has no counter
    assert isinstance(res.error, praos.NoCounterForKeyHashOCERT)
    spans = _of(lt.events, T.WindowSpan)
    assert spans[-1].failed and spans[-1].n_valid == 5
    assert spans[-1].gate == "field-mismatch"
    assert not any(s.failed for s in spans[:-1])
    staged_idx = {s.index for s in _of(lt.events, T.WindowStaged)}
    retired_idx = {s.index for s in spans}
    assert retired_idx < staged_idx or retired_idx == staged_idx


def test_agg_anomaly_redispatch_event(pools, lview, monkeypatch):
    """The aggregate (RLC/MSM) path re-dispatching a dirty window emits
    AggRedispatch BEFORE that window's span (test_aggregate's stubbed
    dispatch plumbing, now with the event order asserted)."""
    from ouroboros_consensus_tpu.ops.pk import aggregate as agg_mod

    from tests.test_aggregate import (
        _stub_aggregate, _stub_verdicts, real_chain,
    )

    before = set(pbatch._JIT)
    params = make_params()
    nonce, hvs = real_chain(params, pools, lview, 12)
    assert len(hvs[0].vrf_proof) == 128
    monkeypatch.setattr(agg_mod, "aggregate_window", _stub_aggregate(False))
    monkeypatch.setattr(pbatch, "verify_praos_any",
                        lambda *cols: _stub_verdicts(cols))
    lt = T.ListTracer()
    pbatch.set_batch_tracer(lt)
    try:
        res = pbatch.validate_chain(
            params, lambda _e: lview,
            replace(praos.PraosState(), epoch_nonce=nonce), hvs,
            max_batch=len(hvs),
        )
    finally:
        pbatch.set_batch_tracer(None)
        for k in set(pbatch._JIT) - before:
            del pbatch._JIT[k]
    assert res.error is None and res.n_valid == len(hvs)
    kinds = [type(e).__name__ for e in lt.events]
    assert "AggRedispatch" in kinds
    staged = _of(lt.events, T.WindowStaged)
    assert staged[0].outcome == "packed-agg"
    assert kinds.index("AggRedispatch") < kinds.index("WindowSpan")


# ---------------------------------------------------------------------------
# flight recorder -> registry + Perfetto export of a replay
# ---------------------------------------------------------------------------


def test_recorder_replay_metrics_and_perfetto_schema(pools, lview, stubbed,
                                                     monkeypatch):
    """OCT_TRACE end to end: the recorder rides a (stubbed) pipelined
    replay, the dispatch->materialize latency histogram records p50/p99,
    and the Perfetto export validates against the Chrome trace-event
    schema."""
    params = make_params()
    _, hvs = _forge_chain(params, pools, lview, 24)
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    monkeypatch.setenv("OCT_TRACE", "1")
    assert obs.enabled()
    rec = obs.install()
    try:
        res = pbatch.validate_chain(
            params, lambda _e: lview, st0, hvs, max_batch=8
        )
    finally:
        obs.uninstall()
    assert res.error is None
    assert pbatch.BATCH_TRACER is None  # uninstall restored the seam

    summary = rec.latency_summary()
    assert summary["windows"] >= 3
    assert summary["device_latency_p50_s"] is not None
    assert summary["device_latency_p99_s"] is not None
    assert summary["device_latency_p99_s"] >= summary["device_latency_p50_s"]

    snap = rec.registry.snapshot()
    outcomes = {
        s["labels"]["outcome"]: s["value"]
        for s in snap["oct_windows_total"]["samples"]
    }
    assert sum(outcomes.values()) == summary["windows"]
    assert snap["oct_headers_validated_total"]["samples"][0]["value"] == 24
    assert snap["oct_h2d_bytes_total"]["samples"][0]["value"] > 0
    lat = snap["oct_window_device_latency_seconds"]["samples"][0]
    assert lat["count"] == summary["windows"]
    assert lat["p50"] is not None and lat["p99"] is not None

    doc = rec.chrome_trace()
    assert perfetto.validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    # window indexes are a process-global sequence: assert shape, not 0
    assert any(n.startswith("window ") for n in names)
    assert "stage" in names and "materialize" in names
    # round-trips through real JSON
    doc2 = json.loads(json.dumps(doc))
    assert perfetto.validate_chrome_trace(doc2) == []


def test_perfetto_validator_rejects_malformed():
    assert perfetto.validate_chrome_trace([]) != []
    assert perfetto.validate_chrome_trace({"traceEvents": "no"}) != []
    bad = {"traceEvents": [{"name": 3, "ph": "Q", "ts": -1, "pid": "x"}]}
    errs = perfetto.validate_chrome_trace(bad)
    assert len(errs) >= 4
    good = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 1.5, "pid": 1, "tid": 2},
    ]}
    assert perfetto.validate_chrome_trace(good) == []


# ---------------------------------------------------------------------------
# warmup forensics
# ---------------------------------------------------------------------------


def test_warmup_recorder_report_and_flush(tmp_path, monkeypatch):
    path = str(tmp_path / "warmup.json")
    monkeypatch.setenv("OCT_WARMUP_REPORT", path)
    w = WarmupRecorder()
    assert w.note_stage("ed@b8192", 123.4, via="jit")
    assert not w.note_stage("ed@b8192", 0.001)  # only the first counts
    # the file is flushed ATOMICALLY after every note — a kill at any
    # point leaves the last complete report on disk
    on_disk = read_report(path)
    assert on_disk["stages"]["ed@b8192"]["wall_s"] == pytest.approx(123.4)
    w.note_aot("kes", "rejected", 15.2, "serialized executable is incompatible")
    w.note_cache_probe("stale", 14.9, "serialized executable is incompatible")
    w.note("warmup replay starting")
    rep = read_report(path)
    assert rep["aot"] == {"rejected": 1}
    assert rep["aot_events"][0]["stage"] == "kes"
    assert rep["cache_probe"]["outcome"] == "stale"
    assert rep["compile_total_s"] == pytest.approx(123.4)
    assert rep["n_stages"] == 1
    assert any("warmup replay" in n for n in rep["notes"])
    # the keys benchmark/traffic/replay.py reads off every report;
    # nothing refuses a dispatch, so `refusals` is always empty
    assert {"stages", "aot", "aot_events", "recovery"} <= set(rep)
    assert rep["refusals"] == []
    json.dumps(rep)


def test_warmup_report_survives_a_kill(tmp_path):
    """The r02-r05 failure shape: a bench child dies mid-warmup. The
    per-note atomic flush must leave a readable per-stage diagnosis."""
    path = str(tmp_path / "warmup.json")
    code = (
        "import os\n"
        "from ouroboros_consensus_tpu.obs.warmup import WARMUP\n"
        "WARMUP.note_stage('relayout@b8192', 95.0, via='jit')\n"
        "WARMUP.note_stage('ed@b8192', 180.5, via='jit')\n"
        "WARMUP.note_aot('vrf', 'rejected', 15.0, 'serialized executable is incompatible')\n"
        "os._exit(137)  # killed at the wall mid-compile\n"
    )
    env = dict(os.environ)
    env["OCT_WARMUP_REPORT"] = path
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, timeout=120,
    )
    assert proc.returncode == 137
    rep = read_report(path)
    assert rep is not None, "a warmup death must still bank a diagnosis"
    assert rep["stages"]["ed@b8192"]["wall_s"] == pytest.approx(180.5)
    assert rep["compile_total_s"] == pytest.approx(275.5)
    assert rep["aot"] == {"rejected": 1}
    # and bench.py banks exactly this block into the round JSON
    import bench

    assert bench._read_warmup_report(path) == rep


def test_stage_call_records_first_execute(monkeypatch):
    from ouroboros_consensus_tpu.obs.warmup import WARMUP
    from ouroboros_consensus_tpu.ops.pk import kernels

    monkeypatch.setenv("OCT_PK_AOT", "0")  # jit path only
    WARMUP.reset()
    kernels._FIRST_EXEC.discard("obstest@b4")
    calls = []

    def fake_stage(x):
        calls.append(x)
        return x

    out = kernels._stage_call("obstest", fake_stage, 4, 2, np.zeros(3))
    kernels._stage_call("obstest", fake_stage, 4, 2, np.zeros(3))
    assert len(calls) == 2 and out is calls[0]
    rep = WARMUP.report()
    assert "obstest@b4" in rep["stages"]
    assert rep["stages"]["obstest@b4"]["via"] == "jit"


# ---------------------------------------------------------------------------
# instrumentation purity (the telemetry-adds-zero-equations ratchet)
# ---------------------------------------------------------------------------


def test_instrumentation_purity_zero_eqn_growth():
    from ouroboros_consensus_tpu.analysis import graphs

    budgets = graphs.load_budgets()
    assert budgets["instrumentation_purity"]["graphs"], (
        "the purity ratchet must pin at least the protocol/batch graphs"
    )
    # the cheap protocol/batch graph: one differential proves the wiring
    assert graphs.check_instrumentation_purity(
        budgets, names=["verdict_reduce"]
    ) == []


# ---------------------------------------------------------------------------
# Prometheus endpoint (tools/immdb_server.serve_metrics)
# ---------------------------------------------------------------------------


def test_metrics_http_endpoint():
    import asyncio

    from ouroboros_consensus_tpu.tools import immdb_server

    reg = MetricsRegistry()
    reg.counter("oct_widgets_total", "w").inc(5)

    async def scenario():
        server = await immdb_server.serve_metrics(port=0, registry=reg)
        port = server.sockets[0].getsockname()[1]

        async def get(path):
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
            await writer.drain()
            data = await reader.read()
            writer.close()
            return data

        text = await get("/metrics")
        assert text.startswith(b"HTTP/1.0 200 OK")
        assert b"oct_widgets_total 5" in text
        js = await get("/metrics.json")
        body = js.split(b"\r\n\r\n", 1)[1]
        snap = json.loads(body)
        assert snap["oct_widgets_total"]["samples"][0]["value"] == 5
        # scrapes counted themselves
        assert snap["oct_metrics_scrapes_total"]["samples"][0]["value"] >= 1
        missing = await get("/nope")
        assert missing.startswith(b"HTTP/1.0 404")
        server.close()
        await server.wait_closed()

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# histogram hardening: non-finite observations must never leak NaN into
# JSON snapshots (and through them the bench round file)
# ---------------------------------------------------------------------------


def test_histogram_nonfinite_observations_dropped_and_counted():
    reg = MetricsRegistry()
    h = reg.histogram("oct_nan_seconds", "hardening", buckets=(1.0, 10.0))
    # empty histogram: None, never NaN (regression for the quantile
    # contract the bench round file depends on)
    assert h.quantile(0.5) is None
    assert h.quantile(0.99) is None
    h.observe(float("nan"))
    h.observe(float("inf"))
    h.observe_many([1.0, float("nan"), 2.0, float("-inf")])
    assert h.count == 2
    assert h.dropped_nonfinite == 4
    assert h.sum == pytest.approx(3.0)  # NaN never poisoned the sum
    snap = reg.snapshot()
    # the whole snapshot stays STRICT json — json.dumps(allow_nan=False)
    # is exactly what obs/ledger.append enforces
    json.dumps(snap, allow_nan=False)
    row = snap["oct_nan_seconds"]["samples"][0]
    assert row["dropped_nonfinite"] == 4
    assert row["p50"] is not None and row["p99"] is not None
    # exposition still renders (finite values only)
    assert "oct_nan_seconds_count 2" in reg.expose_text()


def test_latency_summary_none_not_nan_on_empty_recorder():
    rec = obs.recorder()
    s = rec.latency_summary()
    assert s["windows"] == 0
    assert s["device_latency_p50_s"] is None
    assert s["device_latency_p99_s"] is None
    json.dumps(s, allow_nan=False)


# ---------------------------------------------------------------------------
# metric-name drift gate: obs/README.md vs the registrations, both ways
# ---------------------------------------------------------------------------


def _readme_metric_names():
    import re

    readme = os.path.join(
        os.path.dirname(os.path.abspath(obs.__file__)), "README.md"
    )
    with open(readme, encoding="utf-8") as f:
        text = f.read()
    concrete, wildcards = set(), set()
    # tokens like oct_windows_total, oct_window_{a,b}_seconds{label=},
    # oct_node_*_total; the lookbehind keeps ".oct_ledger" (a path, not
    # a metric) out
    for tok in re.findall(r"(?<![.\w])oct_[a-z0-9_]+(?:\{[^}\s]*\})?"
                          r"[a-z0-9_*]*", text):
        # strip a trailing label annotation: {kind=} / {stage=,kind=}
        tok = re.sub(r"\{[^}]*=[^}]*\}", "", tok)
        m = re.match(r"^([a-z0-9_]*)\{([a-z0-9_,]+)\}([a-z0-9_]*)$", tok)
        if m:  # brace EXPANSION: oct_window_{stage,dispatch}_seconds
            for alt in m.group(2).split(","):
                concrete.add(m.group(1) + alt + m.group(3))
        elif "*" in tok:
            wildcards.add(tok)
        elif re.fullmatch(r"oct_[a-z0-9_]+", tok):
            concrete.add(tok)
    return concrete, wildcards


def _registered_metric_names():
    import re

    from ouroboros_consensus_tpu.node import serve as node_serve
    from ouroboros_consensus_tpu.obs import resources as obs_resources
    from ouroboros_consensus_tpu.obs import server as obs_server
    from ouroboros_consensus_tpu.obs.recorder import FlightRecorder
    from ouroboros_consensus_tpu.tools import immdb_server

    reg = MetricsRegistry()
    FlightRecorder(reg)
    NodeMetrics().bind(reg)
    obs_resources.register_families(reg)
    names = set(reg._families)
    # the immdb server, the (factored-out) HTTP endpoint and the serving
    # plane register their families at serve time: hold them to the same
    # contract via their registration literals
    for mod in (immdb_server, obs_server, node_serve):
        with open(mod.__file__, encoding="utf-8") as f:
            names |= set(re.findall(r'"(oct_[a-z0-9_]+)"', f.read()))
    return names


def test_readme_metric_names_match_registrations():
    """Both directions: the README's metric table cannot rot as families
    are added (this PR adds oct_stage_*), and no documented family may
    silently disappear from the code."""
    import fnmatch

    concrete, wildcards = _readme_metric_names()
    actual = _registered_metric_names()
    assert concrete, "README metric table parsed empty — parser broken?"

    documented_missing = {
        n for n in concrete if n not in actual
    } | {
        w for w in wildcards
        if not any(fnmatch.fnmatch(a, w) for a in actual)
    }
    assert not documented_missing, (
        f"obs/README.md documents families the code never registers: "
        f"{sorted(documented_missing)}"
    )
    undocumented = {
        a for a in actual
        if a not in concrete
        and not any(fnmatch.fnmatch(a, w) for w in wildcards)
    }
    assert not undocumented, (
        f"registered families missing from obs/README.md: "
        f"{sorted(undocumented)}"
    )


# ---------------------------------------------------------------------------
# Perfetto warmup track (compile walls visible in the wall visualizer)
# ---------------------------------------------------------------------------


def test_perfetto_warmup_track_slices_and_instants():
    from ouroboros_consensus_tpu.obs.warmup import WARMUP

    WARMUP.reset()
    WARMUP.note_stage("agg-packed:410b", 12.5, via="xla-jit")
    WARMUP.note_aot("ed", "rejected", 1.0, "serialized executable is incompatible")
    rec = obs.recorder()
    doc = rec.chrome_trace()
    assert perfetto.validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    names = [e["name"] for e in evs]
    # thread metadata names the warmup row
    threads = {e["args"]["name"] for e in evs if e["ph"] == "M"
               and e["name"] == "thread_name"}
    assert "warmup" in threads
    (slice_ev,) = [e for e in evs if "first-execute" in e["name"]]
    assert slice_ev["ph"] == "X"
    assert slice_ev["dur"] == pytest.approx(12.5e6, rel=1e-6)
    assert slice_ev["tid"] == perfetto._TIDS["warmup"]
    assert slice_ev["args"]["via"] == "xla-jit"
    assert any(n == "aot ed: rejected" for n in names)
    # a report WITHOUT its t0 (cross-process file) adds no warmup rows
    doc2 = perfetto.to_chrome_trace([], warmup_report=WARMUP.report(),
                                    warmup_t0=None)
    assert not any("first-execute" in e["name"]
                   for e in doc2["traceEvents"])
    WARMUP.reset()


def test_trace_out_replay_includes_warmup_track(pools, lview, stubbed,
                                                monkeypatch):
    """The --trace-out shape: a (stubbed) replay export carries BOTH
    window spans and the warmup first-execute slices in one document."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP

    WARMUP.reset()
    # earlier tests in this process may have consumed the stub jits'
    # first executes — clear the once-only gate so THIS replay notes them
    pbatch._WARM_SEEN.clear()
    params = make_params()
    _, hvs = _forge_chain(params, pools, lview, 16)
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    monkeypatch.setenv("OCT_TRACE", "1")
    rec = obs.install()
    try:
        res = pbatch.validate_chain(
            params, lambda _e: lview, st0, hvs, max_batch=8
        )
    finally:
        obs.uninstall()
    assert res.error is None
    doc = rec.chrome_trace()
    assert perfetto.validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert any(n.startswith("window ") for n in names)
    # the stubbed jits ARE first executes: their compile slices show up
    assert any("first-execute" in n for n in names)
    WARMUP.reset()


# ---------------------------------------------------------------------------
# acceptance: a stubbed-crypto replay appends ONE well-formed ledger
# record carrying the recorder's state
# ---------------------------------------------------------------------------


def test_stubbed_replay_appends_one_ledger_record(pools, lview, stubbed,
                                                  monkeypatch, tmp_path):
    from ouroboros_consensus_tpu.obs import ledger

    led = str(tmp_path / "ledger")
    monkeypatch.setenv("OCT_LEDGER", led)
    monkeypatch.setenv("OCT_TRACE", "1")
    params = make_params()
    _, hvs = _forge_chain(params, pools, lview, 24)
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    rec = obs.install()
    try:
        res = pbatch.validate_chain(
            params, lambda _e: lview, st0, hvs, max_batch=8
        )
    finally:
        obs.uninstall()
    assert res.error is None and res.n_valid == 24
    out = ledger.record_replay(
        "replay", recorder=rec,
        config={"n": 24, "max_batch": 8},
        result={"headers": res.n_valid},
    )
    assert out is not None
    runs = ledger.read_runs(led)
    assert len(runs) == 1, "exactly one record per run"
    rec_d = runs[0]
    assert ledger.validate_record(rec_d) == []
    assert rec_d["kind"] == "replay"
    # the recorder's state rode in: metrics snapshot + latency summary
    assert rec_d["metrics"]["oct_headers_validated_total"][
        "samples"][0]["value"] == 24
    assert rec_d["metrics_summary"]["windows"] >= 3
    assert rec_d["warmup_report"] is not None
    assert rec_d["env"].get("OCT_TRACE") == "1"


# ---------------------------------------------------------------------------
# lint --changed: obs edits re-run the instrumentation-purity re-trace
# ---------------------------------------------------------------------------


def test_lint_changed_maps_obs_sources_to_purity_graphs():
    """An obs/ (or perf_report) edit cannot change a crypto graph, but
    it CAN leak telemetry into a traced program — the --changed fast
    path must select the instrumentation-purity graphs instead of
    skipping every graph pass."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "lint_gate", os.path.join(repo, "scripts", "lint.py")
    )
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    # forge_sweep joined the purity plane in round 18: ForgeSpan
    # telemetry is emitted beside the traced sweep program
    purity = {"packed_unpack", "verdict_reduce", "spmd_sharded_verify",
              "forge_sweep"}
    assert set(lint._select_graphs(
        {"ouroboros_consensus_tpu/obs/recorder.py"}
    )) == purity
    assert set(lint._select_graphs({"scripts/perf_report.py"})) == purity
    # the round-11 live-plane modules ride the obs/ prefix
    assert set(lint._select_graphs(
        {"ouroboros_consensus_tpu/obs/live.py"}
    )) == purity
    assert set(lint._select_graphs(
        {"ouroboros_consensus_tpu/obs/server.py"}
    )) == purity
    # parallel/spmd.py emits ShardSpan telemetry beside the shard_map
    # program: an spmd edit re-runs the purity differential ON TOP of
    # its own graph selection
    assert purity <= set(lint._select_graphs(
        {"ouroboros_consensus_tpu/parallel/spmd.py"}
    ))
    # composes with ordinary graph-source selection
    sel = lint._select_graphs({
        "ouroboros_consensus_tpu/obs/ledger.py",
        "ouroboros_consensus_tpu/ops/pk/msm.py",
    })
    assert set(sel) == purity | {"aggregate_core", "aggregate_vrf_core",
                                 "msm"}
    # and still selects nothing for unrelated files
    assert lint._select_graphs({"README.md"}) == []
