"""Serving-plane invariants (node/serve.py + protocol/admission.py).

The contract stack, from the ISSUE acceptance wording:

  * differential equality — the continuous-batching scheduler's
    per-tenant verdicts and final fold states are byte-identical to a
    sequential per-tenant `validate_batch` reference, on a mixed
    draft-03 / batch-compatible tenant population with fork storms,
    equivocating pools and injected failure lanes;
  * first-failure semantics per peer under interleaving, and no
    cross-tenant verdict bleed inside shared windows;
  * fairness — one tenant's backlog (same shape via quantum fill, or
    a cold shape via the shape-rotation + rung-capped admission path)
    cannot starve the other tenants;
  * OCT_SERVE_DEVICE=0 actually REROUTES dispatch (a trap on
    `prepare_window` proves the device path is never touched) and the
    host-fold verdicts equal the sequential reference on REAL crypto
    (the host reference fold uses the real host verifiers — stub
    traffic cannot exercise it);
  * a device fault mid-traffic (`device-error@serve-dispatch`) sheds
    to the recovery ladder: verdicts byte-identical to the undisturbed
    run, no tenant dropped, the degraded interval visible (and closed)
    on the SLO surface;
  * a REAL SIGKILL mid-traffic (`sigkill@serve`) relaunches with
    per-tenant carry resume: regenerated seeded traffic fast-forwards
    and the combined verdicts equal the uninterrupted run's;
  * the /slo route serves the live snapshot over HTTP.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import urllib.error
import urllib.request

import pytest

from ouroboros_consensus_tpu.node import serve
from ouroboros_consensus_tpu.obs import recovery
from ouroboros_consensus_tpu.obs.registry import MetricsRegistry
from ouroboros_consensus_tpu.protocol import admission, praos
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.testing import chaos, fixtures, stubs, traffic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def stub_crypto(monkeypatch):
    stubs.install_stub_crypto(monkeypatch)


@pytest.fixture(autouse=True)
def _chaos_disarmed(monkeypatch):
    monkeypatch.delenv("OCT_CHAOS", raising=False)
    monkeypatch.delenv("OCT_SERVE_DEVICE", raising=False)
    chaos.reset()
    recovery.reset_for_tests()
    yield
    monkeypatch.delenv("OCT_CHAOS", raising=False)
    chaos.reset()
    recovery.reset_for_tests()


def _service(tr, **kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("max_window", 32)
    return serve.ValidationService(tr.params, tr.lview, tr.eta0, **kw)


def _drive(svc, tr):
    """Submit the full seeded arrival order, then drain."""
    for sfx in tr.suffixes():
        svc.submit(sfx.tenant_id, sfx.hvs)
    svc.run_until_drained()


def _verdict_rows(svc, tr):
    return {spec.tenant_id: [v.row() for v in svc.verdicts(spec.tenant_id)]
            for spec in tr.tenants}


def _final_states(svc, tr):
    return {spec.tenant_id:
            recovery.encode_state(svc.tenants[spec.tenant_id].state)
            for spec in tr.tenants}


def _reference(tr):
    """Sequential per-tenant validate_batch fold: the differential
    oracle. One tenant at a time, one suffix per call — the exact
    semantics the shared-window scheduler must reproduce."""
    fresh = traffic.Traffic(tr.cfg)
    rows: dict[str, list] = {s.tenant_id: [] for s in fresh.tenants}
    states = {s.tenant_id: fresh.genesis_state() for s in fresh.tenants}
    for sfx in fresh.suffixes():
        st = states[sfx.tenant_id]
        ticked = praos.tick(fresh.params, fresh.lview, sfx.hvs[0].slot, st)
        res = pbatch.validate_batch(fresh.params, ticked, list(sfx.hvs))
        rows[sfx.tenant_id].append(
            [sfx.seq, res.n_valid, serve._canon_error(res.error)]
        )
        states[sfx.tenant_id] = res.state
    return rows, {t: recovery.encode_state(s) for t, s in states.items()}


# ---------------------------------------------------------------------------
# differential equality + first-failure + no cross-tenant bleed
# ---------------------------------------------------------------------------


def test_differential_batched_vs_sequential(stub_crypto):
    """The headline: shared continuous-batched windows over a mixed
    draft-03/bc population with fork storms, equivocators and both
    injected failure classes == the sequential per-tenant reference,
    verdict rows AND final fold states."""
    tr = traffic.make_traffic(
        n_tenants=6, rounds=2, suffix_len=8, bc_every=3,
        fork_storm=4, equivocators=2, bad_lane_every=5,
        unknown_pool_every=6, seed=11,
    )
    svc = _service(tr)
    _drive(svc, tr)
    ref_rows, ref_states = _reference(tr)
    assert _verdict_rows(svc, tr) == ref_rows
    assert _final_states(svc, tr) == ref_states
    # every suffix resolved: nothing dropped, nothing double-counted
    snap = svc.slo_snapshot()
    assert snap["suffixes_done"] == 12 and snap["queue_depth"] == 0


def test_first_failure_per_peer_and_no_bleed_in_shared_windows(stub_crypto):
    """Tenants share windows (fewer windows than suffixes), the bad
    tenant's counter jump surfaces at ITS exact lane, and every clean
    tenant sharing those windows stays fully valid."""
    tr = traffic.make_traffic(
        n_tenants=6, rounds=1, suffix_len=6, bad_lane_every=3, seed=4,
    )
    svc = _service(tr)
    _drive(svc, tr)
    assert svc.windows < 6  # windows were genuinely shared
    bad = {s.tenant_id for s in tr.tenants if s.bad_lane is not None}
    assert bad  # the mix really contains failure lanes
    for spec in tr.tenants:
        (row,) = _verdict_rows(svc, tr)[spec.tenant_id]
        if spec.tenant_id in bad:
            # first-failure: the valid prefix stops AT the bad lane
            assert row[1] == spec.bad_lane
            assert row[2].startswith("CounterOverIncrementedOCERT")
        else:
            assert row[1] == 6 and row[2] is None


# ---------------------------------------------------------------------------
# fairness
# ---------------------------------------------------------------------------


def test_quantum_fill_big_backlog_cannot_starve_same_shape(stub_crypto):
    """Same-shape fairness: the rotating quantum fill shares each
    window, so three 8-header tenants finish in two 16-lane windows
    even though a 64-header suffix is pending the whole time."""
    small = traffic.make_traffic(n_tenants=3, rounds=1, suffix_len=8,
                                 seed=5)
    big = traffic.make_traffic(n_tenants=4, rounds=1, suffix_len=64,
                               seed=5)
    svc = _service(small, max_window=16)
    big_sfx = big.next_suffix(big.tenants[3])  # peer-003: same shape
    svc.submit(big_sfx.tenant_id, big_sfx.hvs)
    for sfx in small.suffixes():
        svc.submit(sfx.tenant_id, sfx.hvs)
    assert svc.pump() and svc.pump()
    for spec in small.tenants:
        assert len(svc.verdicts(spec.tenant_id)) == 1  # smalls resolved
    assert not svc.verdicts("peer-003")  # the backlog is still pending
    svc.run_until_drained()
    (row,) = [v.row() for v in svc.verdicts("peer-003")]
    assert row[1] == 64 and row[2] is None


def test_cold_shape_cannot_starve_warm_tenants(stub_crypto):
    """Cross-shape fairness: a cold tenant with an alien window shape
    (different body length -> different compiled program) rides its
    own rung-capped windows under the shape rotation; the warm
    tenants' traffic completes within a bounded number of pumps."""
    warm = traffic.make_traffic(n_tenants=2, rounds=1, suffix_len=8,
                                seed=3)
    cold = traffic.make_traffic(n_tenants=3, rounds=1, suffix_len=64,
                                body_len=96, seed=3)
    svc = _service(warm, max_window=16)
    cold_sfx = cold.next_suffix(cold.tenants[2])
    svc.submit(cold_sfx.tenant_id, cold_sfx.hvs)  # cold arrives FIRST
    for sfx in warm.suffixes():
        svc.submit(sfx.tenant_id, sfx.hvs)
    for _ in range(4):
        svc.pump()
    for spec in warm.tenants:
        assert len(svc.verdicts(spec.tenant_id)) == 1, (
            "warm tenant starved behind the cold shape"
        )
    svc.run_until_drained()
    (row,) = [v.row() for v in svc.verdicts("peer-002")]
    assert row[1] == 64 and row[2] is None
    # both shapes retired windows of their own
    assert svc.windows >= 5


# ---------------------------------------------------------------------------
# admission
# ---------------------------------------------------------------------------


def _shape():
    return admission.WindowShape(proof_len=80, body_len=64)


def test_admission_rung_ladder_escalates_one_rung_per_warm_window():
    pol = admission.AdmissionPolicy(rungs=(8, 16))
    shape = _shape()
    pol.note_window(shape, 8)  # bucket 8 earned
    d = pol.admit(shape, 32)
    assert d.mode == "rung" and d.lane_cap == 16  # one rung up
    pol.note_window(shape, 16)
    d = pol.admit(shape, 32)
    assert d.mode == "rung" and d.lane_cap == 32  # ladder top reached
    pol.note_window(shape, 32)
    d = pol.admit(shape, 32)
    assert d.mode == "warm" and d.lane_cap == 32
    assert pol.decisions == {"warm": 1, "rung": 2, "host": 0}


def test_admission_kill_switch_caps_nothing(monkeypatch):
    monkeypatch.setenv("OCT_SERVE_DEVICE", "0")
    d = admission.AdmissionPolicy().admit(_shape(), 12)
    assert d.mode == "host" and d.lane_cap == 12


def test_admission_refuses_malformed_at_the_door(stub_crypto):
    tr = traffic.make_traffic(n_tenants=2, rounds=1, suffix_len=4, seed=1)
    hvs = list(tr.next_suffix(tr.tenants[0]).hvs)
    with pytest.raises(admission.AdmissionRefused, match="empty"):
        admission.shape_of("t", [])
    bc = traffic.make_traffic(n_tenants=2, rounds=1, suffix_len=4,
                              bc_every=2, seed=1)
    mixed = hvs[:2] + list(bc.next_suffix(bc.tenants[1]).hvs)[:2]
    with pytest.raises(admission.AdmissionRefused, match="proof formats"):
        admission.shape_of("t", mixed)
    with pytest.raises(admission.AdmissionRefused, match="non-increasing"):
        admission.shape_of("t", [hvs[1], hvs[0]])
    # the service: refusal surfaces to the caller, counts, touches nothing
    svc = _service(tr)
    with pytest.raises(admission.AdmissionRefused):
        svc.submit("peer-000", [hvs[1], hvs[0]])
    assert svc.slo_snapshot()["queue_depth"] == 0
    assert svc._m_suffixes.labels(result="refused").value == 1


# ---------------------------------------------------------------------------
# the OCT_SERVE_DEVICE=0 lever: must actually reroute, on REAL crypto
# ---------------------------------------------------------------------------

_REAL_PARAMS = praos.PraosParams(
    slots_per_kes_period=100, max_kes_evolutions=62, security_param=4,
    active_slot_coeff=__import__("fractions").Fraction(1, 2),
    epoch_length=500, kes_depth=3,
)


def test_lever_reroutes_to_host_fold_real_crypto(monkeypatch):
    """OCT_SERVE_DEVICE=0 regression pin: the device window path is
    NEVER entered (prepare_window is trapped), every window retires
    mode="host", and the host-fold verdicts equal the sequential
    praos.update reference — on REAL crypto, because the host
    reference fold uses the real host verifiers (stub traffic cannot
    reach this floor)."""
    pools = [fixtures.make_pool(i, kes_depth=3) for i in range(3)]
    lview = fixtures.make_ledger_view(pools)
    eta0 = b"\x07" * 32
    chains: dict[str, list] = {"peer-a": [], "peer-b": []}
    slot = 1
    while any(len(c) < 3 for c in chains.values()):
        pool = fixtures.find_leader(_REAL_PARAMS, pools, lview, slot, eta0)
        if pool is not None:
            tid = min(chains, key=lambda t: len(chains[t]))
            if len(chains[tid]) < 3:
                chains[tid].append(fixtures.forge_header_view(
                    _REAL_PARAMS, pool, slot=slot, epoch_nonce=eta0,
                    prev_hash=None, body_bytes=b"b%07d" % slot,
                ))
        slot += 1

    def _trap(*a, **kw):
        raise AssertionError("device path entered with the lever down")

    monkeypatch.setenv("OCT_SERVE_DEVICE", "0")
    monkeypatch.setattr(pbatch, "prepare_window", _trap)
    reg = MetricsRegistry()
    svc = serve.ValidationService(_REAL_PARAMS, lview, eta0,
                                  registry=reg, max_window=8)
    for tid, hvs in chains.items():
        svc.submit(tid, hvs)
    svc.run_until_drained()
    for tid, hvs in chains.items():
        ticked = praos.tick(_REAL_PARAMS, lview, hvs[0].slot,
                            praos.PraosState(epoch_nonce=eta0))
        st, n, err = hvs[0], 0, None
        state = ticked.state
        for i, hv in enumerate(hvs):
            try:
                state = praos.update(
                    _REAL_PARAMS, hv, hv.slot,
                    praos.TickedPraosState(state, lview))
                n = i + 1
            except praos.PraosValidationError as e:
                err = e
                break
        (row,) = [v.row() for v in svc.verdicts(tid)]
        assert row == [0, n, serve._canon_error(err)]
        if err is None:
            assert recovery.encode_state(svc.tenants[tid].state) \
                == recovery.encode_state(state)
    # the reroute is visible on the metrics surface, not just implied
    fam = svc._m_windows
    assert fam.labels(mode="host").value == svc.windows > 0
    assert svc.slo_snapshot()["device_serving"] is False


# ---------------------------------------------------------------------------
# chaos: device-error@serve-dispatch degrades, never drops
# ---------------------------------------------------------------------------


def test_device_error_sheds_to_ladder_byte_identical(stub_crypto,
                                                     monkeypatch):
    """A device fault at the serving dispatch seam: the faulted
    window's segments shed down the recovery ladder, every affected
    tenant still gets byte-identical verdicts, the service keeps
    serving, and the degraded interval opens AND closes on the SLO
    surface."""
    cfg = dict(n_tenants=5, rounds=2, suffix_len=6, bc_every=4,
               bad_lane_every=3, seed=9)
    base_tr = traffic.make_traffic(**cfg)
    base = _service(base_tr)
    _drive(base, base_tr)
    base_rows = _verdict_rows(base, base_tr)

    monkeypatch.setenv("OCT_CHAOS", "device-error@serve-dispatch:1")
    chaos.reset()
    tr = traffic.make_traffic(**cfg)
    svc = _service(tr)
    _drive(svc, tr)
    monkeypatch.delenv("OCT_CHAOS")
    chaos.reset()

    assert chaos.plan() is None  # leave the process disarmed
    assert _verdict_rows(svc, tr) == base_rows
    assert _final_states(svc, tr) == _final_states(base, base_tr)
    snap = svc.slo_snapshot()
    assert snap["degraded"] is False  # recovered: the flag came back
    (iv,) = snap["degraded_intervals"]
    t_open, t_close, klass = iv
    assert t_close is not None and t_close >= t_open
    assert klass == "DeviceChaosError"
    assert svc._m_degraded.value == 0
    assert snap["suffixes_done"] == 10 and snap["queue_depth"] == 0


# ---------------------------------------------------------------------------
# chaos: a REAL SIGKILL mid-traffic, relaunch with per-tenant carry resume
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os, sys
sys.path.insert(0, os.environ["OCT_REPO"])
from ouroboros_consensus_tpu.node import serve
from ouroboros_consensus_tpu.obs.registry import MetricsRegistry
from ouroboros_consensus_tpu.testing import stubs, traffic

stubs.install_stub_crypto(None)
tr = traffic.make_traffic(n_tenants=4, rounds=2, suffix_len=6,
                          bad_lane_every=3, seed=7)
svc = serve.ValidationService(
    tr.params, tr.lview, tr.eta0,
    registry=MetricsRegistry(), max_window=8,
)
for sfx in tr.suffixes():
    svc.submit(sfx.tenant_id, sfx.hvs)
svc.run_until_drained()
out = {
    "resumed": svc.resumed,
    "windows": svc.windows,
    "verdicts": {s.tenant_id: [v.row() for v in svc.verdicts(s.tenant_id)]
                 for s in tr.tenants},
}
with open(os.environ["OCT_TEST_OUT"], "w") as f:
    json.dump(out, f)
"""


def test_sigkill_mid_traffic_resumes_per_tenant_carry(tmp_path):
    """sigkill@serve:N kills the service AFTER a window's checkpoint
    landed; the relaunch resumes every tenant's fold state, the seeded
    traffic re-submits byte-identically (already-banked suffixes
    fast-forward) and the combined verdicts equal an uninterrupted
    run's."""

    def run_child(extra_env):
        out = str(tmp_path / f"out_{len(os.listdir(tmp_path))}.json")
        env = dict(os.environ)
        for k in ("OCT_CHAOS", "OCT_SERVE_CHECKPOINT", "OCT_SERVE_DEVICE"):
            env.pop(k, None)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "OCT_REPO": REPO,
            "OCT_TEST_OUT": out,
        })
        env.update(extra_env)
        proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                              cwd=REPO, capture_output=True, timeout=300)
        return proc, out

    ck = str(tmp_path / "serve_ck.json")
    # 1. the uninterrupted reference
    proc, ref_out = run_child({})
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    ref = json.load(open(ref_out))
    assert sum(len(v) for v in ref["verdicts"].values()) == 8

    # 2. the killed child: SIGKILL after a mid-run window's checkpoint
    proc, _ = run_child({
        "OCT_SERVE_CHECKPOINT": ck,
        "OCT_CHAOS": "sigkill@serve:2",
    })
    assert proc.returncode == -signal.SIGKILL, (
        proc.returncode, proc.stderr.decode()[-2000:]
    )
    doc = serve.read_serve_checkpoint(ck)
    assert doc is not None and doc["windows"] == 3
    banked = sum(len(t["verdicts"]) for t in doc["tenants"].values())
    assert banked < 8  # genuinely mid-traffic

    # 3. the relaunch: carry resume + fast-forward == the reference
    proc, res_out = run_child({"OCT_SERVE_CHECKPOINT": ck})
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    res = json.load(open(res_out))
    assert res["resumed"] is True
    assert res["verdicts"] == ref["verdicts"]
    assert res["windows"] >= doc["windows"]


def test_checkpoint_read_is_fail_closed(tmp_path, stub_crypto):
    tr = traffic.make_traffic(n_tenants=2, rounds=1, suffix_len=4, seed=2)
    ck = str(tmp_path / "ck.json")
    svc = _service(tr, checkpoint=ck)
    _drive(svc, tr)
    doc = serve.read_serve_checkpoint(ck)
    assert doc is not None and doc["windows"] == svc.windows
    # a flipped byte anywhere -> the whole record is refused
    tampered = dict(doc)
    tampered["windows"] = doc["windows"] + 1
    with open(ck, "w") as f:
        json.dump(tampered, f)
    assert serve.read_serve_checkpoint(ck) is None
    with open(ck, "w") as f:
        f.write("{not json")
    assert serve.read_serve_checkpoint(ck) is None
    assert serve.read_serve_checkpoint(str(tmp_path / "absent.json")) is None
    # a refused checkpoint means a FRESH start, never a wrong re-seed
    svc2 = _service(tr, checkpoint=ck)
    assert svc2.resumed is False


# ---------------------------------------------------------------------------
# the live SLO surface
# ---------------------------------------------------------------------------


def test_slo_endpoint_serves_live_snapshot(stub_crypto):
    from ouroboros_consensus_tpu.obs import server as obs_server

    tr = traffic.make_traffic(n_tenants=3, rounds=1, suffix_len=5, seed=6)
    reg = MetricsRegistry()
    svc = _service(tr, registry=reg)
    srv = obs_server.MetricsServer(registry=reg,
                                   slo_doc=svc.slo_snapshot)
    try:
        _drive(svc, tr)
        url = f"http://127.0.0.1:{srv.port}"
        doc = json.load(urllib.request.urlopen(f"{url}/slo"))
        assert doc["kind"] == "oct-serve-slo"
        assert doc["headers"] == 15 and doc["queue_depth"] == 0
        assert doc["verdict_latency_p50_s"] is not None
        assert doc["verdict_latency_p99_s"] is not None
        assert doc["headers_per_s"] > 0
        assert doc["degraded"] is False
        # the scrape itself is counted on the shared registry
        txt = urllib.request.urlopen(f"{url}/metrics").read().decode()
        assert 'oct_metrics_scrapes_total{path="/slo"} 1' in txt
        assert "oct_serve_headers_total 15" in txt
        # unmounted twin: /slo without a serving plane is a 404
        bare = obs_server.MetricsServer(registry=MetricsRegistry())
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{bare.port}/slo")
            assert ei.value.code == 404
        finally:
            bare.close()
    finally:
        srv.close()
