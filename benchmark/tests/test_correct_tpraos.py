"""`correct` of the kind `replay_tpraos` has to come out false when the timed
path is broken underneath, as `test_correct.py` holds for the plain kind.

The same drive: the harness's look for a chip skipped, the rest of a run
(`traffic/replay_tpraos.run` through `run.main`) at a tiny size on the CPU,
the program's native verifier standing in for the device path (both proofs a
header: native/hostcrypto.cpp `oc_validate_tpraos`), the faults planted where
the program hands its answers over, `protocol.batch.validate_chain`.
"""

import dataclasses
import json

import pytest

from benchmark import run as brun
from benchmark.traffic import replay, replay_tpraos

SEED = 2_400_000_123  # more than 32 signed bits hold
CELL = "replay-tpraos-2epoch"
KINDS = ["ocert-signature", "kes-signature", "vrf-eta-proof",
         "vrf-leader-proof", "overlay-wrong-delegate"]


@pytest.fixture()
def on_cpu(monkeypatch):
    # `replay_once` and `_validate_window` are `replay`'s, and read its
    monkeypatch.setattr(replay, "BACKEND", "native")
    monkeypatch.setattr(replay_tpraos, "nothing_hid_the_chip",
                        lambda *a, **k: {})


def _run(capsys, seed=SEED):
    rc = brun.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    "0.2", "--trace", "0", "--cpu-rehearsal"])
    out, err = capsys.readouterr()
    lines = [json.loads(x) for x in out.strip().splitlines()]
    last = lines[-1]
    assert rc == 2 and last["line"] == "rehearsal"  # never a result line
    judged = next(x for x in lines if x["line"] == "judged")
    return last["would_be"], err, judged


def _plant(monkeypatch, fault):
    from ouroboros_consensus_tpu.protocol import batch as pbatch

    real = pbatch.validate_chain

    def broken(params, lview_for_epoch, state, hvs, **kw):
        return fault(real, params, lview_for_epoch, state, hvs, kw)

    monkeypatch.setattr(pbatch, "validate_chain", broken)


def test_sound_run_is_correct(on_cpu, capsys):
    res, err, judged = _run(capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["compared"].values())
    assert "compared wrong_header_mismatches: value 0 limit 0" in err
    assert err.strip().splitlines()[-1] == "correct: True"
    cases = judged["wrong_header_cases"]
    assert [c["corrupted"] for c in cases] == KINDS
    # each refused at its own lane, by the error of its own rule
    assert [c["reference"][1][0] for c in cases] == [
        "InvalidSignatureOCERT", "InvalidKesSignatureOCERT",
        "VRFKeyBadNonce", "VRFKeyBadLeaderValue", "WrongGenesisDelegate"]
    assert all(c["agree"] and c["reference"][0] == c["lane"] for c in cases)
    # under both leader rules: the nonce proof on a lottery lane, the
    # leader proof and the wrong delegate on overlay lanes
    by = {c["corrupted"]: c["overlay_lane"] for c in cases}
    assert by["vrf-eta-proof"] is False
    assert by["vrf-leader-proof"] is True
    assert by["overlay-wrong-delegate"] is True
    # the reference verified every header's four signatures at this size
    assert judged["reference_crypto_verified"] == judged["reference_headers"]
    assert 0 < judged["reference_overlay_headers"] \
        < judged["reference_headers"]
    assert judged["reference_issuers"] >= 3


def test_chain_differs_by_seed_and_carries_two_certificates(on_cpu):
    from benchmark.manifest import Manifest
    from benchmark.reference import tpraos as ref

    m = Manifest()
    a = replay_tpraos.make_inputs(m.cell(CELL), SEED, True)
    b = replay_tpraos.make_inputs(m.cell(CELL), SEED + 1, True)
    ha, hb = (ref.read_chain(x.path) for x in (a, b))
    assert ha[0].vk_cold != hb[0].vk_cold
    assert {len(h.vrf_proof) for h in ha} == {80}
    assert {len(h.vrf_leader_proof) for h in ha} == {80}
    assert all(h.vrf_output != h.vrf_leader_output for h in ha)
    assert len(a.rparams.gen_delegs) == 7 and len(a.pools) == 8


def test_state_returned_unchanged_is_not_correct(on_cpu, capsys, monkeypatch):
    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        return dataclasses.replace(r, state=state)

    _plant(monkeypatch, fault)
    res, _, _ = _run(capsys)
    assert res["correct"] is False
    assert res["compared"]["state_mismatches"]["value"] > 0


def test_half_the_lanes_left_out_is_not_correct(on_cpu, capsys, monkeypatch):
    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        if r.error is not None and r.n_valid >= len(hvs) // 2:
            return real(params, lv, state,
                        hvs[:r.n_valid], **kw)  # the wrong header unseen
        return r

    _plant(monkeypatch, fault)
    res, _, _ = _run(capsys)
    assert res["correct"] is False
    assert res["compared"]["wrong_header_mismatches"]["value"] == 5


def test_an_altered_answer_is_not_correct(on_cpu, capsys, monkeypatch):
    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        return dataclasses.replace(r, n_valid=max(0, r.n_valid - 1))

    _plant(monkeypatch, fault)
    res, _, _ = _run(capsys)
    assert res["correct"] is False and res["failed"] > 0
    assert res["compared"]["n_valid_gap"]["value"] >= 1


def test_the_control_is_not_correct(on_cpu, capsys, monkeypatch):
    """The control at a size a test run can hold: a path that leaves the
    NONCE proof's check out accepts the header whose nonce proof is wrong,
    and no other. (On the chip `skip_nonce_proof_check` forces the row of
    `finish_tp`; the native stand-in has no such row, so the same
    guarantee is broken one level up.)"""
    from ouroboros_consensus_tpu.protocol import tpraos

    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        if isinstance(r.error, tpraos.VRFKeyBadNonce):
            return real(params, lv, state, hvs[:r.n_valid], **kw)
        return r

    _plant(monkeypatch, fault)
    res, _, _ = _run(capsys)
    assert res["correct"] is False
    assert res["compared"]["wrong_header_mismatches"]["value"] == 1
    assert res["compared"]["state_mismatches"]["value"] == 0
    assert callable(replay_tpraos.skip_nonce_proof_check)


def test_no_chip_is_no_result(capsys):
    rc = brun.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                    "--trace", "0"])
    out, _ = capsys.readouterr()
    assert rc == 3
    assert "correct" not in out.strip().splitlines()[-1]


def test_a_program_without_tpraos_fails_cleanly(monkeypatch, capsys):
    """The parent commit under this PR's benchmark files: exit 1, at once,
    no result."""
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    monkeypatch.delattr(synth, "make_tpraos")
    rc = brun.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                    "--trace", "0", "--cpu-rehearsal"])
    out, _ = capsys.readouterr()
    assert rc == 1
    assert "correct" not in out.strip().splitlines()[-1]
