"""`correct` of the kind `replay_cardano` has to come out false when the
timed path is broken underneath, as `test_correct.py` holds for the plain
kind.

The same drive: the harness's look for a chip skipped, the rest of a run
(`traffic/replay_cardano.run` through `run.main`) at the mix's toy scale on
the CPU (a 40-slot Byron epoch, 120-slot epochs after, k = 10: both hard
forks inside 150 headers), the program's native verifier standing in for
the device path (`backend="native"`: one Ed25519 check a Byron header,
`oc_validate_tpraos` / `oc_validate_praos` after), the faults planted where
the program hands its answers over, `protocol.batch.validate_chain`.
"""

import dataclasses
import json

import pytest

from benchmark import run as brun
from benchmark.traffic import replay, replay_cardano

SEED = 2_400_000_139  # more than 32 signed bits hold
CELL = "replay-cardano-3era"
KINDS = ["pbft-signature", "pbft-not-delegate", "pbft-threshold",
         "overlay-wrong-delegate", "vrf-leader-proof", "babbage-stale-nonce"]


@pytest.fixture()
def on_cpu(monkeypatch):
    # `replay_once` is `replay`'s and reads its BACKEND, as the window
    # validation here does
    monkeypatch.setattr(replay, "BACKEND", "native")
    monkeypatch.setattr(replay_cardano, "nothing_hid_the_chip",
                        lambda *a, **k: {})


def _run(capsys, seed=SEED):
    rc = brun.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    "0.2", "--trace", "0", "--cpu-rehearsal"])
    out, err = capsys.readouterr()
    lines = [json.loads(x) for x in out.strip().splitlines()]
    last = lines[-1]
    assert rc == 2 and last["line"] == "rehearsal"  # never a result line
    judged = next(x for x in lines if x["line"] == "judged")
    window = next(x for x in lines if x["line"] == "window")
    return last["would_be"], err, judged, window


def _plant(monkeypatch, fault):
    from ouroboros_consensus_tpu.protocol import batch as pbatch

    real = pbatch.validate_chain

    def broken(params, lview_for_epoch, state, hvs, **kw):
        return fault(real, params, lview_for_epoch, state, hvs, kw)

    monkeypatch.setattr(pbatch, "validate_chain", broken)


def test_sound_run_is_correct(on_cpu, capsys):
    res, err, judged, window = _run(capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["compared"].values())
    assert err.strip().splitlines()[-1] == "correct: True"
    # the chain crosses both hard forks, an EBB opens Byron
    assert judged["reference_crossings"] == [[0, 1], [1, 2]]
    assert window["crossings"] == [[0, 1], [1, 2]]
    assert all(n > 0 for n in judged["reference_headers_per_era"])
    assert judged["reference_ebbs"] == 1
    cases = judged["wrong_header_cases"]
    assert [c["corrupted"] for c in cases] == KINDS
    # each refused by the error of its own rule, in its own era
    assert [c["reference"][1][0] for c in cases] == [
        "PBftInvalidSignature", "PBftNotGenesisDelegate",
        "PBftExceededSignThreshold", "WrongGenesisDelegate",
        "VRFKeyBadLeaderValue", "VRFKeyBadProof"]
    assert [c["era"] for c in cases] == [0, 0, 0, 1, 1, 2]
    assert all(c["agree"] for c in cases)
    # a single-lane corruption at its own lane; the threshold at the
    # first lane over, at or after the first re-signed one
    assert all(c["reference"][0] == c["lane"] for c in cases
               if c["corrupted"] != "pbft-threshold")
    thr = cases[2]
    assert thr["reference"][0] >= thr["lane"]
    assert thr["reference"][1][1]["signed"] == \
        thr["reference"][1][1]["allowed"] + 1
    by = {c["corrupted"]: c["overlay_lane"] for c in cases}
    assert by["overlay-wrong-delegate"] is True
    assert by["vrf-leader-proof"] is False
    # the reference verified every header's signatures at this size
    assert judged["reference_crypto_verified"] == \
        judged["reference_headers"] - judged["reference_ebbs"]


def test_chain_differs_by_seed_and_tags_its_eras(on_cpu):
    from benchmark.manifest import Manifest
    from benchmark.reference import cardano as ref

    m = Manifest()
    a = replay_cardano.make_inputs(m.cell(CELL), SEED, True)
    b = replay_cardano.make_inputs(m.cell(CELL), SEED + 1, True)
    ha, hb = (ref.read_chain(x.path) for x in (a, b))
    assert [e for e, _h in ha] == sorted(e for e, _h in ha)
    assert {e for e, _h in ha} == {0, 1, 2}
    by = [h for e, h in ha if e == 0]
    assert by[0].ebb and not any(h.ebb for h in by[1:])
    assert {len(h.signed_bytes) for h in by[1:]} == {255}
    assert ha[1][1].delegate_vk != hb[1][1].delegate_vk
    sh = [h for e, h in ha if e == 1]
    assert {len(h.vrf_leader_proof) for h in sh} == {80}
    assert {len(h.vrf_proof) for e, h in ha if e == 2} == {80}
    assert a.rparams.genesis_nonce != b.rparams.genesis_nonce


def test_state_returned_unchanged_is_not_correct(on_cpu, capsys, monkeypatch):
    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        return dataclasses.replace(r, state=state)

    _plant(monkeypatch, fault)
    res, _, _, _ = _run(capsys)
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
    res, _, _, _ = _run(capsys)
    assert res["correct"] is False
    assert res["compared"]["wrong_header_mismatches"]["value"] == 6


def test_an_altered_answer_is_not_correct(on_cpu, capsys, monkeypatch):
    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        return dataclasses.replace(r, n_valid=max(0, r.n_valid - 1))

    _plant(monkeypatch, fault)
    res, _, _, _ = _run(capsys)
    assert res["correct"] is False and res["failed"] > 0
    assert res["compared"]["n_valid_gap"]["value"] >= 1


def test_the_control_is_not_correct(on_cpu, capsys, monkeypatch):
    """The control at a size a test run can hold: a path that leaves the
    Byron signature check out accepts the header whose signature is wrong,
    and no other. (On the chip `skip_byron_signature_check` forces the row
    of `finish_pbft`; the native stand-in has no such row, so the same
    guarantee is broken one level up.)"""
    from ouroboros_consensus_tpu.protocol.instances import (
        PBftInvalidSignature)

    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        if isinstance(r.error, PBftInvalidSignature):
            return real(params, lv, state, hvs[:r.n_valid], **kw)
        return r

    _plant(monkeypatch, fault)
    res, _, _, _ = _run(capsys)
    assert res["correct"] is False
    assert res["compared"]["wrong_header_mismatches"]["value"] == 1
    assert res["compared"]["state_mismatches"]["value"] == 0
    assert callable(replay_cardano.skip_byron_signature_check)


def test_no_chip_is_no_result(capsys):
    rc = brun.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                    "--trace", "0"])
    out, _ = capsys.readouterr()
    assert rc == 3
    assert "correct" not in out.strip().splitlines()[-1]


def test_a_program_without_the_cardano_path_fails_cleanly(monkeypatch,
                                                          capsys):
    """The parent commit under this PR's benchmark files: exit 1, at once,
    no result."""
    import sys

    from ouroboros_consensus_tpu import hardfork

    monkeypatch.delattr(hardfork, "cardano", raising=False)
    monkeypatch.setitem(sys.modules, "ouroboros_consensus_tpu.hardfork."
                        "cardano", None)
    rc = brun.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                    "--trace", "0", "--cpu-rehearsal"])
    out, _ = capsys.readouterr()
    assert rc == 1
    assert "correct" not in out.strip().splitlines()[-1]
