"""Kind `replay_stake` at test size: a sound run over a chain of several
issuers is correct, each planted fault is not, and every corrupted header is
its own issuer's. As `test_correct.py`: on the CPU, the program's native
verifier standing in for the device path, the faults planted at
`protocol.batch.validate_chain`. A rehearsal's child elects for itself on
the host, so no chip is needed to forge."""

import dataclasses
import json

import pytest

from benchmark import run as brun
from benchmark.manifest import Manifest
from benchmark.reference import praos as ref
from benchmark.traffic import replay, replay_stake

SEED = 2_400_000_321
CELL = "replay-stakepools-2epoch"


@pytest.fixture()
def on_cpu(monkeypatch):
    # `replay_once` and `_validate_window` are `replay`'s and read its BACKEND
    monkeypatch.setattr(replay, "BACKEND", "native")
    monkeypatch.setattr(replay_stake, "nothing_hid_the_chip",
                        lambda *a, **k: {})


def _run(capsys, seed=SEED):
    rc = brun.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    "0.2", "--trace", "0", "--cpu-rehearsal"])
    out, err = capsys.readouterr()
    lines = [json.loads(x) for x in out.strip().splitlines()]
    assert rc == 2 and lines[-1]["line"] == "rehearsal"
    return lines[-1]["would_be"], err, {x["line"]: x for x in lines}


def _plant(monkeypatch, fault):
    from ouroboros_consensus_tpu.protocol import batch as pbatch

    real = pbatch.validate_chain

    def broken(params, lview_for_epoch, state, hvs, **kw):
        return fault(real, params, lview_for_epoch, state, hvs, kw)

    monkeypatch.setattr(pbatch, "validate_chain", broken)


def test_sound_run_is_correct_and_holds_several_issuers(on_cpu, capsys):
    res, err, lines = _run(capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["compared"].values())
    assert err.strip().splitlines()[-1] == "correct: True"
    judged = lines["judged"]
    assert judged["reference_issuers"] >= 3
    assert judged["reference_counters"] == judged["reference_issuers"]
    for case in judged["wrong_header_cases"]:
        assert case["agree"] and case["issuer_rank"] != 1
        assert case["window_issuers"] >= 3
    assert lines["chain"]["pools"] == 8
    assert res["metrics"]["replay_headers_per_s"]["value"] == \
        pytest.approx(res["attempted"] / lines["window"]["seconds"],
                      rel=1e-3)


def test_the_chain_is_many_pools_by_the_configured_stake(on_cpu):
    cell = Manifest().cell(CELL)
    inp = replay_stake.make_inputs(cell, SEED, True)
    headers = ref.read_chain(inp.path)
    keys = {p.vk_cold for p in inp.pools}
    assert len(inp.pools) == 8
    assert {h.vk_cold for h in headers} <= keys
    assert len({h.vk_cold for h in headers}) >= 6  # of 300 blocks, 8 pools
    assert sum(s for s, _ in inp.pool_distr.values()) == 1
    # the pool of rank r is make_pool(seed + r - 1): the next seed moves
    # every pool up a rank, a far one shares none
    nxt = replay_stake.make_inputs(cell, SEED + 1, True)
    assert nxt.pools[0].vk_cold == inp.pools[1].vk_cold
    assert [h.vk_cold for h in ref.read_chain(nxt.path)] != \
        [h.vk_cold for h in headers]
    far = replay_stake.make_inputs(cell, SEED + 1000, True)
    assert not keys & {p.vk_cold for p in far.pools}


def test_a_corrupted_header_signed_by_another_pool_is_caught(on_cpu, capsys,
                                                              monkeypatch):
    """`replay.corrupt` handed the wrong pool (what `replay`'s own
    `wrong_header_cases` would do here) signs the body with a key the
    header does not name: the reference then reports the KES signature,
    not the proof, so the case is no test of the VRF check. Here the
    issuer signs, and the reference's first failure is the proof."""
    res, _, lines = _run(capsys)
    case = next(c for c in lines["judged"]["wrong_header_cases"]
                if c["corrupted"] == "vrf-proof")
    assert case["reference"][1][0] == "VRFKeyBadProof"


def test_state_returned_unchanged_is_not_correct(on_cpu, capsys, monkeypatch):
    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        return dataclasses.replace(r, state=state)

    _plant(monkeypatch, fault)
    res, _, _ = _run(capsys)
    assert res["correct"] is False
    assert res["compared"]["state_mismatches"]["value"] > 0


def test_a_lost_counter_is_not_correct(on_cpu, capsys, monkeypatch):
    """One issuer's OCert counter dropped from the final state: every
    nonce still agrees, and the state compared holds every counter."""
    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        counters = dict(r.state.ocert_counters)
        if len(counters) > 1:
            counters.pop(sorted(counters)[-1])
        return dataclasses.replace(
            r, state=dataclasses.replace(r.state, ocert_counters=counters))

    _plant(monkeypatch, fault)
    res, _, _ = _run(capsys)
    assert res["correct"] is False
    assert res["compared"]["state_mismatches"]["value"] > 0
    assert res["compared"]["n_valid_gap"]["value"] == 0


def test_the_control_is_not_correct(on_cpu, capsys, monkeypatch):
    from ouroboros_consensus_tpu.protocol import praos

    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        if isinstance(r.error, praos.VRFKeyBadProof):
            return real(params, lv, state, hvs[:r.n_valid], **kw)
        return r

    _plant(monkeypatch, fault)
    res, _, _ = _run(capsys)
    assert res["correct"] is False
    assert res["compared"]["wrong_header_mismatches"]["value"] == 1
    assert res["compared"]["state_mismatches"]["value"] == 0
