"""`correct` has to come out false when the timed path is broken underneath.

These tests skip the harness's look for a chip and drive the rest of a run
(`traffic/replay.run` through `run.main`) at a tiny size on the CPU. The
program's native verifier stands in for the device path (the XLA twin of the
device path costs minutes of XLA:CPU compile per header layout); the faults
are planted where the program hands its answers over,
`protocol.batch.validate_chain`, which both `revalidate` and the control's
corrupted windows go through, whatever backend runs underneath.
"""

import dataclasses
import json

import pytest

from benchmark import control, run as brun
from benchmark.traffic import replay

SEED = 2_400_000_123  # more than 32 signed bits hold


@pytest.fixture()
def on_cpu(monkeypatch):
    monkeypatch.setattr(replay, "BACKEND", "native")
    monkeypatch.setattr(replay, "nothing_hid_the_chip", lambda *a, **k: {})


def _run(capsys, cell="replay-bc-2epoch", seed=SEED):
    rc = brun.main(["--workload", cell, "--seed", str(seed), "--seconds",
                    "0.2", "--trace", "0", "--cpu-rehearsal"])
    out, err = capsys.readouterr()
    lines = [json.loads(x) for x in out.strip().splitlines()]
    last = lines[-1]
    assert rc == 2 and last["line"] == "rehearsal"  # never a result line
    window = next(x for x in lines if x["line"] == "window")
    return last["would_be"], err, window


def _plant(monkeypatch, fault):
    from ouroboros_consensus_tpu.protocol import batch as pbatch

    real = pbatch.validate_chain

    def broken(params, lview_for_epoch, state, hvs, **kw):
        return fault(real, params, lview_for_epoch, state, hvs, kw)

    monkeypatch.setattr(pbatch, "validate_chain", broken)


@pytest.mark.parametrize("cell", ["replay-bc-2epoch",
                                  "replay-draft03-2epoch"])
def test_sound_run_is_correct(on_cpu, capsys, cell):
    res, err, window = _run(capsys, cell)
    assert res["correct"] is True and res["failed"] == 0
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["compared"].values())
    # each number compared stands beside its limit in the last lines
    assert "compared wrong_header_mismatches: value 0 limit 0" in err
    assert err.strip().splitlines()[-1] == "correct: True"
    assert list(res)[-1] == "compared"
    # the rate is all the headers of the window over all its wall
    assert res["metrics"]["replay_headers_per_s"]["value"] == \
        pytest.approx(res["attempted"] / window["seconds"], rel=1e-3)
    assert res["attempted"] == 12 * window["replays"] >= 12


def test_chain_differs_by_seed_and_proof_format(on_cpu, capsys):
    from benchmark.manifest import Manifest
    from benchmark.reference import praos as ref

    m = Manifest()
    a = replay.make_inputs(m.cell("replay-bc-2epoch"), SEED, True)
    b = replay.make_inputs(m.cell("replay-bc-2epoch"), SEED + 1, True)
    c = replay.make_inputs(m.cell("replay-draft03-2epoch"), SEED, True)
    ha, hb, hc = (ref.read_chain(x.path) for x in (a, b, c))
    assert ha[0].vk_cold != hb[0].vk_cold
    assert {len(h.vrf_proof) for h in ha} == {128}
    assert {len(h.vrf_proof) for h in hc} == {80}
    assert "OCT_VRF_BATCH" not in __import__("os").environ


def test_state_returned_unchanged_is_not_correct(on_cpu, capsys, monkeypatch):
    """A step that returns its state unchanged: the nonce carry is lost."""
    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        return dataclasses.replace(r, state=state)

    _plant(monkeypatch, fault)
    res, _, _ = _run(capsys)
    assert res["correct"] is False
    assert res["compared"]["state_mismatches"]["value"] > 0


def test_half_the_lanes_left_out_is_not_correct(on_cpu, capsys, monkeypatch):
    """Half of the batch left out: the upper half of a window is taken to
    be valid unseen, so a wrong header there is accepted."""
    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        if r.error is not None and r.n_valid >= len(hvs) // 2:
            return real(params, lv, state,
                        hvs[:r.n_valid], **kw)  # the wrong header unseen
        return r

    _plant(monkeypatch, fault)
    res, _, _ = _run(capsys)
    assert res["correct"] is False
    assert res["compared"]["wrong_header_mismatches"]["value"] == 3


def test_an_altered_answer_is_not_correct(on_cpu, capsys, monkeypatch):
    """An answer altered where it is produced: one header too few."""
    def fault(real, params, lv, state, hvs, kw):
        r = real(params, lv, state, hvs, **kw)
        return dataclasses.replace(r, n_valid=max(0, r.n_valid - 1))

    _plant(monkeypatch, fault)
    res, _, _ = _run(capsys)
    assert res["correct"] is False and res["failed"] > 0
    assert res["compared"]["n_valid_gap"]["value"] >= 1


def test_the_control_is_not_correct(on_cpu, capsys, monkeypatch):
    """The control at a size a test run can hold: a path that leaves the
    VRF proof check out accepts the header whose proof is wrong. (On the
    chip `control.skip_vrf_check` forces the finish stage's ok_vrf row; the
    native stand-in has no such row, so the same guarantee is broken one
    level up.)"""
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
    assert callable(control.skip_vrf_check)


def test_no_chip_is_no_result(capsys):
    rc = brun.main(["--workload", "replay-bc-2epoch", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    out, _ = capsys.readouterr()
    assert rc == 3
    assert "correct" not in out.strip().splitlines()[-1]


def test_set_up_has_every_program_stored():
    """While set-up replays, the program's note of first executes remembers
    none (so its write-back stores each program it builds, not a stage's
    first alone); afterwards the program's own note is back, with what
    set-up executed."""
    from ouroboros_consensus_tpu.ops.pk import kernels

    before = set(kernels._FIRST_EXEC)
    try:
        kernels._FIRST_EXEC.add("ed@b8")
        with replay.every_program_stored() as on:
            assert on
            assert "ed@b8" not in kernels._FIRST_EXEC
            kernels._FIRST_EXEC.add("kes@b8")
            assert "kes@b8" not in kernels._FIRST_EXEC
        assert type(kernels._FIRST_EXEC) is set
        assert {"ed@b8", "kes@b8"} <= kernels._FIRST_EXEC
    finally:
        kernels._FIRST_EXEC.clear()
        kernels._FIRST_EXEC.update(before)
