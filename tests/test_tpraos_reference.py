"""The TPraos program against the benchmark's plain reference
(`benchmark/reference/tpraos.py`, which imports nothing of the program), at
a size a test run can hold and with EVERY signature verified by both.

  * seeded `db_synthesizer` chains at d in {0, 1/2, 1} with 2-3 genesis
    delegates, replayed by `db_analyser.revalidate` (the C++ verifier
    standing in for the device, and the sequential host fold): the count of
    valid headers, the first error with its fields and the whole final
    state are the reference's;
  * six wrong headers, each refused at its own index with the reference's
    error: the five corruptions of the cell `replay-tpraos-2epoch` and a
    block in an INACTIVE overlay slot;
  * `mk_seed` and the overlay arithmetic against vectors worked by hand,
    the columnar overlay pass against the scalar rule, slot by slot;
  * the forged chain is byte for byte the same from the per-slot loop and
    from the pipelined forge; a TPraos chain carries its own config, and
    the tools take the protocol from it.
"""

import dataclasses
import hashlib
import os
from fractions import Fraction

import numpy as np
import pytest

from benchmark.reference import tpraos as ref
from benchmark.traffic import replay_tpraos as kind
from benchmark.traffic.replay import error_doc, state_doc
from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos, tpraos
from ouroboros_consensus_tpu.protocol.views import ViewColumns
from ouroboros_consensus_tpu.testing import fixtures
from ouroboros_consensus_tpu.tools import config as tconfig
from ouroboros_consensus_tpu.tools import db_analyser as ana
from ouroboros_consensus_tpu.tools import db_synthesizer as synth

PP = praos.PraosParams(
    slots_per_kes_period=100, max_kes_evolutions=62, security_param=5,
    active_slot_coeff=Fraction(1, 2), epoch_length=120, kes_depth=3,
)
RP = ref.Params(100, 62, 5, Fraction(1, 2), 120, 3)
SLOTS = 300  # two epochs and a half: the nonce rotates twice


def _deployment(d, n_delegs, seed=7):
    pool = fixtures.make_pool(seed, kes_depth=3)
    params, creds, lview = synth.make_tpraos(
        PP, [pool], fixtures.make_ledger_view([pool]), n_delegs, d,
        first_seed=seed + 1000)
    rparams = ref.TParams(
        RP, Fraction(d),
        tuple((g.vk_cold, g.vrf_key_hash) for g in lview.gen_delegs))
    distr = {k: (e.stake, e.vrf_key_hash)
             for k, e in lview.pool_distr.items()}
    return params, creds, lview, rparams, distr


@pytest.fixture(scope="module", params=[
    (Fraction(0), 2), (Fraction(1, 2), 3), (Fraction(1), 2),
    (Fraction(3, 10), 3),
], ids=lambda p: f"d={p[0]}-{p[1]}delegs")
def chain(request, tmp_path_factory):
    d, n = request.param
    params, creds, lview, rparams, distr = _deployment(d, n)
    path = str(tmp_path_factory.mktemp("tpraos") / "db")
    res = synth.synthesize(path, params, creds, lview,
                           synth.ForgeLimit(slots=SLOTS), chunk_size=120)
    headers = ref.read_chain(path)
    assert len(headers) == res.n_blocks > 60
    return d, path, params, creds, lview, rparams, distr, headers, res


def test_the_replay_is_the_references(chain):
    d, path, params, _creds, lview, rparams, distr, headers, forged = chain
    want = ref.replay(rparams, distr, headers)  # every signature verified
    assert want.error is None and want.n_crypto == len(headers)
    overlay = [ref.overlay(rparams, h.slot) is not None for h in headers]
    if d == 0:
        assert not any(overlay)
    elif d == 1:
        assert all(overlay)
    else:
        assert any(overlay) and not all(overlay)
    for backend in ("native", "host"):
        got = ana.revalidate(path, params, lview, backend=backend,
                             validate_all="stream", max_batch=32)
        assert got.n_valid == want.n_valid == got.n_blocks
        assert error_doc(got.error) == want.error
        assert state_doc(got.final_state) == want.state.doc(), backend
        assert type(got.final_state) is tpraos.TPraosState
    assert state_doc(forged.final_state) == want.state.doc()
    # the counters of the pool (where the lottery has slots) and of every
    # delegate that has issued
    assert len(want.state.counters) == len({h.vk_cold for h in headers})


def _inputs(chain):
    """`traffic/replay_tpraos.Inputs` of the test chain: its corruptions
    are the cell's own code."""
    _d, path, params, creds, lview, rparams, distr, headers, _ = chain
    return kind.Inputs(path, params, rparams, creds, lview, distr, 32, None,
                       True, 0.0, len(headers))


def _program(chain, hvs, st0):
    _d, _path, params, _creds, lview, *_ = chain
    cols = ViewColumns.from_views(hvs)
    return pbatch.validate_chain(
        params, lambda _e: lview, st0,
        cols if cols is not None else hvs, max_batch=32, backend="native")


@pytest.mark.parametrize("what", [
    "ocert-signature", "kes-signature", "vrf-eta-proof", "vrf-leader-proof",
    "overlay-wrong-delegate", "inactive-overlay-slot",
])
def test_a_wrong_header_is_refused_with_the_references_error(chain, what):
    d, _path, params, creds, lview, rparams, distr, headers, _ = chain
    overlay = [ref.overlay(rparams, h.slot) is not None for h in headers]
    if d == 0 and what in ("overlay-wrong-delegate",
                           "inactive-overlay-slot"):
        # a fully decentralised chain has no overlay slot to break
        assert not any(overlay)
        assert all(ref.overlay(rparams, s) is None for s in range(SLOTS))
        return
    inp = _inputs(chain)
    # a run of one CBOR layout inside one epoch, and a lane of the rule
    # the corruption is to meet
    start = 25
    run = [i for i in range(start, len(headers))
           if headers[i].slot // 120 == headers[start].slot // 120
           and len(headers[i].signed_bytes)
           == len(headers[start].signed_bytes)]
    want_overlay = {"overlay-wrong-delegate": True,
                    "vrf-leader-proof": any(overlay[i] for i in run)}.get(
                        what)
    lane = next(i for i in run[3:]
                if want_overlay is None or overlay[i] == want_overlay)
    before = ref.replay(rparams, distr, headers[:start], crypto_at=())
    eta0 = ref.tick(rparams, headers[start].slot, before.state).epoch_nonce
    if what == "inactive-overlay-slot":
        # the pool forges where nobody may: the first inactive overlay
        # slot after the lane's predecessor
        prev = headers[lane - 1]
        slot = next(s for s in range(prev.slot + 1, prev.slot + 50)
                    if ref.overlay(rparams, s) == ("inactive",))
        assert slot // 120 == prev.slot // 120
        body, _ = ref._cbor_item(headers[lane].signed_bytes, 0)
        blk = forge_block(
            params, creds[0], slot=slot, block_no=body[0],
            prev_hash=headers[lane].prev_hash, epoch_nonce=eta0,
            is_leader=tpraos.prove_certificates(creds[0].vrf_seed, slot,
                                                eta0))
        bad, _end = ref._header_at(blk.bytes_, 0)
    else:
        bad = kind.corrupt(what, headers[lane], inp, eta0)
    window = headers[start:lane] + [bad]
    want = ref.replay(rparams, distr, window, st=before.state)
    assert want.n_valid == lane - start
    assert want.error[0] == {
        "ocert-signature": "InvalidSignatureOCERT",
        "kes-signature": "InvalidKesSignatureOCERT",
        "vrf-eta-proof": "VRFKeyBadNonce",
        "vrf-leader-proof": "VRFKeyBadLeaderValue",
        "overlay-wrong-delegate": "WrongGenesisDelegate",
        "inactive-overlay-slot": "NonActiveSlot",
    }[what]
    got = _program(chain, [kind._to_view(h) for h in window],
                   kind._to_state(before.state))
    assert got.n_valid == want.n_valid
    assert error_doc(got.error) == want.error
    assert state_doc(got.state) == want.state.doc()
    # and the sequential host fold says the same
    st = kind._to_state(before.state)
    for i, h in enumerate(window):
        hv = kind._to_view(h)
        try:
            st = tpraos.update(params, hv, hv.slot,
                               tpraos.tick(params, lview, hv.slot, st))
        except praos.PraosValidationError as e:
            assert (i, error_doc(e)) == (want.n_valid, want.error)
            break
    else:
        pytest.fail("the host fold accepted the wrong header")


# ---------------------------------------------------------------------------
# vectors worked by hand
# ---------------------------------------------------------------------------


def _h(b: bytes) -> bytes:
    return hashlib.blake2b(b, digest_size=32).digest()


def test_mk_seed_by_hand():
    seed_eta, seed_l = _h(bytes(8)), _h(bytes(7) + b"\x01")
    assert (tpraos.SEED_ETA, tpraos.SEED_L) == (seed_eta, seed_l)
    assert (ref.SEED_ETA, ref.SEED_L) == (seed_eta, seed_l)
    eta0 = bytes(range(32))
    slot = 0x0102030405
    base = _h(bytes([0, 0, 0, 1, 2, 3, 4, 5]) + eta0)
    for mk in (tpraos.mk_seed, ref.mk_seed):
        assert mk(seed_eta, slot, eta0) == bytes(
            a ^ b for a, b in zip(base, seed_eta))
        assert mk(seed_l, slot, eta0) == bytes(
            a ^ b for a, b in zip(base, seed_l))
        # a neutral nonce contributes no bytes
        assert mk(seed_l, 7, None) == bytes(
            a ^ b for a, b in zip(_h(bytes(7) + b"\x07"), seed_l))
    # the two inputs of one header differ in exactly seedEta XOR seedL
    x = bytes(a ^ b for a, b in zip(tpraos.mk_seed(seed_eta, 9, eta0),
                                    tpraos.mk_seed(seed_l, 9, eta0)))
    assert x == bytes(a ^ b for a, b in zip(seed_eta, seed_l))


def test_overlay_arithmetic_by_hand():
    """d = 3/10, f = 1/2 (ascInv 2), 3 delegates, an epoch of 120 slots:
    ceil(i * 3/10) steps at i = 0, 3, 6, 10, 13, 16, 20, ... (positions
    0, 1, 2, 3, ...); the even positions are active, round-robin."""
    params, _creds, lview, rparams, _ = _deployment(Fraction(3, 10), 3)
    want = {0: ("active", 0), 3: ("inactive",), 6: ("active", 1),
            10: ("inactive",), 13: ("active", 2), 16: ("inactive",),
            20: ("active", 0), 1: None, 2: None, 4: None, 9: None, 19: None}
    for i, w in want.items():
        for slot in (i, 120 + i, 7 * 120 + i):  # every epoch alike
            assert ref.overlay(rparams, slot) == w, (slot, w)
            a = tpraos.overlay_slot_assignment(params, 3, slot)
            assert a == {None: None, ("inactive",): (False, None)}.get(
                w, (True, w[-1]) if w else None), slot
    # d = 1: every slot an overlay slot, every second one active
    p1, _c, _l, r1, _ = _deployment(Fraction(1), 2)
    assert [ref.overlay(r1, s) for s in range(5)] == [
        ("active", 0), ("inactive",), ("active", 1), ("inactive",),
        ("active", 0)]
    # an epoch holds ceil(120 d) overlay slots
    for d in (Fraction(3, 10), Fraction(1, 2), Fraction(1), Fraction(1, 7)):
        _p, _c, _l, r, _ = _deployment(d, 3)
        n = sum(ref.overlay(r, s) is not None for s in range(120))
        assert n == -(-120 * d.numerator // d.denominator)


@pytest.mark.parametrize("d", [Fraction(0), Fraction(1, 2), Fraction(1),
                               Fraction(3, 10), Fraction(7, 50),
                               Fraction(49, 50)], ids=str)
@pytest.mark.parametrize("n_delegs", [0, 1, 7])
def test_the_columnar_overlay_pass_is_the_scalar_rule(d, n_delegs):
    inner = dataclasses.replace(PP, epoch_length=4320,
                                active_slot_coeff=Fraction(1, 20))
    params = tpraos.TPraosParams(inner, d)
    slots = np.concatenate([np.arange(0, 9000),
                            np.arange(10**9, 10**9 + 2000)])
    kind_col, deleg = tpraos.overlay_columns(params, n_delegs, slots)
    for s, k, j in zip(slots.tolist(), kind_col.tolist(), deleg.tolist()):
        a = tpraos.overlay_slot_assignment(params, n_delegs, s)
        assert (k, j) == {
            None: (tpraos.LOTTERY, -1), (False, None): (tpraos.INACTIVE, -1),
        }.get(a, (tpraos.ACTIVE, a[1] if a else -1)), s


def test_the_512_bit_leader_rule():
    """nat(beta_L) / 2^512 < 1 - (1 - f)^sigma: exact at sigma = 1, and by
    the series elsewhere; the program's bracket holds the reference's
    verdict on both sides."""
    f = Fraction(1, 2)
    half = 1 << 511
    assert ref.wins(half - 1, Fraction(1), f)  # exact in rationals
    assert not ref.wins(half, Fraction(1), f)
    eps = 1 << 400  # 2^-112 of the range: the 128-term series decides to 2^-135
    for rule in (lambda lv, s: ref.wins(lv, s, f),
                 lambda lv, s: tpraos.check_leader_value(
                     lv, s, f, tpraos.LEADER_VALUE_MAX)):
        assert rule(half - eps, Fraction(1))
        assert not rule(half + eps, Fraction(1))
        assert not rule(0, Fraction(0))
    sigma = Fraction(1, 3)
    lo, hi = pbatch.leader_threshold_bracket(sigma, f, 512)
    assert 0 < hi - lo < 1 << 450  # 2^-62 of the range and tighter
    assert ref.wins(lo - 1, sigma, f) and not ref.wins(hi, sigma, f)
    # the 256-bit bracket is the 512-bit one's upper half
    lo256, hi256 = pbatch.leader_threshold_bracket(sigma, f)
    assert (lo >> 256) in (lo256 - 1, lo256, lo256 + 1)
    assert (hi >> 256) in (hi256 - 1, hi256, hi256 + 1)
    rows = pbatch._threshold_rows(Fraction(1), f, 512)
    assert [r.shape for r in rows] == [(64,), (64,)]
    assert int.from_bytes(rows[0].tobytes(), "big") <= half <= int.from_bytes(
        rows[1].tobytes(), "big")


# ---------------------------------------------------------------------------
# the forge, the config
# ---------------------------------------------------------------------------


def _chunks(path):
    imm = os.path.join(path, "immutable")
    return {n: open(os.path.join(imm, n), "rb").read()
            for n in sorted(os.listdir(imm)) if n.endswith(".chunk")}


def test_the_loop_and_the_pipeline_forge_the_same_bytes(tmp_path,
                                                        monkeypatch):
    params, creds, lview, _r, _d = _deployment(Fraction(1, 2), 3, seed=11)
    monkeypatch.setenv("OCT_FORGE_DEVICE", "0")  # the per-slot loop
    a = synth.synthesize(str(tmp_path / "loop"), params, creds, lview,
                         synth.ForgeLimit(blocks=90), chunk_size=120)
    monkeypatch.delenv("OCT_FORGE_DEVICE")  # the batched host pipeline
    b = synth.synthesize(str(tmp_path / "pipe"), params, creds, lview,
                         synth.ForgeLimit(blocks=90), chunk_size=120)
    assert a.n_blocks == b.n_blocks == 90 and a.n_slots == b.n_slots
    assert a.final_state == b.final_state
    assert _chunks(str(tmp_path / "loop")) == _chunks(str(tmp_path / "pipe"))
    # a resumed forge converges on the same chain
    c = synth.synthesize(str(tmp_path / "part"), params, creds, lview,
                         synth.ForgeLimit(blocks=40), chunk_size=120)
    synth._REPLAY_MEMO.clear()  # rebuild the state from the chain itself
    synth.synthesize(str(tmp_path / "part"), params, creds, lview,
                     synth.ForgeLimit(blocks=90), chunk_size=120, resume=True)
    assert c.n_blocks == 40
    assert _chunks(str(tmp_path / "part")) == _chunks(str(tmp_path / "loop"))


@pytest.mark.parametrize("how", ["vrf_backend", "OCT_FORGE_DEVICE"])
def test_a_tpraos_device_forge_is_refused(tmp_path, monkeypatch, how):
    """There is no TPraos leader-value sweep: a caller who asks for the
    chip's forge is told so, not handed the host's in silence."""
    params, creds, lview, _r, _d = _deployment(Fraction(1, 2), 3, seed=11)
    kw = {}
    if how == "vrf_backend":
        kw["vrf_backend"] = "device"
    else:
        monkeypatch.setenv("OCT_FORGE_DEVICE", "1")
    with pytest.raises(ValueError, match="no TPraos device forge"):
        synth.synthesize(str(tmp_path / "db"), params, creds, lview,
                         synth.ForgeLimit(blocks=5), **kw)


def test_a_tpraos_chain_carries_its_own_config(tmp_path, capsys):
    """`db_synthesizer --protocol tpraos` writes the genesis file with
    `genDelegs` and `decentralisation`; `db_analyser` takes the protocol
    from the DB's config and revalidates the chain."""
    db = str(tmp_path / "db")
    synth.main(["--out", db, "--pools", "1", "--kes-depth", "3",
                "--slots", "150", "--protocol", "tpraos", "--delegates",
                "3", "--decentralisation", "1/2"])
    capsys.readouterr()
    cfg = os.path.join(db, "config", "config.json")
    params, lview, pools = tconfig.load_config(cfg)
    assert isinstance(params, tpraos.TPraosParams)
    assert params.decentralization == Fraction(1, 2)
    assert isinstance(lview, tpraos.TPraosLedgerView)
    assert len(lview.gen_delegs) == 3 and len(pools) == 4
    assert [g.vk_cold for g in lview.gen_delegs] == [
        p.vk_cold for p in pools[1:]]
    assert pbatch.rules_of(params).name == "tpraos"
    ana.main(["--db", db, "--backend", "native"])
    out = capsys.readouterr().out
    assert "-> OK" in out and "validated 0/" not in out
    # a Praos chain's config reads as before, and an unknown protocol's
    # error says which ones this tool takes
    p2, l2, _ = tconfig.load_config(tconfig.write_genesis_files(
        str(tmp_path / "praos"), PP,
        fixtures.make_ledger_view([fixtures.make_pool(1, kes_depth=3)])))
    assert type(p2) is praos.PraosParams and pbatch.rules_of(p2).name == "praos"
    import json

    with open(cfg) as f:
        doc = json.load(f)
    doc["Protocol"] = "PBFT"
    with open(cfg, "w") as f:
        json.dump(doc, f)
    with pytest.raises(ValueError, match='"Praos" and "TPraos"'):
        tconfig.load_config(cfg)
